"""compute_io: the ``grid`` schema with an IO die beside every SKU's
compute chiplets (the paper's Fig. 5 compute/IO product line).

Each SKU of the ``space`` block adds ``io_die`` (``name``, ``area``,
``process``): the IO content on its own node.  The candidate order and
form are the grid's.  A monolithic SoC holds the compute and the IO
content on one die of the compute node; a split holds ``n`` even
compute chiplets plus the IO die; a reuse scheme holds ``count`` copies
of the slice plus the IO die, and a shared package is sized for the
largest system's silicon, IO die included.  The IO die's design is
named by its ``name`` and the integration, so within a candidate the
SKUs that hold the same IO die under the same packaging share it.

``build`` gives the program's space; ``Decoder`` is the reference's own
reading and imports nothing of the program.
"""
import pathlib
from typing import Dict, List

from harness.catalog import load
from harness.reference import TECH, _chip, _system, spec_system

_HERE = pathlib.Path(__file__).resolve()
grid = load(_HERE.parents[1], _HERE.with_name("grid.py"))


def build(space_cfg: Dict):
    from repro.dse import SKU, DesignSpace, IODie

    s = space_cfg
    return DesignSpace(
        skus=tuple(SKU(k["name"], float(k["area"]), float(k["quantity"]),
                       IODie(k["io_die"]["name"], float(k["io_die"]["area"]),
                             k["io_die"]["process"]))
                   for k in s["skus"]),
        processes=tuple(s["processes"]),
        integrations=tuple(s["integrations"]),
        chiplet_counts=tuple(s["chiplet_counts"]),
        allow_reuse=bool(s.get("allow_reuse", True)),
        reuse_package_options=tuple(s.get("reuse_package_options",
                                          [False])),
        reuse_within_sku=bool(s.get("reuse_within_sku", True)))


class Decoder(grid.Decoder):
    """The grid's decoding, with each SKU's IO die in its systems."""

    def __init__(self, cfg: Dict):
        super().__init__(cfg)
        self.io = [s["io_die"] for s in cfg["skus"]]

    def _io_chip(self, i: int, integration: str) -> Dict:
        io = self.io[i]
        name = f"{io['name']}_{integration}"
        return _chip(name, [(f"{name}_modules", float(io["area"]),
                             io["process"])], io["process"], integration)

    def systems(self, cand) -> List[Dict]:
        kind, body = cand
        if kind == "reuse":
            a, p, t, pkg = body
            counts = [self._tiles(s[1], a) for s in self.skus]
            cname = f"reuse_{p}_{t}_{a:g}mm2"
            chip = _chip(cname, [(f"{cname}_modules", a, p)], p, t)
            chips = [[chip] * k + [self._io_chip(i, t)]
                     for i, k in enumerate(counts)]
            pname = parea = None
            if pkg:
                pname = f"{cname}_pkg{max(counts)}s"
                parea = (max(sum(c["area"] for c in cs) for cs in chips)
                         * TECH["integrations"][t]["package_area_factor"])
            return [_system(nm, cs, t, q, pname, parea)
                    for (nm, _, q), cs in zip(self.skus, chips)]
        out = []
        for i, ((nm, area, q), (n, p, t)) in enumerate(zip(self.skus, body)):
            if n == 1:
                out.append(spec_system({
                    "kind": "soc", "name": nm,
                    "area": area + float(self.io[i]["area"]), "process": p,
                    "quantity": q}))
            else:
                split = spec_system({"kind": "split", "name": nm,
                                     "area": area, "process": p, "n": n,
                                     "integration": t, "quantity": q,
                                     "reuse_chiplet": self.within_sku})
                out.append(_system(nm, split["chips"]
                                   + [self._io_chip(i, t)], t, q))
        return out
