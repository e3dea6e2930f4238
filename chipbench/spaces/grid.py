"""grid: the program's ``DesignSpace`` schema.

The ``space`` block lists ``skus`` (``name``, ``area``, ``quantity``),
the ``processes``, ``integrations`` and ``chiplet_counts`` menus, and the
reuse flags ``allow_reuse``, ``reuse_package_options`` and
``reuse_within_sku``.  Every SKU picks one architecture of the same menu
(the monolithic SoC per process where 1 is a count, then an even split
into each count above 1 per process and integration), and one-slice
reuse schemes follow.

``build`` gives the program's space; ``Decoder`` is the reference's own
reading of the same candidate order and imports nothing of the program.
"""
from typing import Dict, List, Optional

from harness.reference import TECH, _chip, _system, spec_system

_REL_TOL = 1e-6


def build(space_cfg: Dict):
    from repro.dse import SKU, DesignSpace

    s = space_cfg
    return DesignSpace(
        skus=tuple(SKU(k["name"], float(k["area"]), float(k["quantity"]))
                   for k in s["skus"]),
        processes=tuple(s["processes"]),
        integrations=tuple(s["integrations"]),
        chiplet_counts=tuple(s["chiplet_counts"]),
        allow_reuse=bool(s.get("allow_reuse", True)),
        reuse_package_options=tuple(s.get("reuse_package_options",
                                          [False])),
        reuse_within_sku=bool(s.get("reuse_within_sku", True)))


class Decoder:
    """Candidate decoding of a design space given as the configuration's
    ``space`` block (SKUs, menus, reuse flags)."""

    def __init__(self, cfg: Dict):
        self.skus = [(s["name"], float(s["area"]), float(s["quantity"]))
                     for s in cfg["skus"]]
        self.processes = list(cfg["processes"])
        self.integrations = list(cfg["integrations"])
        self.counts = sorted(set(cfg["chiplet_counts"]))
        self.within_sku = bool(cfg.get("reuse_within_sku", True))
        self.arch = ([(1, p, "SoC") for p in self.processes]
                     if 1 in self.counts else [])
        self.arch += [(n, p, t) for n in self.counts if n > 1
                      for p in self.processes for t in self.integrations]
        self.reuse = []
        if cfg.get("allow_reuse", True):
            self.reuse = [(a, p, t, bool(pkg)) for a in self._slices()
                          for p in self.processes for t in self.integrations
                          for pkg in cfg.get("reuse_package_options",
                                             [False])]
        self.n_arch = len(self.arch) ** len(self.skus)
        self.size = self.n_arch + len(self.reuse)

    def _tiles(self, area: float, a: float) -> Optional[int]:
        k = area / a
        if abs(k - round(k)) > _REL_TOL * max(k, 1.0) \
                or int(round(k)) not in self.counts:
            return None
        return int(round(k))

    def _slices(self) -> List[float]:
        out: List[float] = []
        for a in sorted({s[1] / n for s in self.skus for n in self.counts},
                        reverse=True):
            if all(self._tiles(s[1], a) for s in self.skus) \
                    and not any(abs(a - b) <= _REL_TOL * a for b in out):
                out.append(a)
        return out

    def candidate(self, i: int):
        """``("arch", ((n, process, integration), ...))`` or
        ``("reuse", (slice_mm2, process, integration, package_reuse))``."""
        if not 0 <= i < self.size:
            raise IndexError(i)
        if i >= self.n_arch:
            return ("reuse", self.reuse[i - self.n_arch])
        digits = []
        for _ in self.skus:
            i, d = divmod(i, len(self.arch))
            digits.append(self.arch[d])
        return ("arch", tuple(reversed(digits)))

    def index(self, cand) -> Optional[int]:
        """Index of a candidate, or None where it is not in the space."""
        kind, body = cand
        if kind == "reuse":
            return (self.n_arch + self.reuse.index(body)
                    if body in self.reuse else None)
        i = 0
        for c in body:
            if c not in self.arch:
                return None
            i = i * len(self.arch) + self.arch.index(c)
        return i

    def systems(self, cand) -> List[Dict]:
        kind, body = cand
        if kind == "reuse":
            a, p, t, pkg = body
            counts = [self._tiles(s[1], a) for s in self.skus]
            cname = f"reuse_{p}_{t}_{a:g}mm2"
            chip = _chip(cname, [(f"{cname}_modules", a, p)], p, t)
            pname = parea = None
            if pkg:
                pname = f"{cname}_pkg{max(counts)}s"
                parea = (chip["area"] * max(counts)
                         * TECH["integrations"][t]["package_area_factor"])
            return [_system(nm, [chip] * k, t, q, pname, parea)
                    for (nm, _, q), k in zip(self.skus, counts)]
        out = []
        for (nm, area, q), (n, p, t) in zip(self.skus, body):
            if n == 1:
                out.append(spec_system({"kind": "soc", "name": nm,
                                        "area": area, "process": p,
                                        "quantity": q}))
            else:
                out.append(spec_system({"kind": "split", "name": nm,
                                        "area": area, "process": p, "n": n,
                                        "integration": t, "quantity": q,
                                        "reuse_chiplet": self.within_sku}))
        return out

    @staticmethod
    def swap(cand, process: str, integration: str):
        """The what-if grid's move: same architecture, another node and
        packaging (a monolithic SKU stays SoC)."""
        kind, body = cand
        if kind == "reuse":
            return ("reuse", (body[0], process, integration, body[3]))
        return ("arch", tuple((n, process, "SoC" if n == 1 else integration)
                              for n, _, _ in body))
