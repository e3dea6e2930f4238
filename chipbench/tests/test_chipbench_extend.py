"""A cell is added as files only: a configuration, a workload, a request
kind, a per-layer metric or a design-space schema, each a new file, and
entries in BENCHMARK.json.  The harness runs it without an edit to any
file it had."""
import hashlib
import json

from chipbench_cases import small_scms, small_points

KIND = '''"""price_block: a PriceRequest of ``rows`` consecutive indices."""
import numpy as np

from repro.service import PriceRequest


def shape(params, rng):
    return {"rows": int(params["rows"])}


def make(space_size, params, shape, rng):
    start = int(rng.integers(0, space_size - shape["rows"]))
    return PriceRequest(indices=np.arange(start, start + shape["rows"]))


def rows(req):
    return len(req.indices)


def payload(req, resp, pick):
    return {"pf": resp.result.portfolio_cost[pick]}


def answer(req, ref, pick):
    return {"pf": ref.candidates(np.asarray(req.indices)[pick])["pf"]}


def compare(req, ref, got, pick):
    return [("rel", got["pf"], answer(req, ref, pick)["pf"])]
'''

METRIC = '''"""Rows priced per tick in the traced window."""


def read(ctx):
    c = ctx["counters"]
    return c["rows_priced"] / c["ticks"] if c and c["ticks"] else None
'''


SPACE = '''"""menu: SKUs over an architecture menu listed one by one."""
from harness.reference import spec_system


def build(space_cfg):
    from repro.dse import SKU, DesignSpace

    archs = [tuple(a) for a in space_cfg["archs"]]
    space = DesignSpace(
        skus=tuple(SKU(k["name"], float(k["area"]), float(k["quantity"]))
                   for k in space_cfg["skus"]),
        processes=tuple(dict.fromkeys(a[1] for a in archs)),
        integrations=tuple(dict.fromkeys(a[2] for a in archs
                                         if a[0] > 1)),
        chiplet_counts=tuple(dict.fromkeys(a[0] for a in archs)),
        allow_reuse=False)
    menu = [(c.n_chiplets, c.process, c.integration)
            for c in space.arch_choices()]
    if menu != archs:
        raise ValueError(f"menu {archs} is not the program's {menu}")
    return space


class Decoder:
    def __init__(self, space_cfg):
        self.skus = space_cfg["skus"]
        self.arch = [tuple(a) for a in space_cfg["archs"]]
        self.processes = list(dict.fromkeys(a[1] for a in self.arch))
        self.integrations = list(dict.fromkeys(a[2] for a in self.arch
                                               if a[0] > 1))
        self.size = len(self.arch) ** len(self.skus)

    def candidate(self, i):
        if not 0 <= i < self.size:
            raise IndexError(i)
        digits = []
        for _ in self.skus:
            i, d = divmod(i, len(self.arch))
            digits.append(self.arch[d])
        return tuple(reversed(digits))

    def index(self, cand):
        i = 0
        for c in cand:
            if c not in self.arch:
                return None
            i = i * len(self.arch) + self.arch.index(c)
        return i

    def systems(self, cand):
        return [spec_system({"kind": "soc" if n == 1 else "split",
                             "name": k["name"], "area": k["area"],
                             "process": p, "n": n, "integration": t,
                             "quantity": k["quantity"],
                             "reuse_chiplet": True})
                for k, (n, p, t) in zip(self.skus, cand)]

    @staticmethod
    def swap(cand, process, integration):
        return tuple((n, process, "SoC" if n == 1 else integration)
                     for n, _, _ in cand)
'''


def _digests(root):
    return {p: hashlib.sha1(p.read_bytes()).hexdigest()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_cell_as_files(checkout):
    before = _digests(checkout.bench)
    checkout.write("requests/price_block.py", KIND)
    checkout.write("metrics/rows_per_tick.py", METRIC)
    wl = {"config": "tiny", "why": "test",
          "open": {"rate_per_s": 60.0, "knee_per_s": 75.0,
                   "mix": [{"share": 1.0, "kind": "price_block",
                            "params": {"rows": 8}}]},
          "check": {}}
    checkout.add_cell("tiny.block", "tiny", small_scms(), wl)
    spec = checkout.spec()
    spec["per_layer"].append({"name": "rows_per_tick.lat", "unit": "rows",
                              "better": "higher",
                              "source": "program_counter",
                              "layer": "scheduler", "moves": "p95_ms",
                              "workloads": ["tiny.block"]})
    checkout.save_spec(spec)
    after = _digests(checkout.bench)
    assert all(after[p] == d for p, d in before.items())

    out = checkout.run("tiny.block")
    assert out["correct"], out["checks"]
    assert set(out["metrics"]) == {"p50_ms", "p95_ms", "setup_s"}
    traced = checkout.run("tiny.block", trace=True)
    assert traced["correct"]
    assert traced["metrics"]["rows_per_tick.lat"]["value"] > 0
    json.dumps(traced)


def test_new_space_kind_as_files(checkout):
    """A configuration whose ``space`` names a kind of its own: the kind
    file builds the program's space and decodes the reference's
    candidates, and a cell over it runs correct, what-if grids included."""
    before = _digests(checkout.bench)
    checkout.write("spaces/menu.py", SPACE)
    cfg = small_scms()
    cfg["space"] = {"kind": "menu", "skus": cfg["space"]["skus"],
                    "archs": [[1, "7nm", "SoC"], [2, "7nm", "MCM"],
                              [2, "7nm", "2.5D"], [4, "7nm", "MCM"],
                              [4, "7nm", "2.5D"]]}
    wl = small_points()
    wl["config"] = "tiny_menu"
    wl["open"]["mix"] = [m for m in wl["open"]["mix"]
                         if m["kind"] in ("price", "what_if")]
    checkout.add_cell("tiny_menu.points", "tiny_menu", cfg, wl)
    after = _digests(checkout.bench)
    assert all(after[p] == d for p, d in before.items())

    out = checkout.run("tiny_menu.points")
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
