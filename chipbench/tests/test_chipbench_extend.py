"""A cell is added as files only: a configuration, a workload, a request
kind and a per-layer metric, each a new file, and entries in
BENCHMARK.json.  The harness runs it without an edit to any file it had."""
import hashlib
import json

from chipbench_cases import small_scms

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
