"""price: a ``PriceRequest`` of candidate indices.

params: ``rows`` (a size, see ``harness.traffic.draw_rows``) and
``distinct`` (draw the indices without replacement).  Served rows are
compared with the reference at the sampled positions ``pick``.
"""
import numpy as np

from harness.traffic import draw_rows, indices
from repro.service import PriceRequest


def shape(params, rng):
    return {"rows": draw_rows(params["rows"], rng)}


def make(space_size, params, shape, rng):
    return PriceRequest(indices=indices(space_size, shape["rows"],
                                        bool(params.get("distinct")), rng))


def rows(req):
    return len(req.indices)


def payload(req, resp, pick):
    a = resp.result
    return {"idx": np.asarray(a.idx), "unit": a.sku_unit_total[pick],
            "re": a.sku_unit_re[pick], "nre": a.sku_unit_nre[pick],
            "pf": a.portfolio_cost[pick]}


def answer(req, ref, pick):
    idx = np.asarray(req.indices)
    return {"idx": idx, **ref.candidates(idx[pick])}


def compare(req, ref, got, pick):
    want = answer(req, ref, pick)
    return [("exact", got["idx"], want["idx"])] + [
        ("rel", got[k], want[k]) for k in ("unit", "re", "nre", "pf")]
