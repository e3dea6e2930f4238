"""rank: a ``RankRequest`` of candidate indices, top-k by portfolio cost.

params: ``rows``, ``distinct`` as for price, and ``top_k``.  Compared:
the order is a permutation of the request's indices, the served values
are the reference's sorted costs and the reference's costs of the served
order, and the materialized top-k rows are the reference's.  Near-ties
may swap places; they differ by less than the limit.
"""
import numpy as np

from harness.traffic import draw_rows, indices
from repro.service import RankRequest


def shape(params, rng):
    return {"rows": draw_rows(params["rows"], rng)}


def make(space_size, params, shape, rng):
    return RankRequest(indices=indices(space_size, shape["rows"],
                                       bool(params.get("distinct")), rng),
                       top_k=int(params.get("top_k", 10)))


def rows(req):
    return len(req.indices)


def payload(req, resp, pick):
    r = resp.result
    return {"order": np.asarray(r.order), "values": np.asarray(r.values),
            "top_unit": np.asarray([t.sku_unit_total for t in r.top]),
            "top_pf": np.asarray([t.portfolio_cost for t in r.top])}


def answer(req, ref, pick):
    idx = np.asarray(req.indices)
    c = ref.candidates(idx)
    o = np.lexsort((idx, c["pf"]))
    k = o[:req.top_k]
    return {"order": idx[o], "values": c["pf"][o], "top_unit": c["unit"][k],
            "top_pf": c["pf"][k]}


def compare(req, ref, got, pick):
    idx = np.asarray(req.indices)
    want = answer(req, ref, pick)
    at_order = ref.candidates(got["order"]) if (
        np.array_equal(np.sort(got["order"]), np.sort(idx))) else None
    out = [("exact", np.sort(got["order"]), np.sort(idx)),
           ("rel", got["values"], want["values"])]
    if at_order is not None:
        k = len(want["top_pf"])
        out += [("rel", got["values"], at_order["pf"]),
                ("rel", got["top_unit"], at_order["unit"][:k]),
                ("rel", got["top_pf"], at_order["pf"][:k])]
    return out
