"""what_if: a ``WhatIfRequest`` around a uniformly drawn base candidate.

The grid is every (process, integration) of the space's menus.
Compared: the grid's (process, integration) pairs and skipped count,
the base cost and each swapped candidate's portfolio cost.
"""
import numpy as np

from repro.service import WhatIfRequest


def shape(params, rng):
    return {}


def make(space_size, params, shape, rng):
    return WhatIfRequest(base=int(rng.integers(0, space_size)))


def rows(req):
    return 0


def payload(req, resp, pick):
    r = resp.result
    return {"pairs": [(x["process"], x["integration"]) for x in r.rows],
            "skipped": len(r.skipped), "base": np.asarray([r.base_cost]),
            "costs": np.asarray([x["portfolio_cost"] for x in r.rows])}


def answer(req, ref, pick):
    sp = ref.space
    base = sp.candidate(int(req.base))
    pairs, costs, skipped = [], [], 0
    for p in (req.processes or sp.processes):
        for t in (req.integrations or sp.integrations):
            j = sp.index(sp.swap(base, p, t))
            if j is None:
                skipped += 1
                continue
            pairs.append((p, t))
            costs.append(ref.candidate(j)["pf"])
    return {"pairs": pairs, "skipped": skipped,
            "base": np.asarray([ref.candidate(int(req.base))["pf"]]),
            "costs": np.asarray(costs)}


def compare(req, ref, got, pick):
    want = answer(req, ref, pick)
    return [("exact", got["pairs"], want["pairs"]),
            ("exact", got["skipped"], want["skipped"]),
            ("rel", got["base"], want["base"]),
            ("rel", got["costs"], want["costs"])]
