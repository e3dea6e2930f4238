"""mc_risk: an ``MCRiskRequest``, Monte-Carlo quantiles of candidates.

params: ``rows``, ``distinct`` as for price, and ``mc``: ``draws``,
``quantiles``, ``seed`` and optional ``sigmas`` (defect, wafer cost,
bond, interposer).  Requests that share the McSpec coalesce into one
chunk.  Compared: the nominal rows and each quantile of the sampled
positions.
"""
import numpy as np

from harness.traffic import draw_rows, indices
from repro.dse.uncertainty import Uncertainty
from repro.service import McSpec, MCRiskRequest

SIGMAS = ("defect_sigma", "wafer_cost_sigma", "bond_sigma",
          "interposer_sigma")


def spec(params):
    m = params["mc"]
    sig = Uncertainty(*m["sigmas"]) if "sigmas" in m else Uncertainty()
    return McSpec(draws=int(m["draws"]),
                  quantiles=tuple(float(q) for q in m["quantiles"]),
                  seed=int(m.get("seed", 0)), sigmas=sig)


def shape(params, rng):
    return {"rows": draw_rows(params["rows"], rng)}


def make(space_size, params, shape, rng):
    return MCRiskRequest(indices=indices(space_size, shape["rows"],
                                         bool(params.get("distinct")), rng),
                         mc=spec(params))


def rows(req):
    return len(req.indices)


def _qkeys(req):
    return [f"q{int(round(q * 100))}" for q in req.mc.quantiles]


def payload(req, resp, pick):
    a = resp.result
    out = {"idx": np.asarray(a.idx), "unit": a.sku_unit_total[pick],
           "re": a.sku_unit_re[pick], "nre": a.sku_unit_nre[pick],
           "pf": a.portfolio_cost[pick]}
    out["q"] = np.stack([a.risk[k][pick] for k in _qkeys(req)], axis=-1)
    return out


def answer(req, ref, pick):
    idx = np.asarray(req.indices)
    mc = req.mc
    mult = ref.multipliers(mc.seed, mc.draws,
                           [getattr(mc.sigmas, s) for s in SIGMAS])
    out = {"idx": idx, **ref.candidates(idx[pick])}
    out["q"] = np.asarray([ref.mc_quantiles(int(i), mult, mc.quantiles)
                           for i in idx[pick]]).reshape(-1, len(mc.quantiles))
    return out


def compare(req, ref, got, pick):
    want = answer(req, ref, pick)
    return ([("exact", got["idx"], want["idx"])]
            + [("rel", got[k], want[k]) for k in ("unit", "re", "nre", "pf")]
            + [("mc", got["q"], want["q"])])
