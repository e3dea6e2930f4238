"""The comparison that decides ``correct``.

After the window closes, a sample of the responses it completed, drawn
from ``--seed`` (every response of a kind where the workload's ``check``
asks for all, and always the largest of each kind), is compared with the
plain reference (``reference.py``, float64).  A kind's module says how
many rows its answer has (``rows``), what its answer is (``payload``),
what the reference answers in its place (``answer``) and which pairs to
compare (``compare``), each pair in one of three categories:

* ``rel``: prices, costs and rankings, the largest relative error;
* ``mc``: Monte-Carlo quantiles, the same, kept apart;
* ``exact``: indices, grids, names, where any difference is a mismatch.

Rows of a large response are sampled (``check.rows`` positions drawn
from the seed, plus its first and last row).  A request that never
answered is ``missing``.  Each number has its own limit (``LIMITS``),
set in PERF.md from the readings of sound runs and of the control.
"""
from __future__ import annotations

import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from .traffic import SAMPLE, seed_rng

# name -> limit; a run is correct when every number it reports is <= its
# limit.  ``uncompared`` is 1 where no response could be compared.
LIMITS = {"rel_err": 1e-3, "mc_rel_err": 1e-3, "mismatches": 0,
          "missing": 0, "uncompared": 0}


def rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if got.shape != want.shape:
        return math.inf
    if got.size == 0:
        return 0.0
    with np.errstate(divide="ignore", invalid="ignore"):
        err = np.abs(got - want) / np.abs(want)
    err = np.where(got == want, 0.0, err)
    return float(np.nanmax(np.where(np.isnan(err), np.inf, err)))


def same(got, want) -> bool:
    if isinstance(got, np.ndarray) or isinstance(want, np.ndarray):
        return np.array_equal(np.asarray(got), np.asarray(want))
    return got == want


def numbers(triples: List[Tuple[str, object, object]]) -> Dict[str, float]:
    out = {"rel_err": 0.0, "mismatches": 0}
    for cat, got, want in triples:
        if cat == "exact":
            out["mismatches"] += 0 if same(got, want) else 1
        else:
            key = "mc_rel_err" if cat == "mc" else "rel_err"
            out[key] = max(out.get(key, 0.0), rel_err(got, want))
    return out


def merge(parts: List[Dict[str, float]]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for p in parts:
        for k, v in p.items():
            out[k] = v + out.get(k, 0) if k == "mismatches" \
                else max(out.get(k, 0.0), v)
    return out


def sample(records, kinds, seed: int, plan: Dict
           ) -> List[Tuple[object, np.ndarray]]:
    """``(record, row positions)`` of the responses to compare: per kind,
    ``plan['responses']`` drawn from the seed (all where it is 0 or
    absent) plus the largest; per response, ``plan['rows']`` positions
    (all where it is 0 or absent) plus the first and the last."""
    rng = seed_rng(seed, SAMPLE)
    n_resp = int(plan.get("responses", 0))
    n_rows = int(plan.get("rows", 0))
    by_kind: Dict[str, list] = {}
    for r in records:
        if r.ok:
            by_kind.setdefault(r.kind, []).append(r)
    out = []
    for kind in sorted(by_kind):
        rs = by_kind[kind]
        size = [kinds(kind).rows(r.request) for r in rs]
        chosen = list(range(len(rs)))
        if n_resp and len(rs) > n_resp:
            big = max(range(len(rs)), key=size.__getitem__)
            chosen = sorted(set(rng.choice(len(rs), n_resp, replace=False)
                                .tolist()) | {big})
        for i in chosen:
            n = size[i]
            pick = np.arange(n)
            if n_rows and n > n_rows + 2:
                pick = np.unique(np.concatenate([
                    [0, n - 1], rng.choice(n, n_rows, replace=False)]))
            out.append((rs[i], pick))
    return out


def compare(records, kinds, ref, seed: int, plan: Dict,
            control: Optional[object] = None) -> Tuple[Dict, int]:
    """Numbers of the sampled responses against ``ref``; with ``control``
    (a lower-precision reference) the control's answers stand in for the
    served ones.  Returns ``(numbers, responses compared)``."""
    parts = []
    chosen = sample(records, kinds, seed, plan)
    for rec, pick in chosen:
        mod = kinds(rec.kind)
        got = (mod.answer(rec.request, control, pick) if control is not None
               else mod.payload(rec.request, rec.response, pick))
        parts.append(numbers(mod.compare(rec.request, ref, got, pick)))
    return merge(parts), len(chosen)


def verdict(nums: Dict[str, float], checked: int) -> Tuple[bool, Dict]:
    """``correct`` and the ``checks`` block: each number with its limit."""
    nums = {**nums, "uncompared": 0 if checked else 1}
    checks = {k: {"value": v, "limit": LIMITS[k]} for k, v in nums.items()}
    return all(v <= LIMITS[k] for k, v in nums.items()), checks
