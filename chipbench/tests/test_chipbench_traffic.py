"""The traffic generator is deterministic in ``--seed``: one seed gives the
same requests; another seed sends the same sizes and arrivals in another
order, with other contents."""
import numpy as np
import pytest

from chipbench_cases import BENCH

from harness import traffic
from harness.catalog import Catalog

CAT = Catalog(BENCH)
CELLS = [w["name"] for w in CAT.spec["workloads"]]


def _plan(cell, seed, seconds=2.0):
    wl = CAT.workload(cell)
    n = 129 if cell.startswith("scms") else 90_224_199
    return (traffic.open_plan(cell, wl, seconds, seed, n, CAT.request_kind),
            traffic.closed_clients(cell, wl, seed, n, CAT.request_kind))


def _key(p):
    r = p.request
    idx = getattr(r, "indices", None)
    return (p.kind, None if idx is None else tuple(np.asarray(idx)),
            getattr(r, "base", None), getattr(r, "specs", None))


def _size(p):
    r = p.request
    for a in ("indices", "specs"):
        if getattr(r, a, None) is not None and len(getattr(r, a)):
            return (p.kind, len(getattr(r, a)))
    return (p.kind, 0)


@pytest.mark.parametrize("cell", CELLS)
def test_same_seed_same_requests(cell):
    (a, ca), (b, cb) = _plan(cell, 2**31 + 12345), _plan(cell, 2**31 + 12345)
    assert [(_key(p), p.due_s) for p in a] == [(_key(p), p.due_s) for p in b]
    for x, y in zip(ca, cb):
        for _ in range(3):
            assert _key(next(x)) == _key(next(y))


@pytest.mark.parametrize("cell", CELLS)
def test_other_seed_same_work_other_order(cell):
    (a, ca), (b, cb) = _plan(cell, 1), _plan(cell, 2)
    if a:
        assert sorted(map(_size, a)) == sorted(map(_size, b))
        gaps = lambda plan: sorted(np.diff([p.due_s for p in plan]
                                           + [2.0]).round(9))
        assert gaps(a) == gaps(b)
        assert [_key(p) for p in a] != [_key(p) for p in b]
    for x, y in zip(ca, cb):
        px, py = next(x), next(y)
        assert _size(px) == _size(py) and _key(px) != _key(py)
