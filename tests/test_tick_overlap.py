"""The tick loop keeps one fused tick in flight: tick N+1 is dispatched
before tick N lands.  Prices stay bit-exact, every tick is fetched once,
a raw tick lands the flight first, and the failure paths (retry, breaker,
fallback, poisoned rows, cancellation, deadlines, stop) treat a tick in
flight as they treat the tick being run."""
import asyncio
import dataclasses
import time

import numpy as np
import pytest

from repro.dse import ChunkedEvaluator, DesignSpace, SKU
from repro.obs.registry import REGISTRY
from repro.resilience import FaultInjector
from repro.service import (DEADLINE_EXCEEDED, NUMERICAL_ERROR, PriceRequest,
                           PriceSystemsRequest, PricingService,
                           ServiceConfig)

SPACE = DesignSpace(
    skus=(SKU("laptop", 200.0, 2e6), SKU("server", 400.0, 5e5)),
    processes=("7nm", "12nm"), integrations=("MCM",),
    chiplet_counts=(1, 2, 4), allow_reuse=True)
CFG = ServiceConfig(chunk=16, split=4, warm_mc=())
# one request a tick: a request of 16 rows fills a chunk on its own
WHOLE = dataclasses.replace(CFG, split=16)
RAW = PriceSystemsRequest(specs=(
    {"kind": "soc", "name": "s", "area": 120.0, "process": "7nm",
     "quantity": 1e6},))


@pytest.fixture(autouse=True)
def _no_env_faults(monkeypatch):
    monkeypatch.delenv("REPRO_FAULTS", raising=False)


@pytest.fixture(scope="module")
def evaluator():
    return ChunkedEvaluator(SPACE, candidates_per_chunk=16)


@pytest.fixture(scope="module")
def oracle():
    return ChunkedEvaluator(SPACE, candidates_per_chunk=16, fused=False)


def _rows(seed, n):
    return np.random.default_rng(seed).integers(0, SPACE.size(), n).tolist()


def _bit_exact(resp, idx, evaluator):
    assert resp.ok, resp.error
    assert not resp.degraded
    direct = evaluator.evaluate_indices(np.asarray(idx, np.int64))
    for k in ("sku_unit_total", "sku_unit_re", "sku_unit_nre",
              "portfolio_cost"):
        assert np.array_equal(getattr(resp.result, k), getattr(direct, k)), k


async def _until(cond, limit=10_000):
    """Yield to the loop until ``cond()`` holds (bounded)."""
    for _ in range(limit):
        if cond():
            return
        await asyncio.sleep(0)
    raise AssertionError("condition never held")


def _flying(svc, request):
    f = svc._flight
    return f is not None and any(a.item.owner.request is request
                                 for a in f.plan.assignments)


def test_two_sweeps_overlap_bit_exact(evaluator):
    """Two sweeps of more than 3 chunks each keep the queue full: ticks
    overlap, each is fetched once, and every row is the direct call's."""
    a_idx, b_idx = _rows(1, 70), _rows(2, 90)
    reg = REGISTRY.counter("service_ticks_overlapped")
    before = reg.get()

    async def main():
        svc = PricingService(SPACE, CFG)
        await svc.start()
        ra, rb = await asyncio.gather(svc.submit(PriceRequest(indices=a_idx)),
                                      svc.submit(PriceRequest(indices=b_idx)))
        await svc.stop()
        return svc, ra, rb

    svc, ra, rb = asyncio.run(main())
    _bit_exact(ra, a_idx, evaluator)
    _bit_exact(rb, b_idx, evaluator)
    snap = svc.snapshot()
    assert snap["ticks"] == -(-(70 + 90) // 16)
    assert 0 < snap["ticks_overlapped"] == snap["ticks"] - 1
    assert snap["device_gets"] == snap["ticks"]
    assert reg.get() - before == snap["ticks_overlapped"]
    ticks = svc.flight.records("tick")
    assert [t["overlapped"] for t in ticks] == [False] + [True] * (
        len(ticks) - 1)
    assert svc._flight is None and svc.sched.pending_rows == 0


def test_lone_point_never_overlaps(evaluator):
    idx = [3, 1, 4, 1, 5]

    async def main():
        svc = PricingService(SPACE, CFG)
        await svc.start()
        r = await svc.submit(PriceRequest(indices=idx))
        await svc.stop()
        return svc, r

    svc, r = asyncio.run(main())
    _bit_exact(r, idx, evaluator)
    snap = svc.snapshot()
    assert snap["ticks"] == snap["device_gets"] == 1
    assert snap["ticks_overlapped"] == 0


def test_raw_tick_lands_the_flight_first(evaluator):
    """A raw group queued behind a sweep: the chunk tick in flight lands
    before the raw tick runs, and the sweep goes on after it."""
    idx = _rows(3, 16 * 8)
    raw_task = []

    async def main():
        svc = PricingService(SPACE, CFG)
        await svc.start()

        def progress(done, n):
            if done >= 32 and not raw_task:
                raw_task.append(asyncio.ensure_future(svc.submit(RAW)))

        sweep = await svc.submit(PriceRequest(indices=idx),
                                 on_partial=progress)
        raw = await raw_task[0]
        await svc.stop()
        return svc, sweep, raw

    svc, sweep, raw = asyncio.run(main())
    _bit_exact(sweep, idx, evaluator)
    assert raw.ok, raw.error
    ticks = svc.flight.records("tick")
    lanes = [t["lane"] for t in ticks]
    at = lanes.index("raw")
    assert lanes.count("raw") == 1
    # the tick before the raw one flew while the one before it landed
    assert lanes[at - 1] == "chunk" and ticks[at - 1]["overlapped"]
    # the raw tick ran alone; the chunk tick after it started a flight
    assert not ticks[at]["overlapped"] and not ticks[at + 1]["overlapped"]
    assert "chunk" in lanes[at + 1:]
    snap = svc.snapshot()
    assert snap["device_gets"] == snap["ticks"] == len(ticks)


def test_dispatch_error_of_the_overlapped_tick(evaluator, oracle):
    """The second dispatch (while the first tick flies) fails and so does
    its retry: that tick degrades to the host oracle, the breaker opens,
    and the request in the tick already in flight answers fused and
    bit-exact.  The older tick's landing does not close the breaker."""
    a_idx, b_idx = _rows(4, 16), _rows(5, 16)
    cfg = dataclasses.replace(WHOLE, breaker_cooldown_s=60.0)

    async def main():
        svc = PricingService(SPACE, cfg)
        # seed 1: the checks fire as no, yes, yes, then never (n=2)
        svc.faults = FaultInjector("seed=1;dispatch_error:p=0.5,n=2")
        await svc.start()
        ra, rb = await asyncio.gather(svc.submit(PriceRequest(indices=a_idx)),
                                      svc.submit(PriceRequest(indices=b_idx)))
        await svc.stop()
        return svc, ra, rb

    svc, ra, rb = asyncio.run(main())
    _bit_exact(ra, a_idx, evaluator)
    assert rb.ok and rb.degraded and rb.degraded_rows.all()
    legacy = oracle.evaluate_indices_legacy(np.asarray(b_idx, np.int64))
    assert np.array_equal(rb.result.sku_unit_total, legacy.sku_unit_total)
    assert np.array_equal(rb.result.portfolio_cost, legacy.portfolio_cost)
    res = svc.snapshot()["resilience"]
    assert res["fused_failures"] == 2 and res["retries"] == 1
    assert res["fallback_ticks"] == 1 and res["fallback_rows"] == 16
    assert res["breaker_opens"] == 1 and res["breaker_closes"] == 0
    assert res["breaker"]["state"] == "open"
    assert res["loop_errors"] == 0
    assert svc.snapshot()["ticks_overlapped"] == 1


def test_landing_error_redispatches_synchronously(evaluator):
    """An error that surfaces when a tick lands re-dispatches its chunk
    under the retry budget; the tick in flight behind it is untouched."""
    a_idx, b_idx = _rows(6, 16), _rows(7, 16)

    async def main():
        svc = PricingService(SPACE, WHOLE)
        await svc.start()
        fetch, calls = svc._fetch, []

        def flaky(out):
            calls.append(out)
            if len(calls) == 1:
                raise RuntimeError("lost the first landing")
            return fetch(out)

        svc._fetch = flaky
        ra, rb = await asyncio.gather(svc.submit(PriceRequest(indices=a_idx)),
                                      svc.submit(PriceRequest(indices=b_idx)))
        await svc.stop()
        return svc, ra, rb, calls

    svc, ra, rb, calls = asyncio.run(main())
    _bit_exact(ra, a_idx, evaluator)
    _bit_exact(rb, b_idx, evaluator)
    assert len(calls) == 3                 # A lost, A again, then B
    snap = svc.snapshot()
    res = snap["resilience"]
    assert res["fused_failures"] == 1 and res["retries"] == 1
    assert res["fallback_ticks"] == 0 and res["breaker_opens"] == 0
    assert snap["device_gets"] == snap["ticks"] == 2


def test_landing_error_past_the_budget_fails_that_tick_only(evaluator):
    """No fallback and no retry left: the landing's error fails the
    owners of that tick with a typed envelope; the next tick lands."""
    a_idx, b_idx = _rows(8, 16), _rows(9, 16)
    cfg = dataclasses.replace(WHOLE, fallback=False, tick_retries=0)

    async def main():
        svc = PricingService(SPACE, cfg)
        await svc.start()
        fetch, calls = svc._fetch, []

        def flaky(out):
            calls.append(out)
            if len(calls) == 1:
                raise RuntimeError("device lost the tick")
            return fetch(out)

        svc._fetch = flaky
        ra, rb = await asyncio.gather(svc.submit(PriceRequest(indices=a_idx)),
                                      svc.submit(PriceRequest(indices=b_idx)))
        await svc.stop()
        return svc, ra, rb

    svc, ra, rb = asyncio.run(main())
    assert not ra.ok and "device lost the tick" in ra.error.message
    _bit_exact(rb, b_idx, evaluator)
    assert svc.snapshot()["resilience"]["loop_errors"] == 0
    assert svc.sched.pending_rows == 0


def test_poisoned_landing_spares_the_tick_in_flight(evaluator):
    a_idx, b_idx = _rows(10, 16), _rows(11, 16)

    async def main():
        svc = PricingService(SPACE, WHOLE)
        svc.faults = FaultInjector("seed=3;poison:p=1.0,n=1")
        await svc.start()
        ra, rb = await asyncio.gather(svc.submit(PriceRequest(indices=a_idx)),
                                      svc.submit(PriceRequest(indices=b_idx)))
        await svc.stop()
        return svc, ra, rb

    svc, ra, rb = asyncio.run(main())
    assert not ra.ok and ra.error.code == NUMERICAL_ERROR
    _bit_exact(rb, b_idx, evaluator)
    snap = svc.snapshot()
    assert snap["resilience"]["numerical_errors"] == 1
    assert snap["ticks_overlapped"] == 1
    assert svc.sched.pending_rows == 0


def test_stop_lands_the_tick_in_flight(evaluator):
    idx = _rows(12, 16 * 5)

    async def main():
        svc = PricingService(SPACE, CFG)
        await svc.start()
        req = PriceRequest(indices=idx)
        fut = asyncio.ensure_future(svc.submit(req))
        await _until(lambda: _flying(svc, req))
        await svc.stop()
        return svc, await fut

    svc, r = asyncio.run(main())
    _bit_exact(r, idx, evaluator)
    assert svc._flight is None and not svc._active


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_owner_gone_while_in_flight(evaluator, how):
    """A request cancelled, or past its deadline, while its rows are in
    flight: its rows are dropped at the landing, a deadline gets its
    typed envelope, and the neighbours' rows land bit-exact."""
    a_idx, c_idx, b_idx = _rows(13, 16), _rows(14, 16), _rows(15, 32)
    deadline = {"deadline_ms": 1000.0} if how == "deadline" else {}
    seen = []

    async def main():
        svc = PricingService(SPACE, WHOLE)
        await svc.start()
        c_req = PriceRequest(indices=c_idx, **deadline)
        tasks = {}

        def a_landed(done, n):
            # A lands while C, dispatched behind it, is in flight
            seen.append(_flying(svc, c_req))
            if how == "cancel":
                tasks["c"].cancel()
            else:
                time.sleep(1.1)          # C's deadline passes in flight

        tasks["a"] = asyncio.ensure_future(svc.submit(
            PriceRequest(indices=a_idx), on_partial=a_landed))
        tasks["c"] = asyncio.ensure_future(svc.submit(c_req))
        tasks["b"] = asyncio.ensure_future(svc.submit(
            PriceRequest(indices=b_idx)))
        ra, rb = await tasks["a"], await tasks["b"]
        rc = None
        if how == "cancel":
            with pytest.raises(asyncio.CancelledError):
                await tasks["c"]
        else:
            rc = await tasks["c"]
        await svc.stop()
        return svc, ra, rb, rc

    svc, ra, rb, rc = asyncio.run(main())
    assert seen == [True]
    _bit_exact(ra, a_idx, evaluator)
    _bit_exact(rb, b_idx, evaluator)
    snap = svc.snapshot()
    res = snap["resilience"]
    if how == "cancel":
        assert res["cancelled"] == 1
    else:
        assert not rc.ok and rc.error.code == DEADLINE_EXCEEDED
        assert res["deadline_rejected"] == 1
        assert res["deadlines_active"] == 0
    # C's tick still landed, once, and priced nothing for anyone
    assert snap["ticks"] == snap["device_gets"] == 4
    assert svc.sched.pending_rows == 0 and not svc._active
