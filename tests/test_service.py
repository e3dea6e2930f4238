"""repro.service: the hard parity oracle (coalesced responses bit-exact
against direct ChunkedEvaluator / portfolio_search calls), seeded
arrival-interleaving determinism, per-request error isolation, constant
trace counts after warmup, backpressure envelopes, and the scheduler's
fairness/occupancy policy in isolation."""
import asyncio
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import CostEngine, SystemBatch, pad_batch
from repro.core.engine import TRACE_COUNTS
from repro.core.system import Module, make_chip, spec
from repro.dse import (ChunkedEvaluator, DesignSpace, RiskConfig, SKU,
                       Uncertainty, portfolio_search)
from repro.service import (INVALID_REQUEST, Lane, McSpec, MCRiskRequest,
                           PriceRequest, PriceSystemsRequest, PricingService,
                           QUEUE_FULL, RankRequest, Scheduler, SearchRequest,
                           ServiceConfig, SpanWork, WhatIfRequest, serve)
from repro.service import server as server_mod
from repro.service.server import PricingService as _PS


def _space(**kw):
    d = dict(skus=(SKU("laptop", 200.0, 2e6), SKU("server", 400.0, 5e5)),
             processes=("7nm", "12nm"), integrations=("MCM",),
             chiplet_counts=(1, 2, 4), allow_reuse=True)
    d.update(kw)
    return DesignSpace(**d)


@pytest.fixture(scope="module")
def space():
    return _space()


@pytest.fixture(scope="module")
def evaluator(space):
    # same chunk size as CFG below => the service and the direct path
    # share one compiled trace per lane
    return ChunkedEvaluator(space, candidates_per_chunk=16)


CFG = ServiceConfig(chunk=16, split=4, warm_mc=((64, (0.5, 0.9)),))


def _arrays_equal(a, b):
    assert np.array_equal(a.idx, b.idx)
    assert np.array_equal(a.sku_unit_total, b.sku_unit_total)
    assert np.array_equal(a.sku_unit_re, b.sku_unit_re)
    assert np.array_equal(a.sku_unit_nre, b.sku_unit_nre)
    assert np.array_equal(a.portfolio_cost, b.portfolio_cost)
    if a.risk is None:
        assert b.risk is None
    else:
        assert set(a.risk) == set(b.risk)
        for k in a.risk:
            assert np.array_equal(a.risk[k], b.risk[k]), k


# ---------------------------------------------------------------------------
# The hard parity oracle: coalesced == direct, bit for bit
# ---------------------------------------------------------------------------


def test_mixed_workload_bit_exact_parity(space, evaluator):
    """Heterogeneous concurrent requests — coalesced into shared ticks —
    must answer bit-exactly what the direct single-request APIs answer."""
    mc = McSpec(draws=64, quantiles=(0.5, 0.9), seed=7)
    reqs = [
        PriceRequest(indices=[0, 3, 5, 7, 9]),
        PriceRequest(indices=list(range(space.size()))),
        MCRiskRequest(indices=[1, 2, 3, 8], mc=mc),
        RankRequest(indices=list(range(0, space.size(), 2)), top_k=4),
        SearchRequest(seed=3, population=8, generations=4, elite=3),
    ]
    resps, svc = serve(space, reqs, CFG)
    assert all(r.ok for r in resps), [r.error for r in resps]

    _arrays_equal(resps[0].result,
                  evaluator.evaluate_indices(np.asarray([0, 3, 5, 7, 9])))
    _arrays_equal(resps[1].result,
                  evaluator.evaluate_indices(np.arange(space.size())))
    _arrays_equal(resps[2].result, evaluator.evaluate_indices(
        np.asarray([1, 2, 3, 8]), mc_key=jax.random.PRNGKey(7),
        mc_draws=64, mc_quantiles=(0.5, 0.9)))

    # rank: same order/values as a host argsort of the direct arrays
    direct = evaluator.evaluate_indices(np.arange(0, space.size(), 2))
    obj = direct.portfolio_cost
    order = np.lexsort((direct.idx, obj))
    rk = resps[3].result
    assert np.array_equal(rk.order, direct.idx[order])
    assert np.array_equal(rk.values, obj[order])
    assert [r.label for r in rk.top] == [
        space.candidate_at(int(i)).label() for i in direct.idx[order[:4]]]

    # search: identical to the direct portfolio_search call
    ds = portfolio_search(space, jax.random.PRNGKey(3), population=8,
                          generations=4, elite=3)
    gs = resps[4].result
    assert gs.best.label == ds.best.label
    assert gs.best.portfolio_cost == ds.best.portfolio_cost
    assert gs.history == ds.history
    assert [r.label for r in gs.ranked] == [r.label for r in ds.ranked]
    assert [r.portfolio_cost for r in gs.ranked] == \
        [r.portfolio_cost for r in ds.ranked]

    # the tick loop syncs exactly once per tick
    snap = svc.snapshot()
    assert snap["device_gets"] == snap["ticks"]
    assert snap["n_ok"] == len(reqs)


def test_risk_search_parity(space):
    """Risk-objective search (MC lane end to end) equals the direct call."""
    risk = RiskConfig(n_draws=32, quantile=0.9,
                      sigmas=Uncertainty(defect_sigma=0.3))
    resps, _ = serve(space, [SearchRequest(seed=11, population=8,
                                           generations=3, elite=2,
                                           risk=risk)], CFG)
    assert resps[0].ok, resps[0].error
    ds = portfolio_search(space, jax.random.PRNGKey(11), population=8,
                          generations=3, elite=2, risk=risk)
    gs = resps[0].result
    assert gs.objective_key == "q90" == ds.objective_key
    assert gs.history == ds.history
    assert [r.label for r in gs.ranked] == [r.label for r in ds.ranked]
    assert gs.best.risk == ds.best.risk


def test_what_if_parity_and_skips(space, evaluator):
    """What-if rows re-price the base architecture under each tech combo
    (bit-exact vs direct pricing); combos outside the space are skipped,
    not errored."""
    base_idx = 5
    req = WhatIfRequest(base=base_idx, processes=("7nm", "12nm"),
                        integrations=("MCM", "2.5D"))   # 2.5D not in space
    resps, _ = serve(space, [req], CFG)
    assert resps[0].ok, resps[0].error
    wi = resps[0].result
    base = space.candidate_at(base_idx)
    assert wi.base_label == base.label()
    assert wi.base_cost == float(
        evaluator.evaluate_indices(np.asarray([base_idx]))
        .portfolio_cost[0])
    assert wi.rows, "grid empty"
    for row in wi.rows:
        gi = None
        for cand_i in range(space.size()):
            if space.candidate_at(cand_i).label() == row["candidate"]:
                gi = cand_i
                break
        assert gi is not None
        direct = float(evaluator.evaluate_indices(
            np.asarray([gi])).portfolio_cost[0])
        assert row["portfolio_cost"] == direct
        assert row["delta_vs_base"] == row["portfolio_cost"] - wi.base_cost
    reasons = {(s["process"], s["integration"]) for s in wi.skipped}
    assert ("7nm", "2.5D") in reasons       # outside the space's menu


def test_raw_systems_lane(space):
    """Raw spec()-list groups price like CostEngine on the same batch."""
    specs = (
        {"kind": "soc", "name": "a", "area": 150.0, "process": "7nm",
         "quantity": 1e6},
        {"kind": "split", "name": "b", "area": 300.0, "process": "7nm",
         "n_chiplets": 2, "integration": "MCM", "quantity": 5e5},
    )
    resps, _ = serve(space, [PriceSystemsRequest(specs=specs)], CFG)
    assert resps[0].ok, resps[0].error
    rows = resps[0].result.rows
    systems = [spec(dict(d)) for d in specs]
    tot = CostEngine().total(
        SystemBatch.from_systems(systems, share_nre=[0, 0]))
    direct = np.asarray(jax.device_get(tot.total), np.float64)
    for i, row in enumerate(rows):
        assert row["system"] == systems[i].name
        np.testing.assert_allclose(row["total"], direct[i], rtol=1e-6)


def _wide_group(name):
    """One two-chip system whose chips carry five modules each: 10 module
    instances, so one group fits a raw lane of 4 slots x 2 chips (16
    instances) and two groups do not."""
    chips = [make_chip(f"{name}_c{j}",
                       [Module(f"{name}_c{j}_m{k}", 20.0 + k, "7nm")
                        for k in range(5)], "7nm", integration="MCM")
             for j in range(2)]
    return ({"kind": "chips", "name": name, "chips": chips,
             "integration": "MCM", "quantity": 1e5},)


def test_raw_lane_bit_exact_one_transfer_each_way(space, monkeypatch):
    """A raw group is priced bit for bit like ``CostEngine`` on the same
    group at the lane's padded signature, alone in its tick and when a
    tick sheds a group that does not fit; admission touches no device
    array, and a raw tick makes one host-to-device transfer, one dispatch
    of device tables and one ``jax.device_get``."""
    cfg = ServiceConfig(chunk=16, split=4, raw_slots=4, raw_max_chips=2,
                        fallback=False)
    soc = ({"kind": "soc", "name": "a", "area": 150.0, "process": "7nm",
            "quantity": 1e6},
           {"kind": "split", "name": "b", "area": 300.0, "process": "7nm",
            "n_chiplets": 2, "integration": "MCM", "quantity": 5e5})
    groups = [soc, _wide_group("w1"), _wide_group("w2")]
    calls = {"get": 0, "put": 0, "dispatch": 0, "compile": 0}
    seen = {"admit": [], "raw": []}
    real_get, real_put = jax.device_get, jax.device_put
    real_total = server_mod._TOTAL_JIT

    def get(x):
        calls["get"] += 1
        return real_get(x)

    def put(x, *a, **k):
        calls["put"] += 1
        return real_put(x, *a, **k)

    def total(batch, flow):
        calls["dispatch"] += 1
        assert all(isinstance(x, jax.Array)
                   for x in jax.tree_util.tree_leaves(batch))
        return real_total(batch, flow)

    def counted(fn, key):
        def wrapper(*a, **k):
            before = dict(calls)
            # a raw group's admission moves nothing to or from the device
            raw = isinstance(a[0], PriceSystemsRequest)
            with jax.transfer_guard("disallow_explicit" if raw
                                    else "allow"):
                out = fn(*a, **k)
            seen[key].append({c: calls[c] - before[c] for c in calls})
            return out
        return wrapper

    def compiled(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            calls["compile"] += 1

    async def _main():
        svc = PricingService(space, cfg)
        await svc.start()
        jax.monitoring.register_event_duration_secs_listener(compiled)
        monkeypatch.setattr(jax, "device_get", get)
        monkeypatch.setattr(jax, "device_put", put)
        monkeypatch.setattr(server_mod, "_TOTAL_JIT", total)
        svc._admit = counted(svc._admit, "admit")
        svc._tick_raw = counted(svc._tick_raw, "raw")
        alone = await svc.submit(PriceSystemsRequest(specs=groups[0]))
        # both wide groups are queued before the loop runs: the first
        # tick sheds the second back to the queue
        shed = await asyncio.gather(
            *(svc.submit(PriceSystemsRequest(specs=g)) for g in groups[1:]),
            svc.submit(PriceRequest(indices=[0, 1, 2])))
        await svc.stop()
        return svc, [alone, *shed[:2]], shed[2]

    try:
        svc, resps, chunk_resp = asyncio.run(_main())
    finally:
        jax.monitoring.unregister_event_duration_listener(compiled)
    monkeypatch.undo()
    assert calls["compile"] == 0           # warm-up compiled the raw lane
    assert chunk_resp.ok and all(r.ok for r in resps), \
        [r.error for r in resps]
    raw_ticks = [t for t in svc.flight.records("tick") if t["lane"] == "raw"]
    assert [(t["used"], t["rows"]) for t in raw_ticks] == \
        [(2, 2), (2, 1), (1, 1)]                      # the middle one shed
    assert [(d["get"], d["put"], d["dispatch"]) for d in seen["admit"][:3]] \
        == [(0, 0, 0)] * 3
    assert [(d["get"], d["put"], d["dispatch"]) for d in seen["raw"]] == \
        [(1, 1, 1)] * 3
    snap = svc.snapshot()
    assert snap["raw_packs"] == 3 < snap["ticks"]
    assert snap["raw_pack_s"] > 0.0

    engine = CostEngine()
    for g, resp in zip(groups, resps):
        systems = [spec(dict(d)) for d in g]
        batch = pad_batch(SystemBatch.from_systems(
            systems, share_nre=[0] * len(systems), max_chips=2),
            **svc.raw_pad)
        tc = jax.device_get(engine.total(batch, flow="chip-last"))
        n = len(systems)
        rows = resp.result.rows
        assert [r["system"] for r in rows] == [s.name for s in systems]
        for key, want in (("re_total", tc.re.total),
                          ("nre_total", tc.nre.total),
                          ("total", tc.total)):
            got = np.asarray([r[key] for r in rows])
            assert np.array_equal(got, np.asarray(want[:n], np.float64)), key


# ---------------------------------------------------------------------------
# Determinism under arrival interleavings
# ---------------------------------------------------------------------------


def test_interleaving_determinism(space):
    """The same request set must produce identical payloads no matter the
    (seeded, randomized) submission order and inter-arrival delays —
    coalescing changes which rows share a tick, never the rows."""
    base_reqs = [
        PriceRequest(indices=[0, 1, 2, 3, 4, 5, 6, 7]),
        MCRiskRequest(indices=[2, 4, 6], mc=McSpec(draws=64, seed=5)),
        RankRequest(indices=[9, 1, 5, 3], top_k=2),
        SearchRequest(seed=2, population=8, generations=3, elite=2),
        PriceRequest(indices=[7, 7, 1]),
    ]
    cfg = dataclasses.replace(CFG, result_cache_entries=0)  # no short-cuts

    def run(order_seed: int):
        rng = np.random.default_rng(order_seed)
        order = rng.permutation(len(base_reqs))

        async def _main():
            svc = PricingService(space, cfg)
            await svc.start()

            async def client(j):
                await asyncio.sleep(float(rng.integers(0, 4)) * 1e-3)
                return j, await svc.submit(base_reqs[j])

            pairs = await asyncio.gather(*(client(int(j)) for j in order))
            await svc.stop()
            return dict(pairs)

        return asyncio.run(_main())

    runs = [run(s) for s in (0, 1, 2)]
    for other in runs[1:]:
        for j in range(len(base_reqs)):
            a, b = runs[0][j], other[j]
            assert a.ok and b.ok
            if base_reqs[j].kind in ("price", "mc_risk"):
                _arrays_equal(a.result, b.result)
            elif base_reqs[j].kind == "rank":
                assert np.array_equal(a.result.order, b.result.order)
                assert np.array_equal(a.result.values, b.result.values)
            else:  # search
                assert a.result.history == b.result.history
                assert [r.label for r in a.result.ranked] == \
                    [r.label for r in b.result.ranked]


# ---------------------------------------------------------------------------
# Error isolation / validation envelopes
# ---------------------------------------------------------------------------


def test_error_envelope_isolation(space, evaluator, monkeypatch):
    """A request that blows up server-side fails ALONE with a typed
    envelope; coalesced siblings still answer bit-exactly."""
    orig = _PS._rank_payload

    def poisoned(self, arrays, objective, top_k):
        if top_k == 13:
            raise RuntimeError("poisoned request")
        return orig(self, arrays, objective, top_k)

    monkeypatch.setattr(_PS, "_rank_payload", poisoned)
    reqs = [
        PriceRequest(indices=[0, 1, 2, 3]),
        RankRequest(indices=[4, 5, 6], top_k=13),       # the poisoned one
        MCRiskRequest(indices=[7, 8], mc=McSpec(draws=64, seed=1)),
    ]
    resps, svc = serve(space, reqs, CFG)
    assert resps[0].ok and resps[2].ok
    assert not resps[1].ok
    assert resps[1].error.code == "internal"
    assert "poisoned" in resps[1].error.message
    _arrays_equal(resps[0].result,
                  evaluator.evaluate_indices(np.asarray([0, 1, 2, 3])))
    _arrays_equal(resps[2].result, evaluator.evaluate_indices(
        np.asarray([7, 8]), mc_key=jax.random.PRNGKey(1), mc_draws=64,
        mc_quantiles=(0.5, 0.9)))
    assert svc.snapshot()["n_errors"] == 1
    # the failure is in the request log, typed
    assert svc.log.records(event="error")


def test_invalid_requests_are_enveloped(space):
    reqs = [
        PriceRequest(indices=[0, space.size() + 7]),      # out of range
        PriceRequest(),                                   # nothing to price
        RankRequest(indices=[1], objective="q90"),        # objective w/o mc
        SearchRequest(population=4, elite=9),             # elite > population
        PriceSystemsRequest(specs=({"kind": "nope", "name": "x"},)),
        PriceRequest(indices=[1], flow="no-such-flow"),
        PriceSystemsRequest(specs=()),
    ]
    resps, svc = serve(space, reqs, CFG)
    for r in resps:
        assert not r.ok
        assert r.error.code == INVALID_REQUEST
    # admission rejections never reach the device
    assert svc.snapshot()["ticks"] == 0


def test_backpressure_queue_full(space):
    """The bounded queue refuses work past the row budget with a typed
    queue_full envelope — and recovers once the backlog drains."""
    cfg = dataclasses.replace(CFG, max_pending=space.size() + 4)

    async def _main():
        svc = PricingService(space, cfg)
        await svc.start()
        big = asyncio.ensure_future(
            svc.submit(PriceRequest(indices=list(range(space.size())))))
        await asyncio.sleep(0)            # let `big` admit, no ticks yet
        burst = await svc.submit(PriceRequest(indices=[0, 1, 2, 3, 4, 5]))
        r_big = await big
        # after draining, the same burst request is admitted again
        retry = await svc.submit(PriceRequest(indices=[0, 1, 2, 3, 4, 5]))
        await svc.stop()
        return burst, r_big, retry, svc

    burst, r_big, retry, svc = asyncio.run(_main())
    assert not burst.ok and burst.error.code == QUEUE_FULL
    assert r_big.ok and retry.ok
    assert svc.snapshot()["n_rejected"] == 1


# ---------------------------------------------------------------------------
# Warmup / trace discipline / caching / fairness
# ---------------------------------------------------------------------------


def test_trace_counts_constant_after_warmup(space):
    """After start() warms the configured lanes, a mixed workload leaves
    the jit trace counters untouched (no hot-path recompiles)."""

    async def _main():
        svc = PricingService(space, CFG)
        await svc.start()                 # warmup happens here
        before = dict(TRACE_COUNTS)
        reqs = [
            PriceRequest(indices=[0, 1, 2]),
            MCRiskRequest(indices=[3, 4], mc=McSpec(draws=64, seed=9)),
            RankRequest(indices=list(range(10)), top_k=3),
            WhatIfRequest(base=2),
            PriceSystemsRequest(specs=(
                {"kind": "soc", "name": "s", "area": 120.0,
                 "process": "7nm", "quantity": 1e6},)),
        ]
        resps = await asyncio.gather(*(svc.submit(r) for r in reqs))
        await svc.stop()
        return svc, before, dict(TRACE_COUNTS), resps

    svc, before, after, resps = asyncio.run(_main())
    assert all(r.ok for r in resps), [r.error for r in resps]
    assert after == before
    assert svc.snapshot()["recompiles_after_warmup"] == 0


def test_result_cache_hit(space, evaluator):
    """Re-submitting an identical sweep answers from the host cache —
    flagged, bit-exact, and without new device ticks."""

    async def _main():
        svc = PricingService(space, CFG)
        await svc.start()
        r1 = await svc.submit(PriceRequest(indices=[1, 3, 5]))
        ticks = svc.metrics.ticks
        r2 = await svc.submit(PriceRequest(indices=[1, 3, 5]))
        r3 = await svc.submit(PriceRequest(indices=[5, 3, 1]))  # order != hit
        await svc.stop()
        return svc, r1, ticks, r2, r3

    svc, r1, ticks, r2, r3 = asyncio.run(_main())
    assert r1.ok and r2.ok and r3.ok
    assert not r1.cached and r2.cached and not r3.cached
    assert svc.metrics.ticks > ticks     # r3 went to the device again
    _arrays_equal(r1.result, r2.result)
    _arrays_equal(r3.result,
                  evaluator.evaluate_indices(np.asarray([5, 3, 1])))
    assert svc.snapshot()["result_cache"]["hits"] == 1


def test_point_query_not_starved_by_sweep(space):
    """FIFO + chunk splitting: a point query submitted behind a
    space-sized sweep completes before the sweep does."""
    cfg = dataclasses.replace(CFG, chunk=8, split=2)
    done_order = []

    async def _main():
        svc = PricingService(space, cfg)
        await svc.start()

        async def client(tag, req):
            r = await svc.submit(req)
            done_order.append(tag)
            return r

        big, point = await asyncio.gather(
            client("big", PriceRequest(
                indices=list(range(space.size())) * 3)),
            client("point", PriceRequest(indices=[7])))
        await svc.stop()
        return big, point

    big, point = asyncio.run(_main())
    assert big.ok and point.ok
    assert done_order[0] == "point"
    assert point.latency_s <= big.latency_s


# ---------------------------------------------------------------------------
# Scheduler policy in isolation (no device work)
# ---------------------------------------------------------------------------


def _span(lane, n, start=0):
    return SpanWork(owner=object(), lane=lane,
                    idx=np.arange(start, start + n, dtype=np.int64))


def test_scheduler_split_fairness_and_rotation():
    sched = Scheduler(slots=8, split=2, max_pending=100)
    lane = Lane(kind="chunk")
    big = _span(lane, 20)
    small = _span(lane, 2, start=100)
    assert sched.admit([big], 20) and sched.admit([small], 2)
    plan = sched.plan()
    # pass 1 gives each item <= split slots; later passes refill from the
    # survivors, so the chunk still runs full
    assert plan.used == 8
    by_item = {}
    for a in plan.assignments:
        by_item.setdefault(id(a.item), 0)
        by_item[id(a.item)] += a.n
    assert by_item[id(small)] == 2          # the point query fully served
    assert by_item[id(big)] == 6
    # the big survivor rotated behind any newcomers
    assert sched.queue[0] is big and big.remaining == 14
    newcomer = _span(lane, 1, start=200)
    sched.admit([newcomer], 1)
    plan2 = sched.plan()
    served = {id(a.item) for a in plan2.assignments}
    assert id(newcomer) in served           # not starved by the sweep


def test_scheduler_lane_exclusivity_and_budget():
    sched = Scheduler(slots=8, split=8, max_pending=10)
    a = _span(Lane(kind="chunk", flow="chip-last"), 4)
    b = _span(Lane(kind="chunk", flow="chip-first"), 4)
    assert sched.admit([a], 4) and sched.admit([b], 4)
    assert not sched.admit([_span(Lane(kind="chunk"), 4)], 4)  # budget full
    plan = sched.plan()
    assert {id(x.item) for x in plan.assignments} == {id(a)}   # one lane
    assert plan.used == 4                   # no cross-lane fill
    sched.release(4)
    assert sched.admit([_span(Lane(kind="chunk"), 2)], 2)
    plan2 = sched.plan()
    assert plan2.lane == b.lane             # FIFO head defines the lane


def test_scheduler_drop_owned_by():
    sched = Scheduler(slots=4, max_pending=100)
    lane = Lane(kind="chunk")
    owner = object()
    w1 = SpanWork(owner=owner, lane=lane, idx=np.arange(3, dtype=np.int64))
    w2 = _span(lane, 2)
    sched.admit([w1, w2], 5)
    sched.drop_owned_by(owner)
    assert list(sched.queue) == [w2]
