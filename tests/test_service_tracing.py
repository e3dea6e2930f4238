"""The service's spans on the profiler's clock, and the always-on counters
of where a request's time goes: admission, queue wait before its first
tick, and the wait in each tick's fetch."""
import asyncio
import pathlib

import jax
import pytest

from repro.dse import DesignSpace, SKU
from repro.service import (PriceRequest, PriceSystemsRequest, PricingService,
                           RankRequest, SearchRequest, ServiceConfig)

SPACE = DesignSpace(
    skus=(SKU("laptop", 200.0, 2e6), SKU("server", 400.0, 5e5)),
    processes=("7nm", "12nm"), integrations=("MCM",),
    chiplet_counts=(1, 2, 4), allow_reuse=True)
CFG = ServiceConfig(chunk=16, split=4, raw_slots=4, fallback=False)
RAW = PriceSystemsRequest(specs=(
    {"kind": "soc", "name": "s", "area": 120.0, "process": "7nm",
     "quantity": 1e6},
    {"kind": "split", "name": "b", "area": 300.0, "process": "7nm",
     "n_chiplets": 2, "integration": "MCM", "quantity": 5e5}))


def _run(*batches, cfg=CFG):
    """Serve each batch of requests concurrently, one batch after the
    other; returns the stopped service and every response."""
    async def main():
        svc = PricingService(SPACE, cfg)
        await svc.start()
        out = []
        for batch in batches:
            out += await asyncio.gather(*(svc.submit(r) for r in batch))
        await svc.stop()
        return svc, out
    return asyncio.run(main())


SPANS = ("repro.admit", "repro.lower", "repro.tick", "repro.pack",
         "repro.device_wait", "repro.scatter", "repro.finish")


def test_profiler_sees_service_spans_nested(tmp_path):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        svc, resps = _run([PriceRequest(indices=[0, 3, 5]),
                           RankRequest(indices=list(range(12)), top_k=3),
                           RAW])
    finally:
        jax.profiler.stop_trace()
    assert all(r.ok for r in resps), [r.error for r in resps]
    path = sorted(pathlib.Path(tmp_path).rglob("*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    lines = [ln for pl in data.planes if pl.name.startswith("/host:")
             for ln in pl.lines
             if any(e.name.startswith("repro.") for e in ln.events)]
    assert len(lines) == 1, [ln.name for ln in lines]
    # by start, the enclosing span first where two start together
    spans = sorted(((float(e.start_ns), float(e.start_ns + e.duration_ns),
                     e.name) for e in lines[0].events
                    if e.name.startswith("repro.")),
                   key=lambda s: (s[0], -s[1]))
    names = {n for _, _, n in spans}
    assert set(SPANS) <= names, names

    def inside(child, parent):
        return [c for c in spans if c[2] == child
                and not any(p[2] == parent and p[0] <= c[0] and c[1] <= p[1]
                            for p in spans)]
    assert inside("repro.lower", "repro.admit") == []
    for child in ("repro.pack", "repro.device_wait", "repro.scatter"):
        assert inside(child, "repro.tick") == [], child
    # spans nest or are disjoint: no span outlives the span it began in
    for i, (a0, a1, an) in enumerate(spans):
        for b0, b1, bn in spans[i + 1:]:
            if b0 >= a1:
                break
            assert b1 <= a1, (an, bn)
    assert svc.metrics.ticks == sum(1 for s in spans if s[2] == "repro.tick")


def test_counters_count_where_the_work_happens():
    price = PriceRequest(indices=[1, 2, 3, 4])
    svc, resps = _run(
        [price, RankRequest(indices=list(range(20)), top_k=4), RAW,
         SearchRequest(seed=1, population=8, generations=3, elite=2),
         PriceRequest(indices=[])],                  # rejected at admission
        [PriceRequest(indices=[1, 2, 3, 4])])        # a result-cache hit
    assert [r.ok for r in resps] == [True, True, True, True, False, True]
    assert resps[-1].cached
    m = svc.metrics
    # the rejection and the cache hit never enter the queue
    assert m.admitted == 4
    assert 0 < m.admit_s
    # each enqueued request counts its wait once, however many ticks
    # it rides (the search: one per generation, then its ranking sweep)
    assert m.queue_waited == 4
    waits = [r.queue_wait_s for r in m.requests if r.queue_wait_s is not None]
    assert len(waits) == 4 and m.queue_wait_s == pytest.approx(sum(waits))
    assert m.device_get_calls == m.ticks >= 5
    assert 0 < m.device_wait_s <= m.busy_s
    snap = svc.snapshot()
    assert snap["admitted"] == 4 and snap["queue_waited"] == 4
    assert snap["device_gets"] == snap["ticks"]


def test_queue_wait_covers_the_other_lanes_tick():
    """A price request admitted behind a queued raw group waits at least
    the raw lane's whole tick: a tick serves one lane."""
    svc, resps = _run([RAW, PriceRequest(indices=[0, 1])])
    assert all(r.ok for r in resps)
    raw, price = svc.metrics.requests
    assert (raw.kind, price.kind) == ("price_systems", "price")
    lanes = svc.metrics.per_lane
    assert lanes["raw"].ticks == 1 and lanes["chunk"].ticks == 1
    assert price.queue_wait_s >= lanes["raw"].busy_s > 0
    assert raw.queue_wait_s < price.queue_wait_s
