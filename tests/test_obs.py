"""repro.obs: span tracer semantics (ring off by default, nesting /
parent attribution, Chrome export), metrics registry + TraceCounts shim,
JitProbe compile-vs-dispatch attribution, the service's fetch counters
(``jax.device_get`` itself never replaced), the flight recorder ring,
and the trace-count oracle — tracing a warmed fused sweep must not
retrace anything and must leave results bit-exact.
"""
import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs
from repro.obs import jaxhooks
from repro.obs.flight import FlightRecorder
from repro.obs.registry import Registry, REGISTRY, TraceCounts
from repro.obs.trace import TRACER, Tracer


@pytest.fixture
def traced():
    """Globally enable tracing for one test, restoring prior state."""
    was = obs.enabled()
    obs.enable()
    TRACER.clear()
    yield
    TRACER.clear()
    if not was:
        obs.disable()


# ---------------------------------------------------------------------------
# Tracer
# ---------------------------------------------------------------------------


def test_disabled_tracer_is_noop():
    tr = Tracer(enabled=False)
    with tr.span("tick"):
        assert tr.events() == [] and tr._stack() == []
    with tr.span("tick", lane="chunk"):
        pass
    tr.add_complete("kernel_dispatch", 0.5)
    tr.instant("marker")
    assert tr.events() == []
    assert tr.phase_table() == {}


def test_span_nesting_records_parents():
    tr = Tracer(enabled=True)
    with tr.span("tick", lane="chunk"):
        with tr.span("pack"):
            pass
        tr.add_complete("kernel_dispatch", 1e-4, fn="dse.chunk")
    evs = tr.events()
    by_name = {e["name"]: e for e in evs}
    assert by_name["pack"]["parent"] == "tick"
    assert by_name["kernel_dispatch"]["parent"] == "tick"
    assert by_name["tick"]["parent"] is None
    assert by_name["tick"]["labels"] == {"lane": "chunk"}
    # children close before the parent -> ordering in the ring
    assert [e["name"] for e in evs] == ["pack", "kernel_dispatch", "tick"]


def test_phase_table_coverage_and_count():
    tr = Tracer(enabled=True)
    for _ in range(3):
        with tr.span("tick"):
            t0 = time.perf_counter()
            time.sleep(0.002)
            tr.add_complete("kernel_dispatch", time.perf_counter() - t0)
    tbl = tr.phase_table()
    assert tbl["tick"]["count"] == 3
    assert tbl["kernel_dispatch"]["count"] == 3
    assert tbl["kernel_dispatch"]["total_s"] >= 6e-3
    assert tbl["kernel_dispatch"]["mean_s"] >= 2e-3
    # the dispatch child dominates the tick wall here
    assert 0.5 < tr.coverage("tick") <= 1.0
    assert tr.count("kernel_dispatch") == 3
    assert tr.count("kernel_dispatch", parent="tick") == 3
    assert tr.count("kernel_dispatch", parent="pack") == 0


def test_ring_capacity_bounds_memory():
    tr = Tracer(capacity=8, enabled=True)
    for i in range(50):
        tr.add_complete("x", 0.0, i=i)
    evs = tr.events()
    assert len(evs) == 8
    assert evs[-1]["labels"] == {"i": 49}


def test_chrome_export_is_valid_trace_event_json(tmp_path):
    tr = Tracer(enabled=True)
    with tr.span("tick", lane="mc"):
        tr.add_complete("device_get", 2e-4, bytes=128)
    path = tr.export_chrome(tmp_path / "trace.json")
    doc = json.loads(path.read_text())
    assert doc["displayTimeUnit"] == "ms"
    evs = doc["traceEvents"]
    assert evs and all(e["ph"] == "X" for e in evs)
    for e in evs:
        assert {"name", "ts", "dur", "pid", "tid", "cat"} <= set(e)
    dg = next(e for e in evs if e["name"] == "device_get")
    assert dg["args"]["bytes"] == 128 and dg["args"]["parent"] == "tick"


def test_enable_disable_runtime_toggle():
    was = obs.enabled()
    try:
        obs.enable()
        assert obs.enabled()
        obs.disable()
        assert not obs.enabled()
        n = len(TRACER.events())
        with TRACER.span("x"):
            pass
        assert len(TRACER.events()) == n
    finally:
        TRACER.clear()
        obs.enable(was)
        if not was:
            obs.disable()


# ---------------------------------------------------------------------------
# Registry
# ---------------------------------------------------------------------------


def test_registry_counter_gauge_histogram():
    reg = Registry()
    c = reg.counter("reqs", help="requests")
    c.inc()
    c.inc(4)
    assert c.get() == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = reg.gauge("depth")
    g.set(7)
    g.dec(2)
    assert g.get() == 5
    h = reg.histogram("lat")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    s = h.sample()
    assert s["count"] == 4 and s["sum"] == 10.0
    assert s["min"] == 1.0 and s["max"] == 4.0
    # get-or-create returns the same instrument; kind clashes are errors
    assert reg.counter("reqs") is c
    with pytest.raises(TypeError):
        reg.gauge("reqs")


def test_histogram_reservoir_decimates_deterministically():
    h = Registry().histogram("h", max_samples=64)
    for i in range(10_000):
        h.observe(float(i))
    assert h.count == 10_000
    assert len(h._samples) <= 64
    # quantiles stay ordered and within the observed range
    q = [h.quantile(x) for x in (0.0, 0.5, 0.95, 1.0)]
    assert q == sorted(q)
    assert 0.0 <= q[0] and q[-1] <= 9999.0


def test_registry_snapshot_and_exposition():
    reg = Registry()
    reg.counter("ticks", help="device ticks").inc(3)
    reg.histogram("lat").observe(0.5)
    snap = reg.snapshot()
    assert snap["ticks"] == {"kind": "counter", "value": 3.0}
    assert snap["lat"]["count"] == 1
    text = reg.exposition()
    assert "# HELP ticks device ticks" in text
    assert "# TYPE ticks counter" in text
    assert "ticks 3" in text
    assert "# TYPE lat histogram" in text
    assert "lat_count 1" in text
    assert 'lat{quantile="50"}' in text


def test_registry_write_json(tmp_path):
    reg = Registry()
    reg.counter("a").inc()
    path = reg.write_json(tmp_path / "metrics.json")
    assert json.loads(path.read_text())["a"]["value"] == 1.0


def test_trace_counts_is_counter_compatible_and_mirrors():
    reg = Registry()
    tc = TraceCounts(registry=reg, prefix="trace")
    tc["re"] += 1
    tc["re"] += 1
    tc["nre"] += 1
    assert dict(tc) == {"re": 2, "nre": 1}
    assert tc["missing"] == 0                      # Counter semantics
    assert dict(TraceCounts(registry=reg)) == {}
    assert reg.get("trace_re").get() == 2
    assert reg.get("trace_nre").get() == 1
    # the bench/test oracle idiom stays byte-compatible
    before = dict(tc)
    assert before == {"re": 2, "nre": 1}


# ---------------------------------------------------------------------------
# JitProbe + device_get hook
# ---------------------------------------------------------------------------


def test_jit_probe_attributes_compile_then_dispatch(traced):
    reg = Registry()
    counts = TraceCounts(registry=reg)

    def impl(x):
        counts["k"] += 1
        return x * 2.0

    probe = jaxhooks.instrument(jax.jit(impl), "test.fn",
                                trace_key="k", counts=counts)
    try:
        x = jnp.arange(4.0)
        probe(x)                                   # first call: traces
        probe(x)
        probe(x)                                   # steady state
        st = probe.summary()
        assert st["signatures"] == 1
        assert st["compiles"] == 1 and st["calls"] == 2
        assert st["compile_s"] > 0 and st["dispatch_s"] > 0
        # a new shape is a new signature and a fresh compile
        probe(jnp.arange(8.0))
        st = probe.summary()
        assert st["signatures"] == 2 and st["compiles"] == 2
        assert TRACER.count("jit_compile") == 2
        assert TRACER.count("kernel_dispatch") == 2
    finally:
        jaxhooks._PROBES.remove(probe)


def test_jit_probe_disabled_is_passthrough():
    assert not obs.enabled()

    def impl(x):
        return x + 1

    probe = jaxhooks.instrument(jax.jit(impl), "test.off")
    try:
        out = probe(jnp.arange(3))
        assert np.array_equal(np.asarray(out), [1, 2, 3])
        assert probe.stats == {}                   # nothing recorded
    finally:
        jaxhooks._PROBES.remove(probe)


def test_device_get_hook_counts_calls_and_bytes():
    """The tick sync counts its calls and bytes where it happens: one
    fetch per tick, of the chunk's five outputs."""
    from repro.dse import DesignSpace, SKU
    from repro.service import PriceRequest, ServiceConfig, serve

    space = DesignSpace(
        skus=(SKU("a", 200.0, 1e6),), processes=("7nm",),
        integrations=("MCM",), chiplet_counts=(1, 2), allow_reuse=False)
    before = jaxhooks.device_get_stats()
    resps, svc = serve(space, [PriceRequest(indices=[0, 1])],
                       ServiceConfig(chunk=8, raw_slots=0,
                                     fallback=False))
    assert resps[0].ok, resps[0].error
    after = jaxhooks.device_get_stats()
    m = svc.metrics
    assert m.ticks == 1 and m.device_get_calls == 1
    # unit/re/nre (8, 1) float32, portfolio cost (8,) float32, finite (8,)
    assert m.device_get_bytes == 3 * 8 * 4 + 8 * 4 + 8
    assert after["calls"] - before["calls"] == 1
    assert after["bytes"] - before["bytes"] == m.device_get_bytes
    assert after["total_s"] - before["total_s"] == \
        pytest.approx(m.device_wait_s)
    assert 0 < m.device_wait_s <= m.busy_s


def test_device_get_is_never_replaced():
    from jax._src.api import device_get as jax_own
    was = obs.enabled()
    try:
        obs.enable()
        assert jax.device_get is jax_own
        obs.disable()
        assert jax.device_get is jax_own
    finally:
        TRACER.clear()
        obs.enable(was)


# ---------------------------------------------------------------------------
# Flight recorder
# ---------------------------------------------------------------------------


def test_flight_recorder_ring_and_dump(tmp_path):
    fr = FlightRecorder(capacity=4)
    for i in range(10):
        fr.record("tick", lane="chunk", rows=i, wall_s=1e-3)
    fr.record("request_error", uid=9, kind="price", error="boom")
    assert len(fr) == 4
    assert fr.n_recorded == 11
    recs = fr.records()
    assert recs[-1]["event"] == "request_error"
    assert fr.records(event="tick")[-1]["rows"] == 9
    path = fr.dump(tmp_path / "flight.json")
    doc = json.loads(path.read_text())
    evs = doc["traceEvents"]
    assert len(evs) == 4
    # durationful records export as complete spans, the rest as instants
    phs = {e["name"]: e["ph"] for e in evs}
    assert phs["tick"] == "X" and phs["request_error"] == "i"


def test_flight_recorder_dump_merges_extra_events(tmp_path):
    fr = FlightRecorder()
    fr.record("tick", wall_s=1e-3)
    extra = [{"name": "kernel_dispatch", "ph": "X", "ts": 0.0, "dur": 1.0,
              "pid": 1, "tid": 1, "cat": "repro", "args": {}}]
    doc = json.loads(fr.dump(tmp_path / "f.json",
                             extra_events=extra).read_text())
    assert {e["name"] for e in doc["traceEvents"]} == \
        {"tick", "kernel_dispatch"}


# ---------------------------------------------------------------------------
# The oracle: tracing a warmed sweep neither retraces nor changes results
# ---------------------------------------------------------------------------


def test_tracing_warmed_sweep_no_retrace_bit_exact():
    from repro.core.engine import TRACE_COUNTS
    from repro.dse import ChunkedEvaluator, DesignSpace, SKU

    space = DesignSpace(
        skus=(SKU("a", 200.0, 1e6),), processes=("7nm",),
        integrations=("MCM",), chiplet_counts=(1, 2), allow_reuse=False)
    ev = ChunkedEvaluator(space, candidates_per_chunk=8)
    idx = np.arange(space.size(), dtype=np.int64)
    ev.evaluate_indices(idx)                       # warm the trace
    baseline = ev.evaluate_indices(idx)            # untraced reference
    warm = dict(TRACE_COUNTS)

    obs.enable()
    TRACER.clear()
    try:
        traced = ev.evaluate_indices(idx)
    finally:
        obs.disable()
        TRACER.clear()

    assert dict(TRACE_COUNTS) == warm, \
        "enabling tracing retraced a warmed signature"
    assert np.array_equal(np.asarray(traced.portfolio_cost),
                          np.asarray(baseline.portfolio_cost))
    assert np.array_equal(np.asarray(traced.sku_unit_total),
                          np.asarray(baseline.sku_unit_total))


def test_trace_counts_global_mirrors_registry():
    from repro.core.engine import TRACE_COUNTS
    assert isinstance(TRACE_COUNTS, TraceCounts)
    for key, n in TRACE_COUNTS.items():
        m = REGISTRY.get(f"trace_{key}")
        assert m is not None, f"trace_{key} not mirrored"
        assert m.get() >= 1 if n else True
