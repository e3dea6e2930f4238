"""repro.service.metrics in isolation: quantile edge cases, cached- and
failed-request accounting, the per-lane occupancy fix (gen ticks now
count toward slot occupancy instead of being a blind spot), snapshot key
stability, and the registry mirroring of service counters."""
import time

import pytest

from repro.obs.registry import REGISTRY
from repro.service.metrics import LaneStats, ServiceMetrics, _quantiles


# ---------------------------------------------------------------------------
# Quantile helper edge cases
# ---------------------------------------------------------------------------


def test_quantiles_empty_list_is_zeros():
    assert _quantiles([]) == {"p50": 0.0, "p95": 0.0, "p99": 0.0,
                              "mean": 0.0}


def test_quantiles_single_sample_is_that_sample():
    q = _quantiles([0.25])
    assert q["p50"] == q["p95"] == q["p99"] == q["mean"] == 0.25


def test_quantiles_are_ordered():
    q = _quantiles([float(i) for i in range(100)])
    assert q["p50"] <= q["p95"] <= q["p99"]
    assert q["mean"] == pytest.approx(49.5)


# ---------------------------------------------------------------------------
# Request lifecycle
# ---------------------------------------------------------------------------


def test_cached_request_ttfr_equals_latency():
    m = ServiceMetrics()
    rec = m.start_request("price", 8, time.perf_counter())
    m.finish_request(rec, ok=True, cached=True)    # t_first never set
    assert rec.cached
    assert rec.t_first == rec.t_done
    assert rec.ttfr_s == rec.latency_s
    assert rec.latency_s >= 0.0
    snap = m.snapshot()
    assert snap["n_ok"] == 1
    assert snap["latency_s"]["p50"] == pytest.approx(rec.latency_s)


def test_error_and_rejection_counting():
    m = ServiceMetrics()
    ok = m.start_request("price", 4, time.perf_counter())
    m.finish_request(ok, ok=True)
    bad = m.start_request("search", 0, time.perf_counter())
    m.finish_request(bad, ok=False)
    m.reject()
    m.reject()
    snap = m.snapshot()
    assert snap["n_requests"] == 2
    assert snap["n_ok"] == 1
    assert snap["n_errors"] == 1
    assert snap["n_rejected"] == 2
    assert snap["requests_by_kind"] == {"price": 1, "search": 1}
    # failed requests don't poison the ok-latency quantiles
    assert snap["latency_s"]["p50"] == pytest.approx(ok.latency_s)


# ---------------------------------------------------------------------------
# Tick accounting: per-lane occupancy including the gen lane
# ---------------------------------------------------------------------------


def test_gen_ticks_count_toward_occupancy():
    m = ServiceMetrics()
    m.record_tick("chunk", slots=16, used=8, rows_priced=8, wall_s=0.010)
    m.record_tick("gen", slots=32, used=32, rows_priced=32, wall_s=0.020)
    snap = m.snapshot()
    # gen work is IN the aggregate now: (8+32)/(16+32)
    assert snap["slot_occupancy"] == pytest.approx(40 / 48)
    assert snap["padded_waste_frac"] == pytest.approx(1 - 40 / 48)
    assert snap["rows_priced"] == 40
    assert snap["ticks"] == 2 and snap["gen_ticks"] == 1
    # fetches are counted where they happen, not per recorded tick
    assert snap["device_gets"] == 0
    assert snap["busy_s"] == pytest.approx(0.030)


def test_per_lane_breakdown():
    m = ServiceMetrics()
    m.record_tick("chunk", 16, 8, 8, 0.010)
    m.record_tick("chunk", 16, 16, 16, 0.012)
    m.record_tick("gen", 32, 32, 32, 0.020)
    m.record_tick("mc", 16, 4, 4, 0.005)
    snap = m.snapshot()
    per = snap["per_lane"]
    assert set(per) == {"chunk", "gen", "mc"}
    assert per["chunk"]["ticks"] == 2
    assert per["chunk"]["occupancy"] == pytest.approx(24 / 32)
    assert per["chunk"]["padded_waste_frac"] == pytest.approx(1 - 24 / 32)
    assert per["gen"]["occupancy"] == 1.0
    assert per["gen"]["rows_priced"] == 32
    assert per["mc"]["occupancy"] == pytest.approx(4 / 16)
    assert snap["ticks_by_lane"] == {"chunk": 2, "gen": 1, "mc": 1}
    # rows_priced is consistent: lanes sum to the aggregate
    assert sum(l["rows_priced"] for l in per.values()) \
        == snap["rows_priced"]


def test_lane_stats_empty_division_guards():
    ls = LaneStats()
    assert ls.occupancy == 0.0
    d = ls.as_dict()
    assert d["occupancy"] == 0.0 and d["padded_waste_frac"] == 0.0
    m = ServiceMetrics()
    snap = m.snapshot()
    assert snap["slot_occupancy"] == 0.0
    assert snap["rows_per_sec_busy"] == 0.0


# ---------------------------------------------------------------------------
# Snapshot surface stability (bench/CI consumers key on these)
# ---------------------------------------------------------------------------

EXPECTED_KEYS = {
    "n_requests", "n_done", "n_ok", "n_errors", "n_rejected",
    "requests_by_kind", "latency_s", "ttfr_s", "ticks", "device_gets",
    "gen_ticks", "ticks_by_lane", "per_lane", "slot_occupancy",
    "padded_waste_frac", "rows_priced", "busy_s", "rows_per_sec_busy",
    "wall_s", "admit_s", "admitted", "queue_wait_s", "queue_waited",
    "device_wait_s", "device_get_bytes", "raw_pack_s", "raw_packs",
    "ticks_overlapped",
}


def test_snapshot_key_stability():
    m = ServiceMetrics()
    assert set(m.snapshot()) == EXPECTED_KEYS
    snap = m.snapshot(trace_stats={"tick_recompiles": 0},
                      cache_stats={"hits": 1})
    assert set(snap) == EXPECTED_KEYS | {"trace", "result_cache",
                                         "recompiles_after_warmup"}
    assert snap["recompiles_after_warmup"] == 0


def test_write_json_roundtrip(tmp_path):
    import json
    m = ServiceMetrics()
    m.record_tick("chunk", 8, 8, 8, 0.001)
    path = m.write_json(tmp_path / "snap.json")
    doc = json.loads(path.read_text())
    assert doc["ticks"] == 1
    assert doc["per_lane"]["chunk"]["occupancy"] == 1.0


# ---------------------------------------------------------------------------
# Registry mirroring
# ---------------------------------------------------------------------------


def test_service_counters_mirrored_into_registry():
    before_req = (REGISTRY.get("service_requests").get()
                  if REGISTRY.get("service_requests") else 0)
    before_tick = (REGISTRY.get("service_ticks").get()
                   if REGISTRY.get("service_ticks") else 0)
    m = ServiceMetrics()
    rec = m.start_request("price", 4, time.perf_counter())
    m.finish_request(rec, ok=True)
    m.record_tick("chunk", 8, 8, 8, 0.001)
    assert REGISTRY.get("service_requests").get() == before_req + 1
    assert REGISTRY.get("service_ticks").get() == before_tick + 1
    assert REGISTRY.get("service_latency_s").count >= 1
