"""Pricing-service throughput benchmark: N concurrent clients with a
mixed request diet against one continuous-batching PricingService.

  PYTHONPATH=src python -m benchmarks.service_bench [--fast] [--clients N]

Each client interleaves large price sweeps with point queries; dedicated
clients add Monte-Carlo risk sweeps, ranking, what-if grids and an
evolutionary search, so every service lane (chunk / mc / gen / raw) sees
traffic while the scheduler coalesces across clients.

Asserts (acceptance criteria of the service):
  * ZERO jit recompiles after the warmup tick — every lane the workload
    touches was compiled at startup or admission, never on the tick loop;
  * aggregate coalesced throughput >= 0.5x the single-client fused
    ``ChunkedEvaluator`` rate under >= 8 concurrent clients (the
    continuous-batching overhead bound; skipped under --fast where the
    sample is too small to be stable, which instead enforces a loose p95
    latency ceiling for CI smoke);
  * EVERY answered request carries a trace_id and a closed ledger bill,
    per-tick bills sum to the measured tick wall within 5% with zero
    unattributed device ms, and (traced runs) every request's span tree
    is complete: admission marker + terminal marker, plus a billed tick
    for every request that reached the device.

``--slo`` additionally enables the declarative SLO tracker
(latency + availability objectives over a sliding window) and folds its
error-budget snapshot into BENCH_service.json.

Reports aggregate candidates/s, request latency p50/p95/p99, padded-slot
waste, and cache/recompile counters, and writes BENCH_service.json for
CI trend tracking (guarded against benchmarks/baselines/ by
scripts/check_bench_regression.py).
"""
import argparse
import asyncio
import json
import time

import numpy as np

from repro import obs
from repro.dse import ChunkedEvaluator
from repro.service import (McSpec, MCRiskRequest, PriceRequest,
                           PriceSystemsRequest, PricingService, RankRequest,
                           SearchRequest, SearchWarmup, ServiceConfig,
                           WhatIfRequest)
from repro.service.cache import use_compile_cache

from .common import REPO_ROOT, emit, write_bench_json
from .dse_bench import SPACE


def _client_requests(i: int, rng: np.random.Generator, size: int,
                     sweeps: int, sweep_rows: int, fast: bool):
    """The mixed diet of client ``i`` (deterministic in the seed)."""
    reqs = []
    for _ in range(sweeps):
        reqs.append(PriceRequest(
            indices=rng.integers(0, size, sweep_rows).tolist()))
        reqs.append(PriceRequest(indices=rng.integers(0, size, 4).tolist()))
    if i == 0:
        reqs.append(SearchRequest(seed=1, population=32,
                                  generations=3 if fast else 8, elite=8))
    elif i == 1:
        reqs.append(MCRiskRequest(
            indices=rng.integers(0, size, 64).tolist(),
            mc=McSpec(draws=64, quantiles=(0.5, 0.9), seed=0)))
    elif i == 2:
        reqs.append(WhatIfRequest(base=int(rng.integers(0, size))))
    elif i == 3:
        reqs.append(RankRequest(indices=rng.integers(0, size, 128).tolist(),
                                top_k=5))
    elif i == 4:
        reqs.append(PriceSystemsRequest(specs=(
            {"kind": "soc", "name": "soc_a", "area": 250.0,
             "process": "7nm", "quantity": 1e6},
            {"kind": "split", "name": "mcm_b", "area": 500.0,
             "process": "7nm", "n_chiplets": 2, "integration": "MCM",
             "quantity": 5e5},)))
    return reqs


def run(fast: bool = False, clients: int = 8, slo: bool = False) -> dict:
    size = SPACE.size()
    chunk = 64 if fast else 128
    sweep_rows = 256 if fast else 2048
    sweeps = 2 if fast else 4
    slos = ()
    if slo:
        from repro.obs.slo import SLObjective
        # generous bounds for shared CI boxes: the point of the smoke is
        # that the tracker runs and snapshots, not that CI hardware is
        # fast; the real latency assertions below stay authoritative.
        slos = (SLObjective(kind="*", latency_ms=30_000.0,
                            latency_target=0.95, availability=0.95,
                            window_s=300.0),)
    cfg = ServiceConfig(
        chunk=chunk, split=max(8, chunk // 4),
        warm_mc=((64, (0.5, 0.9)),),
        warm_search=(SearchWarmup(population=32, elite=8),),
        max_pending=10_000_000, slos=slos)

    # -- single-client fused baseline (the 0.5x yardstick) -----------------
    ev = ChunkedEvaluator(SPACE, candidates_per_chunk=chunk)
    rng = np.random.default_rng(0)
    idx = rng.integers(0, size, 4 * sweep_rows)
    ev.evaluate_indices(idx[:chunk])                       # compile
    t0 = time.perf_counter()
    ev.evaluate_indices(idx)
    single = idx.size / (time.perf_counter() - t0)

    # -- the concurrent mixed workload -------------------------------------
    async def _main():
        svc = PricingService(SPACE, cfg)
        await svc.start()                                  # warmup

        async def client(i: int):
            crng = np.random.default_rng(100 + i)
            out = []
            for req in _client_requests(i, crng, size, sweeps, sweep_rows,
                                        fast):
                out.append(await svc.submit(req))
            return out

        t0 = time.perf_counter()
        per_client = await asyncio.gather(*(client(i)
                                            for i in range(clients)))
        wall = time.perf_counter() - t0
        await svc.stop()
        return per_client, wall, svc

    per_client, wall, svc = asyncio.run(_main())
    flat = [r for rs in per_client for r in rs]
    bad = [r for r in flat if not r.ok]
    assert not bad, f"{len(bad)} requests failed: {bad[0].error}"

    snap = svc.snapshot()
    agg = snap["rows_priced"] / wall
    summary = {
        "clients": clients,
        "n_requests": snap["n_ok"],
        "rows_priced": snap["rows_priced"],
        "wall_s": wall,
        "agg_candidates_per_sec": agg,
        "single_client_candidates_per_sec": single,
        "vs_single_client": agg / single,
        "latency_p50_s": snap["latency_s"]["p50"],
        "latency_p95_s": snap["latency_s"]["p95"],
        "latency_p99_s": snap["latency_s"]["p99"],
        "ttfr_p50_s": snap["ttfr_s"]["p50"],
        "ticks": snap["ticks"],
        "device_gets": snap["device_gets"],
        "slot_occupancy": snap["slot_occupancy"],
        "padded_waste_frac": snap["padded_waste_frac"],
        "recompiles_after_warmup": snap["recompiles_after_warmup"],
        "result_cache_hits": snap["result_cache"]["hits"],
        "ledger_ticks_charged": snap["ledger"]["ticks_charged"],
        "ledger_device_ms_total": snap["ledger"]["device_ms_total"],
        "ledger_tick_residual_rel_max":
            snap["ledger"]["tick_residual_rel_max"],
        "ledger_unattributed_ms": snap["ledger"]["unattributed_ms"],
        "ledger_bills_closed": snap["ledger"]["closed"],
        "ledger_by_kind": snap["ledger"]["by_kind"],
        "fast": fast,
    }
    if slo:
        summary["slo"] = snap["slo"]
    if obs.enabled():
        # per-phase breakdown (compile / dispatch / device_get / pack /
        # scatter) rides along only on traced runs, so untraced
        # BENCH_service.json keys never change.
        summary["phases"] = snap["obs"]["phases"]
        summary["jit"] = snap["obs"]["jit"]
        summary["device_get"] = snap["obs"]["device_get"]
        summary["tick_coverage"] = snap["obs"]["tick_coverage"]
        summary["recompiles_in_ticks"] = snap["obs"]["recompiles_in_ticks"]
    emit("service: mixed workload", [{
        "clients": clients, "requests": summary["n_requests"],
        "rows": summary["rows_priced"],
        "agg_cands_per_sec": agg, "single_client": single,
        "vs_single": summary["vs_single_client"],
        "p50_ms": summary["latency_p50_s"] * 1e3,
        "p95_ms": summary["latency_p95_s"] * 1e3,
        "p99_ms": summary["latency_p99_s"] * 1e3,
        "occupancy": summary["slot_occupancy"],
        "recompiles": summary["recompiles_after_warmup"]}])
    write_bench_json("service", summary)

    # -- acceptance --------------------------------------------------------
    assert snap["device_gets"] == snap["ticks"], \
        "tick loop must sync exactly once per tick"
    assert summary["recompiles_after_warmup"] == 0, \
        f"hot path recompiled {summary['recompiles_after_warmup']}x"
    # serving-cost ledger: every answered request is billed, and the
    # bills are a true decomposition of the measured tick wall.
    unbilled = [r for r in flat if not r.trace_id or r.bill is None
                or r.bill["status"] == "open"]
    assert not unbilled, \
        f"{len(unbilled)} responses lack a trace_id/closed ledger bill"
    led = snap["ledger"]
    assert led["open"] == 0, f"{led['open']} bills left open after drain"
    assert led["tick_residual_rel_max"] <= 0.05, \
        (f"per-tick bills diverge from measured tick wall by "
         f"{led['tick_residual_rel_max']:.1%} (need <= 5%)")
    assert led["unattributed_ms"] == 0.0, \
        f"{led['unattributed_ms']:.3f} device ms billed to nobody"
    if obs.enabled():
        # traced run: export the Perfetto trace + registry snapshot and
        # hold the tracer to its own acceptance bar — spans must account
        # for >= 90% of measured tick wall, and the tracer's independent
        # compile attribution must agree that warmed ticks never retrace.
        from repro.obs.registry import REGISTRY
        trace_path = svc.dump_flight_recorder(
            REPO_ROOT / "BENCH_service_trace.json")
        doc = json.loads(trace_path.read_text())
        assert doc.get("traceEvents"), "trace export produced no events"
        REGISTRY.write_json(REPO_ROOT / "BENCH_service_metrics.json")
        print(f"# wrote {trace_path}")
        print(f"# wrote {REPO_ROOT / 'BENCH_service_metrics.json'}")
        cov = summary["tick_coverage"]
        assert cov >= 0.9, \
            f"trace spans cover {cov:.1%} of tick wall (need >= 90%)"
        assert summary["recompiles_in_ticks"] == 0, \
            (f"tracer attributed {summary['recompiles_in_ticks']} "
             f"jit compiles to warmed ticks")
        # span-tree completeness: every response's trace_id must resolve
        # to an admission marker, a terminal marker and — for answers
        # that reached the device — at least one tick span that billed it.
        from repro.obs.trace import TRACER
        for r in flat:
            tree = TRACER.trace_tree(r.trace_id)
            names = {ev["name"] for ev in tree}
            assert "request_admit" in names, \
                f"trace {r.trace_id}: no admission marker"
            assert names & {"request_done", "request_error"}, \
                f"trace {r.trace_id}: no terminal marker"
            if r.ok and not r.cached:
                assert "tick" in names, \
                    f"trace {r.trace_id}: answered on-device without a tick"
        print(f"# service: traced run — {cov:.1%} tick coverage, "
              f"0 tracer-attributed tick recompiles, "
              f"{len(flat)} complete span trees")
    if fast:
        # CI smoke: tiny sample, shared boxes — just a sanity ceiling
        assert summary["latency_p95_s"] < 30.0, \
            f"p95 {summary['latency_p95_s']:.2f}s absurd for the smoke load"
    else:
        assert summary["vs_single_client"] >= 0.5, \
            (f"coalesced throughput {agg:,.0f} cands/s is "
             f"{summary['vs_single_client']:.2f}x the single-client rate "
             f"{single:,.0f} (need >= 0.5x)")
    print(f"# service: {agg:,.0f} cands/s across {clients} clients "
          f"({summary['vs_single_client']:.2f}x single-client), "
          f"p95 {summary['latency_p95_s']*1e3:.1f} ms, "
          f"0 hot-path recompiles")
    print(f"# ledger: {led['closed']} bills over {led['ticks_charged']} "
          f"ticks, worst tick residual {led['tick_residual_rel_max']:.2e}, "
          f"unattributed {led['unattributed_ms']:.3f} ms")
    return summary


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: small sweeps, loose bounds")
    ap.add_argument("--clients", type=int, default=8)
    ap.add_argument("--slo", action="store_true",
                    help="enable the SLO/error-budget tracker and fold "
                         "its snapshot into BENCH_service.json")
    args = ap.parse_args()
    use_compile_cache()
    run(fast=args.fast, clients=args.clients, slo=args.slo)


if __name__ == "__main__":
    main()
