"""Chaos benchmark: a seeded fault schedule against a live service.

  REPRO_FAULTS="seed=...;..." PYTHONPATH=src \\
      python -m benchmarks.chaos_bench [--fast]

Drives a mixed concurrent workload (price sweeps, Monte-Carlo risk,
ranking, search, raw specs, tiny-deadline requests, one deliberately
invalid request) through a PricingService while the
:mod:`repro.resilience` fault injector fires every fault kind it knows:
fused-dispatch exceptions, a tick stall long enough to trip the
watchdog, poisoned candidate rows, admission floods, and forced
recompiles.  The schedule comes from ``REPRO_FAULTS`` when set (the CI
chaos-smoke job sets it) and falls back to :data:`DEFAULT_FAULTS`.

Asserts (the degraded-mode guarantees of README "Failure handling"):
  * every response is ok or carries a **typed** error envelope — zero
    untyped/internal errors, zero exceptions escaping the tick loop;
  * zero cross-request contamination: every ok price/mc_risk row is
    bit-exact against the oracle its provenance names — the fused
    evaluator for fused rows, float32 casts of the legacy host-packing
    evaluator for degraded rows;
  * exactly one watchdog trip AND one flight recording per induced
    stall;
  * every fault kind in the schedule actually fired (a chaos run that
    quietly tested nothing must fail).

A second, separate sub-run injects the ``crash`` fault kind mid-search
(the moral equivalent of SIGKILL at a tick boundary), restarts the
service over the same durability directory, and proves the journal
replay recovers everything: zero requests lost, the resumed search
bit-exact against the uninterrupted ``portfolio_search`` oracle, and
recovery latency reported.

Reports recovery latency (circuit-breaker open time) and degraded-mode
throughput (fallback rows/s), and writes BENCH_chaos.json for
scripts/check_bench_regression.py.
"""
import argparse
import asyncio
import os
import pathlib
import tempfile
import time

import jax
import numpy as np

from repro.dse import ChunkedEvaluator, portfolio_search
from repro.resilience import FaultInjector
from repro.service import (DEADLINE_EXCEEDED, DurabilityConfig,
                           INVALID_REQUEST, McSpec,
                           MCRiskRequest, NUMERICAL_ERROR, PriceRequest,
                           PriceSystemsRequest, PricingService, QUEUE_FULL,
                           RankRequest, RequestJournal, SearchRequest,
                           SearchWarmup, ServiceConfig, SHUTTING_DOWN)
from repro.service.cache import use_compile_cache

from .common import emit, write_bench_json
from .dse_bench import SPACE

# The closed set a client may dispatch on; anything else is a bug.
TYPED_CODES = {QUEUE_FULL, INVALID_REQUEST, DEADLINE_EXCEEDED,
               NUMERICAL_ERROR, SHUTTING_DOWN}

# Every kind enabled, tuned so the seeded schedule exercises each one
# within a --fast run: one long stall (watchdog food), a steady diet of
# dispatch errors (breaker + fallback), a few poisoned rows, floods and
# recompiles.
DEFAULT_FAULTS = ("seed=1337;dispatch_error:p=0.35;stall:p=1.0,ms=1200,n=1;"
                  "poison:p=0.3,n=4;flood:p=0.3,n=3;recompile:p=0.4,n=2")

MC = dict(draws=32, quantiles=(0.5, 0.9), seed=0)


def _requests(rng: np.random.Generator, size: int, fast: bool):
    """The mixed chaos diet: (request, parity_kind) pairs.

    ``parity_kind`` says which oracle (if any) can check the response's
    rows bit-exactly: "price", "mc" or None."""
    sweep = 64 if fast else 128
    n_sweeps = 4 if fast else 8
    out = []
    for _ in range(n_sweeps):
        out.append((PriceRequest(
            indices=rng.integers(0, size, sweep).tolist()), "price"))
        out.append((PriceRequest(
            indices=rng.integers(0, size, 4).tolist()), "price"))
    out.append((MCRiskRequest(
        indices=rng.integers(0, size, 32).tolist(),
        mc=McSpec(**MC)), "mc"))
    out.append((RankRequest(
        indices=rng.integers(0, size, 48).tolist(), top_k=5), None))
    out.append((SearchRequest(seed=3, population=16,
                              generations=2 if fast else 4, elite=4), None))
    out.append((PriceSystemsRequest(specs=(
        {"kind": "soc", "name": "soc_a", "area": 250.0,
         "process": "7nm", "quantity": 1e6},)), None))
    # deadlines that cannot realistically be met: must come back as
    # typed deadline_exceeded (or, if the box is absurdly fast, ok)
    for _ in range(2):
        out.append((PriceRequest(
            indices=rng.integers(0, size, sweep).tolist(),
            deadline_ms=0.5), "price"))
    # one deliberately invalid request: NaN area must be rejected at
    # admission, never reach a kernel next to the others
    out.append((PriceSystemsRequest(specs=(
        {"kind": "soc", "name": "broken", "area": float("nan"),
         "process": "7nm", "quantity": 1e6},)), None))
    return out


def _parity_mismatches(resp, idx, kind, fused_ev, legacy_ev) -> int:
    """Count rows of an ok response that match NEITHER provenance
    oracle's value — i.e. contaminated rows."""
    idx = np.asarray(idx, np.int64)
    mask = (resp.degraded_rows if resp.degraded and resp.degraded_rows
            is not None else np.zeros(idx.size, bool))
    if kind == "mc":
        key = jax.random.PRNGKey(MC["seed"])
        fused = fused_ev.evaluate_indices(idx, mc_key=key,
                                          mc_draws=MC["draws"],
                                          mc_quantiles=MC["quantiles"])
        legacy = legacy_ev.evaluate_indices_legacy(
            idx, mc_key=key, mc_draws=MC["draws"],
            mc_quantiles=MC["quantiles"]) if mask.any() else None
    else:
        fused = fused_ev.evaluate_indices(idx)
        legacy = (legacy_ev.evaluate_indices_legacy(idx)
                  if mask.any() else None)
    bad = 0
    for j in range(idx.size):
        src = legacy if mask[j] else fused
        ok = (np.array_equal(resp.result.sku_unit_total[j],
                             src.sku_unit_total[j])
              and resp.result.portfolio_cost[j] == src.portfolio_cost[j])
        if ok and resp.result.risk is not None:
            ok = all(resp.result.risk[k][j] == src.risk[k][j]
                     for k in resp.result.risk)
        bad += not ok
    return bad


# The crash scenario runs as its own sub-run (the main schedule's
# "every enabled kind fired" assertion would otherwise have to wait for
# a crash that, by design, ends the run).  seed=1 p=0.3 first fires at
# fault check 6, so a few generations — and their checkpoints — land
# before the process "dies".
CRASH_FAULTS = "seed=1;crash:p=0.3,n=1"


def _crash_recovery(fast: bool) -> dict:
    """Injected crash mid-search -> restart -> journal replay: measures
    recovery latency and proves the resumed search bit-exact against the
    uninterrupted ``portfolio_search`` oracle with zero lost requests."""
    gens = 8 if fast else 12
    sr = SearchRequest(seed=3, population=16, generations=gens, elite=4)
    rng = np.random.default_rng(7)
    size = SPACE.size()
    prices = [PriceRequest(indices=rng.integers(0, size, 16).tolist())
              for _ in range(3)]
    with tempfile.TemporaryDirectory(prefix="repro_chaos_crash_") as d:
        dcfg = DurabilityConfig(directory=pathlib.Path(d),
                                checkpoint_every=1)
        cfg = ServiceConfig(chunk=32, split=8,
                            warm_search=(SearchWarmup(population=16,
                                                      elite=4),),
                            durability=dcfg)

        async def _main():
            svc = PricingService(SPACE, cfg)
            await svc.start()
            svc.faults = FaultInjector(CRASH_FAULTS)
            first = await asyncio.gather(svc.submit(sr),
                                         *(svc.submit(p) for p in prices))
            crashes = svc.snapshot()["durability"]["crashes"]
            await svc.stop()
            svc.faults = FaultInjector("")
            t0 = time.perf_counter()
            await svc.start()
            replayed = await svc.drain_replayed()
            recovery_s = time.perf_counter() - t0
            await svc.stop()
            return svc, list(first), replayed, recovery_s, crashes

        svc, first, replayed, recovery_s, crashes = asyncio.run(_main())
        untyped = sum(1 for r in first + replayed
                      if not r.ok and r.error.code not in TYPED_CODES)
        search_resp = next((r for r in replayed + first
                            if r.kind == "search" and r.ok), None)
        oracle = portfolio_search(SPACE, jax.random.PRNGKey(3),
                                  population=16, generations=gens, elite=4)
        bitexact = int(
            search_resp is not None
            and search_resp.result.history == oracle.history
            and [c.label for c in search_resp.result.ranked]
            == [c.label for c in oracle.ranked])
        j = RequestJournal(dcfg.journal_dir)
        lost = len(j.replay())
        j.close()
        snap = svc.snapshot()["durability"]
    out = {
        "crash_recovered": int(crashes >= 1),
        "crash_replayed": snap["journal_replayed"],
        "crash_replayed_lost": lost,
        "crash_resume_bitexact": bitexact,
        "crash_checkpoints_restored": snap["checkpoints_restored"],
        "crash_untyped_errors": untyped,
        "crash_recovery_s": recovery_s,
    }
    emit("chaos: crash -> journal replay recovery", [{
        "crashes": crashes, "replayed": out["crash_replayed"],
        "lost": lost, "bitexact": bitexact,
        "ckpt_restored": out["crash_checkpoints_restored"],
        "recovery_s": recovery_s}])
    assert out["crash_recovered"] == 1, "crash fault never fired"
    assert untyped == 0, "crash recovery produced untyped errors"
    assert lost == 0, f"{lost} journaled requests were silently lost"
    assert bitexact == 1, \
        "resumed search is not bit-exact vs the uninterrupted oracle"
    return out


def run(fast: bool = False, clients: int = 6) -> dict:
    spec = os.environ.get("REPRO_FAULTS") or DEFAULT_FAULTS
    faults = FaultInjector(spec)
    assert faults, "chaos bench needs a non-empty fault schedule"
    size = SPACE.size()
    chunk = 32
    cfg = ServiceConfig(
        chunk=chunk, split=8,
        warm_mc=((MC["draws"], MC["quantiles"]),),
        warm_search=(SearchWarmup(population=16, elite=4),),
        max_pending=200_000,
        breaker_cooldown_s=0.2,
        watchdog_timeout_s=0.4)

    # Parity oracles: the fused evaluator for fused-path rows, the
    # legacy host-packing evaluator (f32 casts) for degraded rows.
    fused_ev = ChunkedEvaluator(SPACE, candidates_per_chunk=chunk)
    legacy_ev = ChunkedEvaluator(SPACE, candidates_per_chunk=chunk,
                                 fused=False)

    # Watchdog dumps need a flight dir; use the ambient one (CI sets it)
    # or a scratch dir, restoring the env either way.
    prior_dir = os.environ.get("REPRO_FLIGHT_DIR")
    dump_dir = prior_dir or tempfile.mkdtemp(prefix="repro_chaos_flight_")
    os.environ["REPRO_FLIGHT_DIR"] = dump_dir

    async def _main():
        svc = PricingService(SPACE, cfg)
        svc.faults = faults
        await svc.start()

        async def client(i: int):
            crng = np.random.default_rng(1000 + i)
            out = []
            for req, parity in _requests(crng, size, fast):
                out.append((req, parity, await svc.submit(req)))
            return out

        t0 = time.perf_counter()
        per_client = await asyncio.gather(*(client(i)
                                            for i in range(clients)))
        wall = time.perf_counter() - t0
        await svc.stop()
        return per_client, wall, svc

    try:
        per_client, wall, svc = asyncio.run(_main())
    finally:
        if prior_dir is None:
            os.environ.pop("REPRO_FLIGHT_DIR", None)

    flat = [t for rs in per_client for t in rs]
    untyped, contaminated, by_code = 0, 0, {}
    n_ok = n_degraded = 0
    for req, parity, resp in flat:
        if not resp.ok:
            code = resp.error.code
            by_code[code] = by_code.get(code, 0) + 1
            untyped += code not in TYPED_CODES
            continue
        n_ok += 1
        n_degraded += bool(resp.degraded)
        if parity is not None:
            contaminated += _parity_mismatches(
                resp, req.indices, parity, fused_ev, legacy_ev)

    snap = svc.snapshot()
    res = snap["resilience"]
    fired = res["faults"]["fired"]
    kinds_fired = sorted(k for k, n in fired.items() if n)
    stalls = fired.get("stall", 0)
    # "one recording per induced stall": every stall must trip the
    # watchdog, and every trip must dump exactly once.  Trips may exceed
    # stalls — a forced-recompile fault makes the next tick compile
    # in-line, which legitimately stalls past the timeout too.
    deficit = max(0, stalls - res["watchdog_trips"]) + \
        abs(res["watchdog_dumps"] - res["watchdog_trips"])
    fb_rows, fb_busy = res["fallback_rows"], res["fallback_busy_s"]
    summary = {
        "clients": clients,
        "fault_spec": spec,
        "n_requests": len(flat),
        "n_ok": n_ok,
        "n_degraded_responses": n_degraded,
        "errors_by_code": by_code,
        "untyped_errors": untyped,
        "contaminated_rows": contaminated,
        "loop_errors": res["loop_errors"],
        "faults_injected": res["faults_injected"],
        "fault_kinds_injected": len(kinds_fired),
        "fault_kinds": kinds_fired,
        "stalls_fired": stalls,
        "watchdog_trips": res["watchdog_trips"],
        "watchdog_dumps": res["watchdog_dumps"],
        "stall_dump_deficit": deficit,
        "retries": res["retries"],
        "fallback_ticks": res["fallback_ticks"],
        "fallback_rows": fb_rows,
        "degraded_rows_per_sec": fb_rows / fb_busy if fb_busy else 0.0,
        "breaker_opens": res["breaker"]["opens"],
        "recovery_open_s_total": res["breaker"]["open_s_total"],
        "recovery_last_open_s": res["breaker"]["last_open_s"],
        "deadline_rejected": res["deadline_rejected"],
        "numerical_errors": res["numerical_errors"],
        "wall_s": wall,
        "fast": fast,
        "survived": 1.0,
    }
    emit("chaos: seeded fault schedule", [{
        "requests": summary["n_requests"], "ok": n_ok,
        "degraded": n_degraded, "untyped": untyped,
        "contaminated": contaminated,
        "kinds": "+".join(kinds_fired),
        "fallback_rows_per_sec": summary["degraded_rows_per_sec"],
        "recovery_s": summary["recovery_open_s_total"],
        "loop_errors": summary["loop_errors"]}])
    # crash/restore sub-run: its keys ride the same BENCH_chaos.json so
    # the regression guard pins the recovery invariants too.
    summary.update(_crash_recovery(fast))
    write_bench_json("chaos", summary)

    # -- acceptance --------------------------------------------------------
    assert untyped == 0, \
        f"{untyped} responses carried untyped errors: {by_code}"
    assert contaminated == 0, \
        f"{contaminated} ok rows match neither provenance oracle"
    assert res["loop_errors"] == 0, \
        f"{res['loop_errors']} exceptions escaped a tick into the loop guard"
    assert deficit == 0, \
        (f"stalls={stalls} but trips={res['watchdog_trips']} "
         f"dumps={res['watchdog_dumps']}")
    assert len(kinds_fired) == len(faults.rules), \
        (f"schedule enables {sorted(faults.rules)} but only "
         f"{kinds_fired} fired — retune DEFAULT_FAULTS")
    assert by_code.get(INVALID_REQUEST, 0) >= clients, \
        "the NaN-area request must be rejected as invalid_request"
    print(f"# chaos: survived {len(flat)} requests under "
          f"{'+'.join(kinds_fired)}; {n_degraded} degraded responses, "
          f"0 untyped errors, 0 contaminated rows, "
          f"recovery {summary['recovery_open_s_total']*1e3:.0f} ms total")
    return summary


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: smaller sweeps and searches")
    ap.add_argument("--clients", type=int, default=6)
    args = ap.parse_args()
    use_compile_cache()
    run(fast=args.fast, clients=args.clients)


if __name__ == "__main__":
    main()
