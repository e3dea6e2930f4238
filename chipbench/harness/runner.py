"""One benchmark run: set-up, the measured window, the check, the result.

``run()`` builds the cell's ``PricingService`` from its configuration
file, warms every lane and host path the cell's traffic uses (set-up),
drives the window (``drive.Window``), optionally traces a steady
sub-window with the JAX profiler, then compares a sample of the answers
with the plain reference and assembles the result line.
"""
from __future__ import annotations

import asyncio
import dataclasses
import json
import pathlib
import shutil
import sys
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import check, drive, reduce, traffic
from .catalog import Catalog
from .reference import Reference

# Published peaks per chip, keyed by JAX's ``device_kind``.  A device
# that is not here is refused: no defaults.  Source: Google Cloud
# documentation, "TPU v5e" (system architecture, chip specifications).
DEVICES = {
    "TPU v5 lite": {"chip": "TPU v5e", "bf16_flops": 197e12,
                    "int8_ops": 393e12, "hbm_bytes": 16e9,
                    "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud TPU v5e documentation"},
}

TRACE_DIR = ".chipbench_trace"


class NoChip(RuntimeError):
    """No TPU, an unknown device, or fewer chips than the cell asks for."""


def devices(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise NoChip(f"needs a TPU, found {d.platform} ({d.device_kind})")
    if d.device_kind not in DEVICES:
        raise NoChip(f"device {d.device_kind!r} is not in the device table")
    if len(devs) < chips:
        raise NoChip(f"cell needs {chips} chips, found {len(devs)}")
    return devs[:chips]


class CompileClock:
    """Counts JAX's own compile events (a persistent-cache hit counts as
    its retrieval) and sums their seconds."""

    def __init__(self):
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0

    def _duration(self, event: str, duration: float, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def __enter__(self):
        import jax

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)


def use_cache():
    """JAX's persistent compile cache where the program keeps it
    (``service.cache.use_compile_cache``), every program cached however
    fast it compiled, so only a cell's first run in a checkout compiles."""
    import jax
    from repro.service.cache import use_compile_cache

    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def design_space(cfg: Dict):
    from repro.dse import SKU, DesignSpace

    s = cfg["space"]
    return DesignSpace(
        skus=tuple(SKU(k["name"], float(k["area"]), float(k["quantity"]))
                   for k in s["skus"]),
        processes=tuple(s["processes"]),
        integrations=tuple(s["integrations"]),
        chiplet_counts=tuple(s["chiplet_counts"]),
        allow_reuse=bool(s.get("allow_reuse", True)),
        reuse_package_options=tuple(s.get("reuse_package_options",
                                          [False])),
        reuse_within_sku=bool(s.get("reuse_within_sku", True)))


def service_config(cfg: Dict, requests: List):
    """The deployment's settings, with only the lanes this traffic uses
    warmed: the MC signatures its requests carry, the raw lane only where
    it sends raw groups."""
    from repro.service import ServiceConfig

    svc = dict(cfg["service"])
    mc = sorted({(int(r.mc.draws), tuple(float(q) for q in r.mc.quantiles))
                 for r in requests if getattr(r, "mc", None) is not None})
    svc["warm_mc"] = tuple(mc)
    svc["warm_search"] = ()
    if not any(r.kind == "price_systems" for r in requests):
        svc["raw_slots"] = 0
    svc["flows"] = tuple(svc.get("flows", ("chip-last",)))
    return ServiceConfig(**svc)


def percentile(xs: List[float], q: float) -> float:
    """``q``-th percentile (linear between order statistics) of all timed
    latencies; a failed or unanswered request counts as infinitely late."""
    a = np.sort(np.asarray(xs, np.float64))
    if not a.size:
        return float("nan")
    pos = (a.size - 1) * q / 100.0
    lo, hi = int(np.floor(pos)), int(np.ceil(pos))
    if np.isinf(a[hi]):
        return float("inf")
    return float(a[lo] + (a[hi] - a[lo]) * (pos - lo))


def end_to_end(win: drive.Window, setup_s: float) -> Dict[str, float]:
    lat = [r.latency_s * 1e3 if r.ok else float("inf") for r in win.timed()]
    return {"setup_s": setup_s,
            "candidates_per_s": win.rows_in_window() / win.seconds,
            "p50_ms": percentile(lat, 50), "p95_ms": percentile(lat, 95)}


@dataclasses.dataclass
class Traced:
    """What the traced sub-window collects."""

    reduction: Optional[Dict] = None
    counters: Optional[Dict] = None


def trace_probe(svc, seconds: float, out: pathlib.Path, traced: Traced):
    """Traces ``[0.4, 0.4 + span)`` of the window, ``span = min(2 s,
    window / 4)``: long enough for hundreds of ticks, short enough that
    the trace stays small."""
    import jax
    from jax.profiler import TraceAnnotation

    start, span = 0.4 * seconds, min(2.0, seconds / 4.0)

    async def probe(t0: float):
        await asyncio.sleep(max(0.0, t0 + start - time.perf_counter()))
        shutil.rmtree(out, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(out), profiler_options=opts)
        with TraceAnnotation(reduce.START):
            c0 = drive.counters(svc)
        await asyncio.sleep(span)
        c1 = drive.counters(svc)
        with TraceAnnotation(reduce.END):
            pass
        jax.profiler.stop_trace()
        traced.counters = {k: c1[k] - c0[k] for k in c0}
    return probe


@dataclasses.dataclass
class Cell:
    """One cell's inputs for one seed: everything made before set-up."""

    wl: Dict
    cfg: Dict
    space: object
    plan: List
    clients: List
    warm: List
    service: object


def prepare(cat: Catalog, workload: str, seed: int, seconds: float) -> Cell:
    cell = cat.cell(workload)
    wl = cat.workload(workload)
    cfg = cat.config(cell["config"])
    space = design_space(cfg)
    n = space.size()
    plan = traffic.open_plan(workload, wl, seconds, seed, n,
                             cat.request_kind)
    clients = traffic.closed_clients(workload, wl, seed, n,
                                     cat.request_kind)
    warm = traffic.warmup_requests(wl, seed, n, cat.request_kind)
    return Cell(wl, cfg, space, plan, clients, warm,
                service_config(cfg, [p.request for p in plan + warm]))


@dataclasses.dataclass
class Served:
    win: drive.Window
    setup_s: float
    stages: Dict[str, float]      # set-up split: seconds since t_start
    compiles_in_window: int
    snapshot: Dict
    memory_peak_bytes: int


async def serve(cell: Cell, seconds: float, t_start: float, devs,
                clock: CompileClock, probe=None) -> Served:
    """Set-up (service, warm-up) and the window, on the running loop."""
    from repro.service import PricingService

    stages = {"prepared": time.perf_counter() - t_start}
    svc = PricingService(cell.space, cell.service)
    await svc.start()
    stages["service_started"] = time.perf_counter() - t_start
    resps = await asyncio.gather(*(svc.submit(p.request) for p in cell.warm))
    bad = [r.error for r in resps if not r.ok]
    if bad:
        raise RuntimeError(f"warm-up request failed: {bad[0]}")
    win = drive.Window(svc, cell.plan, cell.clients, seconds,
                       probe(svc) if probe is not None else None)
    setup_s = time.perf_counter() - t_start
    compiles0 = clock.compiles
    await win.run()
    in_window = clock.compiles - compiles0
    snap = svc.snapshot()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    await svc.stop()
    return Served(win, setup_s, stages, in_window, snap, int(peak))


def run(workload: str, seed: int, seconds: float, trace: bool,
        t_start: float, catalog: Optional[Catalog] = None,
        on_chip: bool = True, log: Callable[[str], None] = print) -> Dict:
    """One run of cell ``workload``; returns the result line's dict.
    ``on_chip=False`` skips the device table and the compile cache (the
    CPU tests)."""
    import jax

    cat = catalog or Catalog()
    chips = int(cat.cell(workload)["chips"])
    devs = devices(chips) if on_chip else jax.devices()[:1]
    if on_chip:
        use_cache()
    cell = prepare(cat, workload, seed, seconds)
    log(f"[chipbench] workload={workload} seed={seed} seconds={seconds} "
        f"trace={int(trace)} space={cell.space.size()} "
        f"open={len(cell.plan)} closed={len(cell.clients)} "
        f"device={devs[0].device_kind}")
    traced = Traced()
    trace_dir = cat.root / TRACE_DIR / workload
    probe = ((lambda svc: trace_probe(svc, seconds, trace_dir, traced))
             if trace else None)
    with CompileClock() as clock:
        sv = asyncio.run(serve(cell, seconds, t_start, devs, clock, probe))
    win = sv.win
    res = sv.snapshot["resilience"]
    log("[chipbench] setup_s={:.4f} ({}) compile_s={:.4f} compiles={} "
        "cache_hits={} compiles_in_window={}".format(
            sv.setup_s, " ".join(f"{k}={v:.4f}" for k, v in
                                 sv.stages.items()),
            clock.seconds, clock.compiles, clock.cache_hits,
            sv.compiles_in_window))
    late = [(r.t_sent - r.t_due) * 1e3 for r in win.timed()]
    if late:
        log(f"[chipbench] generator_late_ms p50={np.percentile(late, 50):.4f}"
            f" p99={np.percentile(late, 99):.4f} max={max(late):.4f} "
            f"unanswered={win.unanswered}")
    log(f"[chipbench] requests={len(win.records)} "
        f"ticks={sv.snapshot['ticks']} rows_in_window="
        f"{win.rows_in_window()} fallback_ticks={res['fallback_ticks']} "
        f"fused_failures={res['fused_failures']} "
        f"loop_errors={res['loop_errors']}")
    if trace:
        traced.reduction = reduce.reduce(reduce.load(trace_dir))

    # -- correctness, after the window and the service are done ------------
    t_check = time.perf_counter()
    nums, compared = check.compare(win.records, cat.request_kind,
                                   Reference(cell.cfg["space"]), seed,
                                   cell.wl.get("check", {}))
    nums["missing"] = win.unanswered
    correct, checks = check.verdict(nums, compared)
    log(f"[chipbench] compared={compared} responses, check_s="
        f"{time.perf_counter() - t_check:.4f}")

    counted = win.counted()
    failed = sum(1 for r in counted
                 if (r.response is None and r.timed)
                 or (r.response is not None and not r.response.ok))
    dev = devs[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": sv.memory_peak_bytes}
    out: Dict = {"correct": bool(correct), "attempted": len(counted),
                 "failed": failed}
    if trace:
        red = traced.reduction
        ctx = {"trace": red, "counters": traced.counters or {},
               "cell": cell.wl}
        metrics = {}
        for m in cat.metrics_of(workload, "per_layer"):
            v = cat.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
        if red:
            device.update(busy_s=red["busy_s"], window_s=red["window_s"])
            out["breakdown"] = {"device_ops": red["device_ops"],
                                "idle_gaps": red["idle_gaps"]}
    else:
        e2e = end_to_end(win, sv.setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cat.metrics_of(workload, "end_to_end")}
    out.update(metrics=metrics, device=device, checks=checks)
    return out


def main(argv=None, t_start: Optional[float] = None) -> int:
    """The command line of ``chipbench/run.py``."""
    import argparse

    t_start = time.perf_counter() if t_start is None else t_start
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        out = run(args.workload, args.seed, args.seconds, bool(args.trace),
                  t_start)
    except NoChip as e:
        print(f"chipbench: {e}; not run", file=sys.stderr)
        return 2
    sys.stdout.flush()
    for k, v in out["checks"].items():
        print(f"check {k} {v['value']!r} <= {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0
