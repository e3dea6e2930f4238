"""Chip smoke test: the pricing service, end to end, on one TPU.

  python chip_smoke.py [--seed N]

Builds a :class:`~repro.service.PricingService` over a 5-SKU portfolio
(90,224,199 candidates) the way ``examples/pricing_service.py`` does,
warms its lanes, and submits six requests concurrently: a 2^19-row price
sweep, a 2^17-row rank, a 16,384-candidate x 1,024-draw Monte-Carlo risk
sweep, a what-if grid, a population-1,024 search and raw ``spec()``
groups (the paper's Fig. 5 AMD-style parts).  All inputs come from
``--seed``.

The run fails (non-zero exit, no ``ok`` line) unless every response is ok
and not degraded; the service counted no fused failure, fallback tick,
loop error, breaker opening or in-tick recompile, and JAX compiled
nothing between the end of warm-up and the last answer; the served
search and MC quantiles equal direct ``portfolio_search`` /
``ChunkedEvaluator.evaluate_indices`` calls bit for bit; and sampled
candidates and the raw groups agree with the scalar reference
(``amortized_costs``, computed on the host CPU) to 1e-5 relative.

It refuses to run without a TPU.  The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
Every earlier line is a smoke observation of one run, not a benchmark
metric.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import pathlib
import sys
import time
from typing import Dict, List, Tuple

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.core import amortized_costs, spec  # noqa: E402
from repro.dse import (SKU, ChunkedEvaluator, DesignSpace,  # noqa: E402
                       candidate_systems, portfolio_search)
from repro.service import (McSpec, MCRiskRequest, PriceRequest,  # noqa: E402
                           PriceSystemsRequest, PricingService, RankRequest,
                           SearchRequest, SearchWarmup, ServiceConfig,
                           WhatIfRequest)
from repro.service.cache import use_compile_cache  # noqa: E402

SPACE = DesignSpace(
    skus=(SKU("edge", 100.0, 5e6), SKU("laptop", 300.0, 2e6),
          SKU("desktop", 600.0, 1e6), SKU("server", 900.0, 3e5),
          SKU("hpc", 1200.0, 5e4)),
    processes=("5nm", "7nm", "12nm"),
    integrations=("MCM", "InFO", "2.5D"),
    chiplet_counts=(1, 2, 3, 4, 6),
    allow_reuse=True, reuse_package_options=(False, True))


def _amd(name: str, n_ccd: int, iod: float, quantity: float) -> Dict:
    """A Fig. 5 AMD-style part: ``n_ccd`` 74 mm^2 7 nm compute dies that
    every part of the line shares, plus a 12 nm IO die, on MCM."""
    chips = [{"name": "ccd_74", "area": 74.0, "process": "7nm",
              "early": True}] * n_ccd
    chips.append({"name": f"iod_{iod:g}", "area": iod, "process": "12nm",
                  "early": True})
    return {"kind": "chips", "name": name, "chips": chips,
            "integration": "MCM", "quantity": quantity}


RAW_GROUPS: Tuple[Tuple[Dict, ...], ...] = (
    (_amd("amd8", 1, 125.0, 1e6), _amd("amd16", 2, 125.0, 5e5),
     _amd("amd32", 4, 416.0, 2e5)),
    tuple({"kind": "soc", "name": f"amd{c}_soc", "area": a, "process": "7nm",
           "quantity": q, "early": True}
          for c, a, q in ((8, 199.0, 1e6), (16, 273.0, 5e5),
                          (32, 712.0, 2e5))),
    ({"kind": "soc", "name": "mono_server", "area": 900.0, "process": "5nm",
      "quantity": 3e5},
     {"kind": "split", "name": "quad_server", "area": 900.0, "n": 4,
      "process": "5nm", "integration": "2.5D", "quantity": 3e5}),
)


REL_TOL = 1e-5      # against the scalar reference, float32 engine


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Request and serving sizes; the defaults are the chip run's."""

    chunk: int = 1024
    price_rows: int = 1 << 19
    rank_rows: int = 1 << 17
    mc_rows: int = 16_384
    draws: int = 1024
    quantiles: Tuple[float, ...] = (0.5, 0.9)
    population: int = 1024
    generations: int = 20
    elite: int = 64
    ref_candidates: int = 64


class _CompileClock:
    """Sums JAX's own compile-duration events (a persistent-cache hit is
    recorded as its retrieval time) and counts cache hits."""

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
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._duration)
        jax.monitoring.unregister_event_listener(self._event)

    def mark(self) -> Tuple[float, int, int]:
        return self.seconds, self.compiles, self.cache_hits


def _reference_units(space: DesignSpace, idx: np.ndarray,
                     flow: str) -> np.ndarray:
    """(n, S) per-SKU unit totals from the scalar reference
    (``re_cost`` + ``amortized_costs`` over ``candidate_systems``)."""
    out = np.empty((idx.size, len(space.skus)), np.float64)
    for row, i in enumerate(idx):
        systems = candidate_systems(space, space.candidate_at(int(i)))
        ref = amortized_costs(systems, flow=flow)
        out[row] = [float(ref[s.name].total) for s in systems]
    return out


def _rel_err(got, want) -> float:
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.abs(want)))


def run_phases(space: DesignSpace = SPACE, sizes: Sizes = Sizes(),
               seed: int = 0, log=print) -> List[str]:
    """Serve one concurrent request mix, then check it.  Prints smoke
    observations through ``log``; returns the names of the failed checks
    (empty when every check passed)."""
    flow = "chip-last"
    rng = np.random.default_rng(seed)
    n = space.size()
    price_idx = rng.integers(0, n, sizes.price_rows)
    rank_idx = rng.integers(0, n, sizes.rank_rows)
    mc_idx = rng.integers(0, n, sizes.mc_rows)
    base = int(rng.integers(0, n))
    mc = McSpec(draws=sizes.draws, quantiles=sizes.quantiles, seed=seed)
    cfg = ServiceConfig(
        chunk=sizes.chunk, split=max(1, sizes.chunk // 4),
        warm_mc=((sizes.draws, sizes.quantiles),),
        warm_search=(SearchWarmup(population=sizes.population,
                                  elite=sizes.elite),))
    requests = {
        "price": PriceRequest(indices=price_idx),
        "rank": RankRequest(indices=rank_idx, top_k=10),
        "mc_risk": MCRiskRequest(indices=mc_idx, mc=mc),
        "what_if": WhatIfRequest(base=base),
        "search": SearchRequest(seed=seed, population=sizes.population,
                                generations=sizes.generations,
                                elite=sizes.elite),
    }
    for g, group in enumerate(RAW_GROUPS):
        requests[f"raw{g}"] = PriceSystemsRequest(specs=group)
    log(f"[smoke] space.size()={n} chunk={sizes.chunk} seed={seed}")

    with _CompileClock() as clock:
        async def serve():
            t0 = time.perf_counter()
            svc = PricingService(space, cfg)
            await svc.start()
            warm_s = time.perf_counter() - t0
            warm = clock.mark()
            try:
                resps = await asyncio.gather(
                    *(svc.submit(r) for r in requests.values()))
            finally:
                await svc.stop()
            return svc, warm_s, warm, time.perf_counter() - t0 - warm_s, \
                dict(zip(requests, resps))

        svc, warm_s, warm, serve_s, resps = asyncio.run(serve())
        served = clock.mark()
        log(f"[smoke] setup_s={warm_s:.3f} (start-up warming) "
            f"compile_s={warm[0]:.3f} compiles={warm[1]} "
            f"cache_hits={warm[2]}")
        serving_compiles = served[1] - warm[1]
        log(f"[smoke] serve_s={serve_s:.3f} compiles_while_serving="
            f"{serving_compiles}")
        for name, r in resps.items():
            t = r.timing.done_s if r.ok else float("nan")
            log(f"[smoke] request {name}: ok={r.ok} degraded={r.degraded} "
                f"seconds={t:.3f}"
                + ("" if r.ok else f" error={r.error}"))

        failed: List[str] = []

        def check(name: str, ok: bool, detail: str = ""):
            log(f"[smoke] check {name}: {'ok' if ok else 'FAILED'}"
                + (f" ({detail})" if detail else ""))
            if not ok:
                failed.append(name)

        check("responses_ok",
              all(r.ok and not r.degraded for r in resps.values()),
              f"{sum(not r.ok for r in resps.values())} failed, "
              f"{sum(r.degraded for r in resps.values())} degraded")
        snap = svc.snapshot()
        res = snap["resilience"]
        counters = {"fused_failures": res["fused_failures"],
                    "fallback_ticks": res["fallback_ticks"],
                    "loop_errors": res["loop_errors"],
                    "breaker_opens": res["breaker"]["opens"],
                    "tick_recompiles": snap["recompiles_after_warmup"],
                    "serving_compiles": serving_compiles}
        log("[smoke] counters " + " ".join(f"{k}={v}"
                                           for k, v in counters.items())
            + f" ticks={snap['ticks']}")
        for k, v in counters.items():
            check(k, v == 0, f"{v}")
        if failed:
            return failed

        # -- the served search and MC sweep against direct calls ----------
        direct_ev = ChunkedEvaluator(space, candidates_per_chunk=sizes.chunk,
                                     flow=flow)
        t0 = time.perf_counter()
        direct = portfolio_search(
            space, jax.random.PRNGKey(seed), population=sizes.population,
            generations=sizes.generations, elite=sizes.elite,
            evaluator=direct_ev, flow=flow)
        served_sr = resps["search"].result
        check("search_bitexact",
              served_sr.history == direct.history
              and [c.label for c in served_sr.ranked]
              == [c.label for c in direct.ranked]
              and [c.portfolio_cost for c in served_sr.ranked]
              == [c.portfolio_cost for c in direct.ranked],
              f"{len(direct.ranked)} ranked, best {direct.best.label}")
        arrays = direct_ev.evaluate_indices(
            mc_idx, mc_key=jax.random.PRNGKey(mc.seed), mc_draws=mc.draws,
            mc_sigmas=mc.sigmas, mc_quantiles=mc.quantiles)
        got = resps["mc_risk"].result
        check("mc_bitexact",
              set(got.risk) == set(arrays.risk)
              and all(np.array_equal(got.risk[k], arrays.risk[k])
                      for k in arrays.risk)
              and np.array_equal(got.sku_unit_total, arrays.sku_unit_total),
              f"stats {sorted(arrays.risk)}")
        log(f"[smoke] direct_calls_s={time.perf_counter() - t0:.3f}")

        # -- prices against the scalar reference, on the host CPU ---------
        pos = rng.choice(sizes.price_rows, sizes.ref_candidates,
                         replace=False)
        with jax.default_device(jax.devices("cpu")[0]):
            want = _reference_units(space, price_idx[pos], flow)
            err = _rel_err(resps["price"].result.sku_unit_total[pos], want)
            raw_err = 0.0
            for g, group in enumerate(RAW_GROUPS):
                systems = [spec(dict(d)) for d in group]
                ref = amortized_costs(systems, flow=flow)
                rows = resps[f"raw{g}"].result.rows
                raw_err = max(raw_err, _rel_err(
                    [r["total"] for r in rows],
                    [float(ref[s.name].total) for s in systems]))
        log(f"[smoke] reference_max_rel_err={err:.3e} "
            f"(candidates={sizes.ref_candidates}) raw_max_rel_err="
            f"{raw_err:.3e}")
        check("reference", max(err, raw_err) <= REL_TOL,
              f"max {max(err, raw_err):.3e} <= {REL_TOL:g}")
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the request indices, keys and sample")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {dev.platform} "
              f"({dev.device_kind}); not run", file=sys.stderr)
        return 1
    use_compile_cache()
    failed = run_phases(seed=args.seed)
    if failed:
        print(f"chip_smoke: failed checks: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
