"""Benchmark orchestrator: one section per paper table/figure plus the
roofline, codesign, kernel, engine and DSE benches.

  PYTHONPATH=src python -m benchmarks.run

The engine and DSE benches persist their summaries as BENCH_engine.json /
BENCH_dse.json at the repo root (perf trajectory; CI uploads them as
artifacts and guards them with scripts/check_bench_regression.py).

Every bench runs in this one process.  ``benchmarks.restart_bench`` is
not among them: it starts a child process that needs the device, which
this process already holds once the first bench has run.  Run it as its
own command (``python -m benchmarks.restart_bench``).
"""
import sys
import time


def main() -> None:
    from repro.service.cache import use_compile_cache

    from . import (ablations, chaos_bench, codesign, dse_bench,
                   engine_bench, fig2_yield_cost, fig4_re_integration,
                   fig5_amd, fig6_single_system, fig8_scms, fig9_ocme,
                   fig10_fsmc, kernels_bench, roofline, service_bench)

    use_compile_cache()

    benches = [
        ("fig2", fig2_yield_cost), ("fig4", fig4_re_integration),
        ("fig5", fig5_amd), ("fig6", fig6_single_system),
        ("fig8", fig8_scms), ("fig9", fig9_ocme), ("fig10", fig10_fsmc),
        ("ablations", ablations),
        ("roofline", roofline), ("codesign", codesign),
        ("kernels", kernels_bench), ("engine", engine_bench),
        ("dse", dse_bench), ("service", service_bench),
        # chaos goes LAST: it force-clears fused jit caches and injects
        # faults into its own service — nothing downstream to perturb.
        ("chaos", chaos_bench),
    ]
    failures = 0
    for name, mod in benches:
        t0 = time.perf_counter()
        try:
            mod.run()
            print(f"# [{name}] done in {time.perf_counter()-t0:.2f}s\n")
        except Exception as e:  # keep the suite going, report at the end
            failures += 1
            print(f"# [{name}] FAILED: {type(e).__name__}: {e}\n")
    if failures:
        print(f"# {failures} benchmark(s) failed")
        sys.exit(1)
    print("# all benchmarks ok")


if __name__ == "__main__":
    main()
