"""chip_smoke.py at a tiny size on the CPU: every phase and check of the
chip run, and its refusal to run without a TPU."""
import jax

import chip_smoke
from repro.dse import SKU, DesignSpace

TINY = DesignSpace(
    skus=(SKU("laptop", 300.0, 2e6), SKU("desktop", 600.0, 1e6),
          SKU("server", 900.0, 3e5)),
    processes=("5nm", "7nm"), integrations=("MCM", "2.5D"),
    chiplet_counts=(1, 2, 3, 6), allow_reuse=True,
    reuse_package_options=(False, True))


def test_phases_pass_every_check_at_tiny_size():
    lines = []
    failed = chip_smoke.run_phases(
        TINY,
        chip_smoke.Sizes(chunk=32, price_rows=256, rank_rows=128,
                         mc_rows=64, draws=32, population=16,
                         generations=3, elite=4, ref_candidates=8),
        seed=1, log=lines.append)
    assert failed == [], "\n".join(lines)
    checks = [ln for ln in lines if "check" in ln]
    for name in ("responses_ok", "fused_failures", "fallback_ticks",
                 "loop_errors", "breaker_opens", "tick_recompiles",
                 "serving_compiles",
                 "search_bitexact", "mc_bitexact", "reference"):
        assert any(f"check {name}: ok" in ln for ln in checks), name


def test_main_refuses_a_cpu_device(capsys):
    assert jax.devices()[0].platform != "tpu"
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err

