"""The profiler reduction's rules on a trace whose answer is known: busy
is the union of overlapping op intervals inside the window markers,
modules are counted per run, and each idle gap is labelled by what the
host thread was doing at its midpoint."""
from types import SimpleNamespace as NS

import pytest

from harness import reduce


def _ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def _trace():
    host = [_ev(reduce.START, 1_000, 10), _ev(reduce.END, 11_000, 10),
            _ev("bench.submit", 3_000, 1_000),
            _ev("np.asarray(jax.Array)", 6_500, 2_000)]
    ops = [_ev("%fusion.1 = f32[8] fusion(x)", 500, 1_500),     # clipped
           _ev("%fusion.2 = f32[8] fusion(x)", 1_500, 1_000),   # overlaps
           _ev("%sort.3 = f32[8] sort(x)", 5_000, 1_000),
           _ev("%fusion.1 = f32[8] fusion(x)", 10_500, 2_000)]  # clipped
    modules = [_ev("jit__chunk_impl(123)", 400, 2_200),   # began before
               _ev("jit__chunk_mc_impl(456)", 4_900, 1_200),
               _ev("jit__chunk_impl(123)", 10_400, 2_100)]
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="main", events=host)]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops),
                                        NS(name="XLA Modules",
                                           events=modules)])])


def test_known_answer():
    red = reduce.reduce(_trace())
    # window [1010, 11000): busy [1010, 2500) + [5000, 6000) + [10500, 11000)
    assert red["window_s"] == pytest.approx(9_990e-9)
    assert red["busy_s"] == pytest.approx((1_490 + 1_000 + 500) * 1e-9)
    # per-run numbers count the runs that start inside the window
    assert red["modules"]["jit__chunk_impl"]["calls"] == 1
    assert red["modules"]["jit__chunk_impl"]["seconds"] == \
        pytest.approx(2_100e-9)
    assert reduce.module_ms_per_call(red, "_chunk_impl") == \
        pytest.approx(2_100e-6)
    assert reduce.module_ms_per_call(red, "_chunk_mc_impl") == \
        pytest.approx(1_200e-6)
    gaps = dict(red["idle_gaps"])
    # gap [2500, 5000): midpoint in bench.submit; gap [6000, 10500):
    # midpoint 8250 inside the device_get wait [6500, 8500)
    assert gaps == {"bench.submit": pytest.approx(2_500e-9),
                    "np.asarray(jax.Array)": pytest.approx(4_500e-9)}
    ops = dict(red["device_ops"])
    assert ops["jit__chunk_mc_impl/sort.3"] == pytest.approx(1_000e-9)
    assert ops["jit__chunk_impl/fusion.1"] == pytest.approx(990e-9 + 500e-9)
    assert ops["jit__chunk_impl/fusion.2"] == pytest.approx(1_000e-9)

