"""``correct`` holds on sound runs and fails on broken ones.

Each case drives a whole run of a cell through the harness on the CPU, at
a size a test run can hold, with the look for a chip skipped.  The timed
path is broken underneath the service for each fault a pricing cell can
have: an answer altered where it is produced, and half of the chunk left
out (its slots answered with the other half's rows).  The control puts
the plain reference, computed in bfloat16, in the program's place.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_cases import small_points, small_risk, small_scms

from harness import check, runner
from harness.catalog import Catalog
from harness.reference import Reference
from repro.service import server

CELLS = {"scms_small.points": small_points, "scms_small.risk": small_risk}


def _alter(x):
    """Every answer of the tick off by 1%: sampled rows catch it.  (A
    single altered row is caught only where the sample holds it; the
    points cells compare every row.)"""
    return x * 1.01 if x.dtype != jnp.bool_ else x


def _halve(x):
    h = x.shape[0] // 2
    return x.at[h:2 * h].set(x[:h])


def _broken(orig, fn, mc):
    """``orig`` with ``fn`` applied to every per-candidate output (the
    finite-row guard excepted)."""
    def program(*a, **k):
        out = list(orig(*a, **k))
        for i in range(len(out) - 1):
            if isinstance(out[i], dict):
                out[i] = {key: fn(v) for key, v in out[i].items()}
            elif not mc or i < 4:
                out[i] = fn(out[i])
        return tuple(out)
    return program


def _add(checkout, cell):
    checkout.add_cell(cell, "scms_small", small_scms(), CELLS[cell]())


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_sound_run_is_correct(checkout, cell):
    _add(checkout, cell)
    out = checkout.run(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out)[-1] == "checks"
    assert out["checks"]["rel_err"]["value"] < 1e-5


@pytest.mark.parametrize("fault", ["altered", "half_left_out"])
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_broken_timed_path_is_not_correct(checkout, monkeypatch, cell,
                                          fault):
    _add(checkout, cell)
    fn = _alter if fault == "altered" else _halve
    name = "_CHUNK_MC_JIT" if cell.endswith("risk") else "_CHUNK_JIT"
    monkeypatch.setattr(server, name, _broken(getattr(server, name), fn,
                                              name == "_CHUNK_MC_JIT"))
    out = checkout.run(cell)
    assert not out["correct"], out["checks"]
    assert out["failed"] == 0


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_control_in_bfloat16_is_not_correct(checkout, cell):
    """The reference in bfloat16, in the program's place, fails the same
    comparison that the program passes."""
    _add(checkout, cell)
    cat = Catalog(checkout.bench)
    c = runner.prepare(cat, cell, 5, 0.5)
    sv = _serve(c)
    ref = Reference(c.cfg["space"])
    plan = c.wl.get("check", {})
    prog, n = check.compare(sv.win.records, cat.request_kind, ref, 5, plan)
    with jax.default_device(jax.devices("cpu")[0]):
        low = Reference(c.cfg["space"], xp=jnp, dtype=jnp.bfloat16)
        ctrl, _ = check.compare(sv.win.records, cat.request_kind, ref, 5,
                                plan, control=low)
    assert n > 0
    assert check.verdict(prog, n)[0]
    assert not check.verdict(ctrl, n)[0]
    assert ctrl["rel_err"] > 1e-3


def _serve(cell):
    import asyncio
    import time

    with runner.CompileClock() as clock:
        return asyncio.run(runner.serve(cell, 0.5, time.perf_counter(),
                                        jax.devices()[:1], clock))


def test_rel_err_and_exact_numbers():
    assert check.rel_err([1.0, 2.0], [1.0, 2.0]) == 0.0
    assert check.rel_err([1.0, 2.002], [1.0, 2.0]) == pytest.approx(1e-3)
    assert check.rel_err([1.0], [1.0, 2.0]) == float("inf")
    assert check.rel_err([np.nan], [1.0]) == float("inf")
    nums = check.numbers([("exact", [1, 2], [1, 2]), ("exact", 3, 4),
                          ("rel", [1.0], [1.0]), ("mc", [1.01], [1.0])])
    assert nums["mismatches"] == 1
    assert nums["mc_rel_err"] == pytest.approx(0.01)
    ok, checks = check.verdict({"rel_err": 0.0, "mismatches": 0}, 0)
    assert not ok and checks["uncompared"]["value"] == 1
