"""A compute/IO cell (``spaces/compute_io.py``, the Fig. 5 AMD line) runs
through the harness on the CPU at a size a test run can hold: a sound run
is correct and reports the chip-slot fill; a program that amortizes each
IO die over every SKU is not; nor is the reference in bfloat16 in the
program's place."""
import json

import jax
import jax.numpy as jnp
import pytest

from chipbench_cases import BENCH

from harness import check, runner
from harness.catalog import Catalog
from harness.reference import Reference
from repro.dse.space import CandidateEncoder

CELL = "amd_small.sweep"


def _add(checkout):
    """The ``fig5_amd`` configuration at a 64-slot chunk, swept by two
    closed-loop clients of 512 uniform rows; the chip-slot fill listed
    for the cell."""
    cfg = json.loads((BENCH / "configs" / "fig5_amd.json").read_text())
    cfg["service"].update(chunk=64, split=16)
    wl = {"config": "amd_small", "why": "test",
          "closed": [{"clients": 2, "kind": "price",
                      "params": {"rows": 512}}],
          "check": {"responses": 4, "rows": 16}}
    checkout.add_cell(CELL, "amd_small", cfg, wl, e2e=("candidates_per_s",))
    spec = checkout.spec()
    for m in spec["per_layer"]:
        if m["name"] == "chip_slot_fill.tput":
            m["workloads"].append(CELL)
    checkout.save_spec(spec)


def test_sound_compute_io_run_is_correct(checkout):
    _add(checkout)
    out = checkout.run(CELL, trace=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert out["checks"]["rel_err"]["value"] < 1e-5
    fill = out["metrics"]["chip_slot_fill.tput"]
    assert fill["unit"] == "%" and 0.0 < fill["value"] <= 100.0


def test_io_die_amortized_over_every_sku_is_not_correct(checkout,
                                                        monkeypatch):
    """Every IO die made one design (the cIOD and the sIOD alike): each
    is then amortized over every SKU of a candidate, not over the SKUs
    that hold it."""
    build = CandidateEncoder._build

    def one_io_design(self, space):
        build(self, space)
        d = self.tables["io_design"]
        self.tables["io_design"] = jnp.where(d >= 0.0, 0.0, d)

    monkeypatch.setattr(CandidateEncoder, "_build", one_io_design)
    _add(checkout)
    out = checkout.run(CELL)
    assert not out["correct"], out["checks"]
    assert out["checks"]["rel_err"]["value"] > 1e-3
    assert out["failed"] == 0


def test_control_in_bfloat16_is_not_correct(checkout):
    _add(checkout)
    cat = Catalog(checkout.bench)
    c = runner.prepare(cat, CELL, 5, 0.5)
    with runner.CompileClock() as clock:
        import asyncio
        import time

        sv = asyncio.run(runner.serve(c, 0.5, time.perf_counter(),
                                      jax.devices()[:1], clock))
    ref = Reference(c.cfg["space"], kinds=cat.space_kind)
    plan = c.wl.get("check", {})
    prog, n = check.compare(sv.win.records, cat.request_kind, ref, 5, plan)
    with jax.default_device(jax.devices("cpu")[0]):
        low = Reference(c.cfg["space"], xp=jnp, dtype=jnp.bfloat16,
                        kinds=cat.space_kind)
        ctrl, _ = check.compare(sv.win.records, cat.request_kind, ref, 5,
                                plan, control=low)
    assert n > 0
    assert check.verdict(prog, n)[0]
    assert not check.verdict(ctrl, n)[0]
    assert ctrl["rel_err"] > 1e-3


def test_chip_slot_fill_reader_is_silent_without_counts(monkeypatch):
    from repro.obs import registry

    reg = registry.Registry()
    monkeypatch.setattr(registry, "REGISTRY", reg)
    read = Catalog().metric_reader("chip_slot_fill.tput").read
    ctx = {"trace": None, "counters": {}, "cell": {}}
    assert read(ctx) is None                  # a program without them
    reg.counter("service_chunk_chips").inc(9)
    reg.counter("service_chunk_chip_slots")
    assert read(ctx) is None                  # nothing admitted
    reg.counter("service_chunk_chip_slots").inc(36)
    assert read(ctx) == pytest.approx(25.0)
