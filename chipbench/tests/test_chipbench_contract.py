"""BENCHMARK.json keeps to the benchmark's contract, and every name in it
resolves to the files the harness finds it by."""
import json
import re

import pytest

from chipbench_cases import BENCH, ROOT

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TEXT = re.compile(r"^[^\n\t]{1,200}$")
KEYS = {"configs": {"name", "source", "file", "reduced", "why"},
        "workloads": {"name", "config", "traffic", "chips", "why"},
        "end_to_end": {"name", "unit", "better", "bound", "source"},
        "per_layer": {"name", "unit", "better", "source", "layer", "moves"}}


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["chipbench"]
    assert SPEC["command"][1].startswith("chipbench/")
    assert isinstance(SPEC["run_seconds"], int)
    assert 1 <= SPEC["run_seconds"] <= 51
    # a full check of 24 cells must fit its 43200 s budget
    assert (2 + 14 * 24) * (SPEC["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
    assert len((ROOT / "BENCHMARK.json").read_bytes()) <= 64 * 1024


@pytest.mark.parametrize("section", sorted(KEYS))
def test_entries_names_and_units(section):
    names = [e["name"] for e in SPEC[section]]
    assert len(names) == len(set(names))
    for e in SPEC[section]:
        extra = set(e) - KEYS[section]
        assert extra <= ({"workloads"} if "unit" in e else set()), extra
        assert KEYS[section] <= set(e)
        assert NAME.match(e["name"]), e["name"]
        if "unit" in e:
            assert UNIT.match(e["unit"]), e["unit"]
            assert e["better"] in ("lower", "higher")
        for k in ("why", "layer", "source"):
            if k in e:
                assert TEXT.match(str(e[k])), (e["name"], k)


def test_bounds_and_sources():
    e2e = {m["name"]: m for m in SPEC["end_to_end"]}
    assert "setup_s" in e2e
    for m in e2e.values():
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in SPEC["per_layer"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e


def _reported(cell, metric):
    return cell in metric.get("workloads", [cell])


def test_every_cell_reports_what_it_must():
    e2e = SPEC["end_to_end"]
    for w in SPEC["workloads"]:
        names = {m["name"] for m in e2e if _reported(w["name"], m)}
        assert "setup_s" in names and len(names) >= 2, w["name"]
        layer = [m for m in SPEC["per_layer"]
                 if w["name"] in m.get("workloads", [])]
        assert layer, w["name"]
        for m in layer:
            assert m["moves"] in names, (w["name"], m["name"])


def test_cells_resolve_to_files():
    from harness.catalog import Catalog

    cat = Catalog(BENCH)
    configs = {c["name"]: c for c in SPEC["configs"]}
    used = set()
    for w in SPEC["workloads"]:
        assert w["chips"] in (1, 4)
        assert w["name"] == f"{w['config']}.{w['traffic']}"
        wl = cat.workload(w["name"])
        assert wl["config"] == w["config"]
        assert (ROOT / configs[w["config"]]["file"]).is_file()
        cat.config(w["config"])
        used.add(w["config"])
        entries = list(wl.get("closed", []))
        entries += (wl.get("open") or {}).get("mix", [])
        for e in entries:
            for fn in ("shape", "make", "rows", "payload", "answer",
                       "compare"):
                assert callable(getattr(cat.request_kind(e["kind"]), fn))
    assert used == set(configs)
    for m in SPEC["per_layer"]:
        assert callable(cat.metric_reader(m["name"]).read)
    for c in SPEC["configs"]:
        assert c["file"].startswith("chipbench/configs/")
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] \
            == c["reduced"]


def test_compile_cache_at_fixed_checkout_path(monkeypatch):
    """The harness keeps JAX's persistent cache where the program puts it:
    ``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is set,
    and caches every program however fast it compiled."""
    import jax

    from harness import runner

    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        runner.use_cache()
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
        jax.config.update("jax_compilation_cache_dir", saved[keys[0]])
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        runner.use_cache()
        assert jax.config.jax_compilation_cache_dir == saved[keys[0]]
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
