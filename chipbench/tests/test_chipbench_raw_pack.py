"""The reader of the program's raw-lane pack counters."""
import pytest

from chipbench_cases import small_points, small_scms

from harness.catalog import Catalog


def test_raw_pack_reader_is_silent_without_counts(monkeypatch):
    from repro.obs import registry

    reg = registry.Registry()
    monkeypatch.setattr(registry, "REGISTRY", reg)
    read = Catalog().metric_reader("raw_pack_ms.lat").read
    ctx = {"trace": None, "counters": {}, "cell": {}}
    assert read(ctx) is None                  # a program without them
    reg.counter("service_raw_pack_s").inc(0.5)
    reg.counter("service_raw_packs")
    assert read(ctx) is None                  # nothing counted
    reg.counter("service_raw_packs").inc(4)
    assert read(ctx) == pytest.approx(125.0)


def test_points_run_reports_the_raw_pack(checkout):
    """A traced run of the points mix, whose raw spec groups take the raw
    lane, reports the reading from the program's counters."""
    checkout.add_cell("scms_small.points", "scms_small", small_scms(),
                      small_points())
    spec = checkout.spec()
    for m in spec["per_layer"]:
        if m["name"] == "raw_pack_ms.lat":
            m["workloads"].append("scms_small.points")
    checkout.save_spec(spec)
    out = checkout.run("scms_small.points", trace=True)
    assert out["correct"], out["checks"]
    assert out["metrics"]["raw_pack_ms.lat"]["value"] > 0
    assert out["metrics"]["raw_pack_ms.lat"]["unit"] == "ms"
