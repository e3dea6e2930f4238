"""The service's phases in a trace whose answer is known, and the readers
of the program's admission and fetch counters."""
from types import SimpleNamespace as NS

import pytest

from chipbench_cases import small_points, small_scms

from harness import phases, reduce
from harness.catalog import Catalog


def _ev(name, start, end):
    return NS(name=name, start_ns=float(start), duration_ns=float(end - start))


def _trace():
    host = [_ev(reduce.START, 1_000, 1_010), _ev(reduce.END, 11_000, 11_010),
            _ev("repro.admit", 900, 1_100),                # began before
            _ev("repro.tick", 2_000, 9_000),
            _ev("repro.pack", 2_100, 2_400),
            _ev("repro.device_wait", 2_500, 8_000),
            _ev("np.asarray(jax.Array)", 2_600, 7_900),
            _ev("repro.scatter", 8_000, 8_800),
            _ev("bench.submit", 9_000, 9_900),
            _ev("repro.admit", 9_100, 9_800),
            _ev("repro.lower", 9_200, 9_700),
            _ev("repro.tick", 11_500, 12_000)]             # after the end
    ops = [_ev("%fusion.1 = f32[8] fusion(x)", 500, 1_500),
           _ev("%fusion.2 = f32[8] fusion(x)", 1_900, 2_500),
           _ev("%fusion.3 = f32[8] fusion(x)", 8_000, 8_200),
           _ev("%fusion.4 = f32[8] fusion(x)", 10_600, 12_000)]
    return NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="main", events=host)]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=ops)])])


def test_phases_known_answer():
    got = phases.phases(_trace())
    table = {k: (v["calls"], v["seconds"]) for k, v in got["phases"].items()}
    # window [1010, 11000); seconds are clipped to it
    assert table == {
        "repro.admit": (2, pytest.approx((90 + 700) * 1e-9)),
        "repro.tick": (1, pytest.approx(7_000e-9)),
        "repro.pack": (1, pytest.approx(300e-9)),
        "repro.device_wait": (1, pytest.approx(5_500e-9)),
        "repro.scatter": (1, pytest.approx(800e-9)),
        "repro.lower": (1, pytest.approx(500e-9))}
    # gaps [1500, 1900): no span; [2500, 8000): midpoint in the fetch
    # wait; [8200, 10600): midpoint in lowering, inside bench.submit
    idle = dict(got["idle_by_phase"])
    assert idle == {"repro.device_wait": pytest.approx(5_500e-9),
                    "repro.lower": pytest.approx(2_400e-9),
                    "python (no span)": pytest.approx(400e-9)}
    assert [k for k, _ in got["idle_by_phase"]] == \
        ["repro.device_wait", "repro.lower", "python (no span)"]
    # the same idle time as the result line's breakdown, which keeps
    # its own labels (bench.*, else the innermost host event)
    red = reduce.reduce(_trace())
    assert sum(idle.values()) == pytest.approx(red["window_s"]
                                               - red["busy_s"])
    assert dict(red["idle_gaps"]) == {
        "np.asarray(jax.Array)": pytest.approx(5_500e-9),
        "bench.submit": pytest.approx(2_400e-9),
        "python (no span)": pytest.approx(400e-9)}


def test_phases_without_the_programs_spans():
    """An earlier program's trace: no phases, idle labelled as ``reduce``
    labels it."""
    data = _trace()
    line = data.planes[0].lines[0]
    line.events = [e for e in line.events if not e.name.startswith("repro.")]
    got = phases.phases(data)
    assert got["phases"] == {}
    assert dict(got["idle_by_phase"]) == dict(reduce.reduce(data)["idle_gaps"])


READERS = [("admit_ms.lat", "service_admit_s", "service_admitted"),
           ("device_wait_ms.tput", "device_get_s", "service_ticks"),
           ("device_wait_ms.lat", "device_get_s", "service_ticks")]


@pytest.mark.parametrize("metric,total,count", READERS)
def test_counter_reader_is_silent_without_counts(monkeypatch, metric, total,
                                                 count):
    from repro.obs import registry

    reg = registry.Registry()
    monkeypatch.setattr(registry, "REGISTRY", reg)
    read = Catalog().metric_reader(metric).read
    ctx = {"trace": None, "counters": {}, "cell": {}}
    assert read(ctx) is None                  # a program without them
    reg.counter(total).inc(0.5)
    reg.counter(count)
    assert read(ctx) is None                  # nothing counted
    reg.counter(count).inc(4)
    assert read(ctx) == pytest.approx(125.0)


def test_points_run_reports_where_requests_wait(checkout):
    """A traced run of the points mix reports the admission and fetch
    readings from the program's counters."""
    checkout.add_cell("scms_small.points", "scms_small", small_scms(),
                      small_points())
    spec = checkout.spec()
    names = ("admit_ms.lat", "device_wait_ms.lat")
    for m in spec["per_layer"]:
        if m["name"] in names:
            m["workloads"].append("scms_small.points")
    checkout.save_spec(spec)
    out = checkout.run("scms_small.points", trace=True)
    assert out["correct"], out["checks"]
    for name in names:
        assert out["metrics"][name]["value"] > 0, name
        assert out["metrics"][name]["unit"] == "ms"
