"""Open-loop latency runs from the time a request was due: a stall in the
service counts against every request queued behind it."""
import asyncio
import time
import types

import pytest

from harness import drive, runner
from harness.traffic import Planned


class StalledService:
    """Answers at once, except that the first request blocks the event
    loop for ``stall_s`` (a tick that hangs)."""

    def __init__(self, stall_s):
        self.stall_s = stall_s
        self.calls = 0

    async def submit(self, request, on_partial=None):
        self.calls += 1
        if self.calls == 1:
            time.sleep(self.stall_s)
        if on_partial is not None:
            on_partial(4, 4)
        return types.SimpleNamespace(ok=True)


def test_latency_counts_from_due_time():
    plan = [Planned("price", object(), t) for t in (0.0, 0.05, 0.1, 0.15,
                                                    0.35)]
    win = drive.Window(StalledService(0.25), plan, [], 0.5)
    asyncio.run(win.run())
    recs = win.timed()
    assert len(recs) == 5 and all(r.ok for r in recs)
    # sent late (after the stall), timed from when each was due
    for r, due in zip(recs[1:4], (0.05, 0.1, 0.15)):
        assert r.t_sent - r.t_due >= 0.25 - due - 0.01
        assert r.latency_s >= 0.25 - due - 0.01
        assert r.latency_s >= r.t_done - r.t_sent
    assert recs[4].latency_s < 0.05          # after the stall, on time
    lat = runner.end_to_end(win, 1.0)
    assert lat["p95_ms"] >= 200.0 - 10.0


def test_failed_request_counts_as_missing_the_tail():
    win = drive.Window(StalledService(0.0), [], [], 0.1)
    ok = drive.Record("price", None, True, 0.0, 0.0, 0.001,
                      types.SimpleNamespace(ok=True))
    bad = drive.Record("price", None, True, 0.0, 0.0, 0.001,
                       types.SimpleNamespace(ok=False))
    win.records = [ok] * 3 + [bad]
    e2e = runner.end_to_end(win, 1.0)
    assert e2e["p50_ms"] == pytest.approx(1.0)
    assert e2e["p95_ms"] == float("inf")


def test_closed_loop_rows_count_inside_the_window():
    class Service:
        async def submit(self, request, on_partial=None):
            for done in (2, 4):
                await asyncio.sleep(0.01)
                on_partial(done, 4)
            return types.SimpleNamespace(ok=True)

    def stream():
        while True:
            yield Planned("price", object())

    win = drive.Window(Service(), [], [stream()], 0.2)
    asyncio.run(win.run())
    done = sum(1 for r in win.records if r.response is not None)
    assert done >= 5
    assert done * 4 <= win.rows_in_window() <= (done + 1) * 4
