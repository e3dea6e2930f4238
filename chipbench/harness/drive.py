"""The measured window: one ``PricingService``, one asyncio loop.

Open-loop requests are submitted at their due times whether or not
earlier ones have returned; each one's latency runs from when it was
*due* to its response, so a stall counts against every request queued
behind it.  Closed-loop clients resubmit as soon as their previous
request returns, until the window closes; a request of theirs still in
flight then is cancelled.  Rows count toward throughput when the
service hands them to the request (its ``on_partial`` progress), inside
the window.

The harness's own host work is wrapped in ``jax.profiler``
``TraceAnnotation``s, so a trace can say what the host was doing in a
device gap: ``bench.submit`` (generator and admission), ``bench.snapshot``
(counter reads), ``bench.bookkeeping`` (recording a response).
"""
from __future__ import annotations

import asyncio
import dataclasses
import time
from typing import Any, Callable, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

from .traffic import Planned

WAIT_AFTER_CLOSE_S = 60.0


@dataclasses.dataclass
class Record:
    kind: str
    request: Any
    timed: bool                    # open loop: counts in the tails
    t_due: float
    t_sent: float = 0.0
    t_done: float = 0.0
    response: Any = None
    rows_in_window: int = 0
    _rows_seen: int = 0

    @property
    def ok(self) -> bool:
        return self.response is not None and self.response.ok

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_due


def counters(svc) -> Dict[str, float]:
    """The service's tick counters (cheap attribute reads)."""
    m = svc.metrics
    with TraceAnnotation("bench.snapshot"):
        return {"ticks": m.ticks, "busy_s": m.busy_s,
                "slots_used": m.slots_used, "slots_total": m.slots_total,
                "rows_priced": m.rows_priced}


class Window:
    """Drives ``svc`` with ``plan`` (open loop) and ``clients`` (closed
    loop) for ``seconds``; ``probe`` is an optional coroutine function
    ``probe(t0)`` run alongside (the traced sub-window)."""

    def __init__(self, svc, plan: List[Planned],
                 clients: List[Iterator[Planned]], seconds: float,
                 probe: Optional[Callable] = None):
        self.svc = svc
        self.plan = plan
        self.clients = clients
        self.seconds = float(seconds)
        self.probe = probe
        self.records: List[Record] = []
        self.t0 = self.t_end = 0.0
        self.unanswered = 0

    def _progress(self, rec: Record):
        def on_partial(done: int, total: int):
            now = time.perf_counter()
            if self.t0 <= now <= self.t_end:
                rec.rows_in_window += done - rec._rows_seen
            rec._rows_seen = done
        return on_partial

    def _submit(self, rec: Record) -> asyncio.Task:
        """Starts the request eagerly, so admission runs inside the
        ``bench.submit`` annotation and not at some later loop turn."""
        self.records.append(rec)
        with TraceAnnotation("bench.submit"):
            rec.t_sent = time.perf_counter()
            return asyncio.Task(self.svc.submit(
                rec.request, on_partial=self._progress(rec)),
                loop=asyncio.get_running_loop(), eager_start=True)

    async def _await(self, rec: Record, task: asyncio.Task):
        resp = await task
        with TraceAnnotation("bench.bookkeeping"):
            rec.t_done = time.perf_counter()
            rec.response = resp
            if not resp.ok:
                rec.rows_in_window = 0

    async def _open(self, pending: List[asyncio.Task]):
        for p in self.plan:
            due = self.t0 + p.due_s
            delay = due - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            rec = Record(p.kind, p.request, True, due)
            task = self._submit(rec)
            pending.append(asyncio.ensure_future(self._await(rec, task)))

    async def _closed(self, stream: Iterator[Planned]):
        while time.perf_counter() < self.t_end:
            p = next(stream)
            now = time.perf_counter()
            rec = Record(p.kind, p.request, False, now)
            await self._await(rec, self._submit(rec))

    async def run(self) -> "Window":
        self.t0 = time.perf_counter()
        self.t_end = self.t0 + self.seconds
        pending: List[asyncio.Task] = []
        closed = [asyncio.ensure_future(self._closed(s))
                  for s in self.clients]
        opener = asyncio.ensure_future(self._open(pending))
        probe = (asyncio.ensure_future(self.probe(self.t0))
                 if self.probe is not None else None)
        await asyncio.sleep(max(0.0, self.t_end - time.perf_counter()))
        for t in closed:
            t.cancel()
        await asyncio.gather(*closed, return_exceptions=True)
        await opener
        if probe is not None:
            await probe
        if pending:
            done, late = await asyncio.wait(pending,
                                            timeout=WAIT_AFTER_CLOSE_S)
            for t in late:
                t.cancel()
            self.unanswered = len(late)
            for t in done:
                t.result()
        return self

    # -- what the window measured ------------------------------------------
    def timed(self) -> List[Record]:
        return [r for r in self.records if r.timed]

    def counted(self) -> List[Record]:
        """The requests ``attempted`` counts: the timed class where the
        cell times one, else every request sent in the window."""
        timed = self.timed()
        return timed if timed else [r for r in self.records
                                    if r.t_sent <= self.t_end]

    def rows_in_window(self) -> int:
        return sum(r.rows_in_window for r in self.records)
