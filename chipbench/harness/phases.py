"""The service's own phases in a ``jax.profiler`` trace.

The program enters a ``TraceAnnotation`` named ``repro.<phase>`` around
each of its phases (``repro.admit``, ``repro.lower``, ``repro.tick``,
``repro.pack``, ``repro.device_wait``, ``repro.scatter``,
``repro.finish``, ...), on the host thread that runs the service: the
line of the window markers (``harness/reduce.py``).  Within the traced
window:

* phases: count and host seconds (clipped to the window) of each
  ``repro.*`` annotation;
* idle by phase: each gap in the device's busy union labelled by the
  innermost ``repro.*`` annotation at its midpoint, else as
  ``reduce`` labels it (``bench.*``, else the innermost host event,
  else ``python (no span)``).

A program without the annotations gives no phases, and idle time
labelled as ``reduce`` labels it.
"""
from __future__ import annotations

import bisect
import json
import sys
from typing import Dict, List, Optional, Tuple

from . import reduce

PREFIX = "repro."


def _window(host) -> Tuple[float, float]:
    ws = max(b for a, b, n in host if n == reduce.START)
    we = min(a for a, b, n in host if n == reduce.END and a >= ws)
    return ws, we


def _innermost(spans: List[Tuple[float, float, str]], starts: List[float],
               t: float) -> Optional[str]:
    for a, b, name in reversed(spans[:bisect.bisect_right(starts, t)]):
        if a <= t < b:
            return name
    return None


def phases(data) -> Optional[Dict]:
    """``{"phases": {name: {"calls", "seconds"}}, "idle_by_phase":
    [[label, seconds], ...]}`` of the traced window (idle seconds
    averaged over the device planes, largest first), or None where the
    trace has no window markers or no device plane."""
    line = reduce._host_line(data)
    if line is None:
        return None
    host = sorted(reduce._events(line))
    ws, we = _window(host)
    starts = [a for a, _, _ in host]
    spans = [(a, b, n) for a, b, n in host if n.startswith(PREFIX)]
    span_starts = [a for a, _, _ in spans]
    table: Dict[str, Dict] = {}
    for a, b, n in spans:
        if b > ws and a < we:
            row = table.setdefault(n, {"calls": 0, "seconds": 0.0})
            row["calls"] += 1
            row["seconds"] += (min(b, we) - max(a, ws)) * 1e-9
    idle: Dict[str, float] = {}
    n_dev = 0
    for plane in data.planes:
        if not plane.name.startswith("/device:TPU:"):
            continue
        ops = [ln for ln in plane.lines if ln.name == "XLA Ops"]
        if not ops:
            continue
        n_dev += 1
        busy = reduce._union([(max(a, ws), min(b, we)) for a, b, _ in
                              reduce._events(ops[0]) if b > ws and a < we])
        edges = [ws] + [x for ab in busy for x in ab] + [we]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = 0.5 * (a + b)
                lab = (_innermost(spans, span_starts, mid)
                       or reduce._label(host, starts, mid))
                idle[lab] = idle.get(lab, 0.0) + (b - a) * 1e-9
    if not n_dev:
        return None
    return {"phases": table,
            "idle_by_phase": sorted(([k, v / n_dev] for k, v in idle.items()),
                                    key=lambda kv: -kv[1])}


def main(argv=None) -> int:
    """The command line of ``chipbench/phases.py``: prints the phases
    and idle by phase of the newest trace under the directory given (a
    ``--trace 1`` run leaves it under ``.chipbench_trace/<cell>``)."""
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print("usage: phases.py <trace dir>", file=sys.stderr)
        return 2
    print(json.dumps(phases(reduce.load(argv[0]))))
    return 0
