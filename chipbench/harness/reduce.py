"""Reduction of a ``jax.profiler`` trace to the benchmark's device numbers.

The traced sub-window is marked on the host by two ``TraceAnnotation``s,
``bench.traced_window_start`` and ``bench.traced_window_end``; the host
thread that carries them is the one that ran the service.  Within it:

* busy: the union of the intervals of the device's ``XLA Ops`` (async
  copies, on their own line, are not counted), averaged over the device
  planes; idle share is ``1 - busy_s / window_s``;
* modules: count and device seconds of each ``XLA Modules`` event (one
  per program run), keyed by the program's name without its hash
  (``jit__chunk_impl``);
* ops: device seconds per op, named ``<module>/<op>``;
* gaps: the complement of the busy union, each gap labelled by what the
  service's host thread was doing at its midpoint: the innermost
  ``bench.*`` annotation there, else the innermost host event, else
  ``python (no span)``.
"""
from __future__ import annotations

import bisect
import pathlib
import re
from typing import Dict, List, Optional, Tuple

START, END = "bench.traced_window_start", "bench.traced_window_end"
_HASH = re.compile(r"\(\d+\)$")


def load(path) -> "object":
    """The ``ProfileData`` of the newest ``.xplane.pb`` under ``path``."""
    from jax.profiler import ProfileData

    files = sorted(pathlib.Path(path).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return ProfileData.from_file(str(files[-1]))


def _events(line) -> List[Tuple[float, float, str]]:
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name) for e in line.events]


def _union(iv: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _op_name(hlo: str) -> str:
    return hlo.split(" = ", 1)[0].lstrip("%")


def _module_name(name: str) -> str:
    return _HASH.sub("", name)


def _host_line(data):
    for plane in data.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            names = {e.name for e in line.events
                     if e.name.startswith("bench.traced_window")}
            if {START, END} <= names:
                return line
    return None


def _label(host: List[Tuple[float, float, str]], starts: List[float],
           t: float) -> str:
    i = bisect.bisect_right(starts, t)
    bench = other = None
    for a, b, name in reversed(host[max(0, i - 400):i]):
        if a <= t < b:
            if name.startswith("bench.") and bench is None:
                bench = name
            elif other is None and not name.startswith("bench."):
                other = name
    return bench or other or "python (no span)"


def reduce(data, top: int = 10) -> Optional[Dict]:
    """Device numbers of the traced sub-window, or None where the trace
    has no window markers or no device plane."""
    line = _host_line(data)
    if line is None:
        return None
    host = sorted(_events(line))
    ws = max(b for a, b, n in host if n == START)
    we = min(a for a, b, n in host if n == END and a >= ws)
    window_ns = we - ws
    starts = [a for a, _, _ in host]
    devices = [p for p in data.planes if p.name.startswith("/device:TPU:")]
    busy, modules, ops, gaps = [], {}, {}, {}
    for plane in devices:
        lines = {ln.name: ln for ln in plane.lines}
        if "XLA Ops" not in lines:
            continue
        op_ev = [(max(a, ws), min(b, we), n) for a, b, n in
                 _events(lines["XLA Ops"]) if b > ws and a < we]
        mod_ev = sorted((a, b, _module_name(n)) for a, b, n in
                        (_events(lines["XLA Modules"])
                         if "XLA Modules" in lines else []) if b > ws
                        and a < we)
        mod_starts = [a for a, _, _ in mod_ev]
        for a, b, n in mod_ev:
            if a >= ws:          # runs that start inside the window
                c, s = modules.get(n, (0, 0.0))
                modules[n] = (c + 1, s + (b - a) * 1e-9)
        for a, b, n in op_ev:
            j = bisect.bisect_right(mod_starts, a) - 1
            mod = mod_ev[j][2] if j >= 0 and mod_ev[j][1] >= a else "?"
            key = f"{mod}/{_op_name(n)}"
            ops[key] = ops.get(key, 0.0) + (b - a) * 1e-9
        u = _union([(a, b) for a, b, _ in op_ev])
        busy.append(sum(b - a for a, b in u) * 1e-9)
        edges = [ws] + [x for ab in u for x in ab] + [we]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                lab = _label(host, starts, 0.5 * (a + b))
                gaps[lab] = gaps.get(lab, 0.0) + (b - a) * 1e-9
    if not busy:
        return None
    n_dev = len(busy)
    rank = lambda d: sorted(([k, v] for k, v in d.items()),
                            key=lambda kv: -kv[1])[:top]
    return {"window_s": window_ns * 1e-9,
            "busy_s": sum(busy) / n_dev,
            "modules": {k: {"calls": c, "seconds": s / n_dev}
                        for k, (c, s) in modules.items()},
            "device_ops": rank({k: v / n_dev for k, v in ops.items()}),
            "idle_gaps": rank({k: v / n_dev for k, v in gaps.items()})}


def module_ms_per_call(red: Optional[Dict], program: str) -> Optional[float]:
    """Device milliseconds per run of the program whose jitted function is
    ``program`` (module ``jit_<program>``), or None where it did not run."""
    if not red:
        return None
    m = red["modules"].get(f"jit_{program}")
    if not m or not m["calls"]:
        return None
    return m["seconds"] / m["calls"] * 1e3
