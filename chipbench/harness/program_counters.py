"""The program's own counters, read from its metrics registry
(``repro.obs.registry.REGISTRY``): totals over the run's process, which
holds one service (warm-up, window and drain).  A program without a
counter gives nothing, so a reader of a counter that a later program
adds returns nothing on an earlier one."""
from __future__ import annotations

from typing import Optional


def ms_per(total_s: str, count: str) -> Optional[float]:
    """``1e3 * total_s / count`` of two registry counters, or None where
    either is missing or the count is 0."""
    from repro.obs.registry import REGISTRY

    t, n = REGISTRY.get(total_s), REGISTRY.get(count)
    if t is None or n is None or not n.get():
        return None
    return 1e3 * t.get() / n.get()
