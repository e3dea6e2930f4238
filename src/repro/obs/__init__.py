"""repro.obs — stack-wide observability: span tracing, metrics, JAX
cost attribution, and the service flight recorder.

The paper's argument is quantitative cost attribution; this package
applies the same discipline to the stack's own compute cost.  Four
surfaces, used by engine, DSE, search and service alike:

* :mod:`repro.obs.trace` — nestable labeled spans (``admit`` /
  ``lower`` / ``tick`` / ``pack`` / ``device_wait`` / ``scatter`` /
  ``finish`` / ``generation`` / ``chunk``), always emitted as
  ``repro.<name>`` profiler annotations and, while tracing is on, kept
  in a ring exportable as Chrome/Perfetto ``trace_event`` JSON and
  aggregate per-phase wall tables;
* :mod:`repro.obs.registry` — named counters/gauges/histograms with
  JSON + Prometheus-style text exposition (and the ``TRACE_COUNTS``
  compatibility shim);
* :mod:`repro.obs.jaxhooks` — per-signature compile-vs-dispatch
  attribution of the module-level jit entry points, and the totals of
  the service's tick fetches;
* :mod:`repro.obs.flight` — the service's bounded black-box ring,
  dumped as a trace file on error or on demand;
* :mod:`repro.obs.ledger` — per-request serving-cost bills: each
  coalesced tick's measured wall pro-rated to the requests that rode
  it by rows contributed, rolled up into per-kind/per-lane
  cost-per-query aggregates (always on; independent of tracing);
* :mod:`repro.obs.slo` — declarative latency/availability objectives
  per request kind with sliding-window error-budget burn rates; a burn
  excursion latches a flight-recorder auto-dump.

Tracing is **off by default**; turn it on with ``REPRO_TRACE=1`` in the
environment or :func:`enable`.  Off, a span still enters its profiler
annotation, 0.53 us on a TPU v5e host's CPU (PERF.md), and the probes
forward untimed.  It never adds host syncs and never retraces a warmed signature
(pinned by the trace-count oracle in ``tests/test_obs.py``).
"""
from __future__ import annotations

from . import jaxhooks
from .flight import FlightRecorder
from .ledger import Bill, Ledger
from .registry import (Counter, Gauge, Histogram, REGISTRY, Registry,
                       TraceCounts)
from .slo import SLObjective, SLOTracker
from .trace import TRACER, Tracer, span

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "REGISTRY", "TraceCounts",
    "Tracer", "TRACER", "span", "FlightRecorder", "jaxhooks",
    "Bill", "Ledger", "SLObjective", "SLOTracker",
    "enabled", "enable", "disable", "export_chrome", "phase_table",
]


def enabled() -> bool:
    """Is the observability layer currently recording?"""
    return TRACER.enabled()


def enable(on: bool = True):
    """Turn the span ring and the jit probes on/off at runtime (the
    programmatic twin of ``REPRO_TRACE=1``)."""
    TRACER.enable(on)


def disable():
    enable(False)


def export_chrome(path):
    """Write everything the span tracer collected as a Chrome/Perfetto
    ``trace_event`` JSON file."""
    return TRACER.export_chrome(path)


def phase_table():
    """Aggregate per-phase wall table (count/total/mean/max seconds)."""
    return TRACER.phase_table()

