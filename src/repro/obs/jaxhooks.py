"""JAX cost attribution: per-signature compile/dispatch probes and the
totals of the service's tick fetches.

The stack's hot paths are module-level ``jax.jit`` entry points
(``repro.core.engine``, ``repro.dse.evaluate`` / ``search`` /
``uncertainty``).  :func:`instrument` wraps each of them in a
:class:`JitProbe` that attributes every call's host-side wall to either
**jit_compile** (the call traced — detected via the impl body's
``TRACE_COUNTS`` key, which only increments while jax executes the
Python body) or **kernel_dispatch** (steady state), keyed by the call's
argument *signature* (leaf shapes/dtypes + static arguments).  That
turns "zero hot-path recompiles" from an asserted invariant into a
measured, queryable one: ``stats()`` reports compiles per signature and
:func:`recompiles_since` / the tracer's ``jit_compile``-inside-``tick``
count expose any warm-path retrace.

:func:`device_get_stats` reads the fetch counters the service keeps
at its tick syncs (``service/metrics.py``): calls, bytes and the wall
spent waiting in them — the third axis (transfer) next to compile and
dispatch.  ``jax.device_get`` itself is never replaced.

Probes are **off while tracing is off**: they forward with a single
predicate check.  Probes never call ``block_until_ready`` — dispatch
time is the host-side dispatch wall; device waits show up where they
always did, in the tick's fetch.
"""
from __future__ import annotations

import dataclasses
from time import perf_counter
from typing import Any, Callable, Dict, List, Mapping, Optional, Tuple

import jax

from . import trace
from .registry import REGISTRY


@dataclasses.dataclass
class SignatureStats:
    """Wall attribution of one (probe, argument-signature) pair."""

    compiles: int = 0
    compile_s: float = 0.0
    calls: int = 0              # post-compile (steady-state) calls
    dispatch_s: float = 0.0

    def as_dict(self) -> Dict[str, float]:
        return dataclasses.asdict(self)


def _leaf_sig(leaf) -> Any:
    # Keep this fast: it runs per probe call on every pytree leaf.
    # dtype objects hash/compare fine and avoid str(dtype) (~3us each);
    # statics are hashable by jit's own contract.
    shape = getattr(leaf, "shape", None)
    if shape is not None and hasattr(leaf, "dtype"):
        return (tuple(shape), leaf.dtype)
    try:
        return (type(leaf).__name__, hash(leaf))
    except TypeError:
        return repr(leaf)[:80]


class JitProbe:
    """Transparent wrapper over a jitted callable (see module docstring).

    ``trace_key`` names the ``TRACE_COUNTS`` entry the wrapped function's
    Python body increments; a call that bumps it was a (re)trace.  With
    no ``trace_key``, a first call per signature counts as the compile.
    """

    def __init__(self, fn: Callable, name: str,
                 trace_key: Optional[str] = None,
                 counts: Optional[Mapping] = None):
        self.fn = fn
        self.name = name
        self.trace_key = trace_key
        self.counts = counts if counts is not None else {}
        self.stats: Dict[Any, SignatureStats] = {}
        m = self._mname
        self._counter_names = (f"jit_{m}_compiles", f"jit_{m}_compile_s",
                               f"jit_{m}_calls", f"jit_{m}_dispatch_s")
        _PROBES.append(self)

    def __call__(self, *args, **kwargs):
        if not trace.TRACER.enabled():
            return self.fn(*args, **kwargs)
        before = self.counts.get(self.trace_key, 0) if self.trace_key \
            else 0
        # the signature walk is inside the timed window on purpose: it is
        # tracing-induced dispatch cost and must show up as covered span
        # wall, not as an unattributed hole in the tick.
        t0 = perf_counter()
        out = self.fn(*args, **kwargs)
        sig = self._signature(args, kwargs)
        dt = perf_counter() - t0
        if self.trace_key:
            compiled = self.counts.get(self.trace_key, 0) > before
        else:
            compiled = sig not in self.stats
        st = self.stats.setdefault(sig, SignatureStats())
        n_compiles, n_compile_s, n_calls, n_dispatch_s = self._counter_names
        if compiled:
            st.compiles += 1
            st.compile_s += dt
            trace.TRACER.add_complete("jit_compile", dt, fn=self.name)
            REGISTRY.counter(n_compiles).inc()
            REGISTRY.counter(n_compile_s).inc(dt)
        else:
            st.calls += 1
            st.dispatch_s += dt
            trace.TRACER.add_complete("kernel_dispatch", dt, fn=self.name)
            REGISTRY.counter(n_calls).inc()
            REGISTRY.counter(n_dispatch_s).inc(dt)
        return out

    @property
    def _mname(self) -> str:
        return self.name.replace(".", "_").replace("-", "_")

    @staticmethod
    def _signature(args, kwargs) -> Tuple:
        leaves, _ = jax.tree_util.tree_flatten((args, kwargs))
        return tuple(_leaf_sig(l) for l in leaves)

    def summary(self) -> Dict[str, float]:
        """Aggregate over signatures: total compiles / walls / calls."""
        out = {"signatures": len(self.stats), "compiles": 0,
               "compile_s": 0.0, "calls": 0, "dispatch_s": 0.0}
        for st in self.stats.values():
            out["compiles"] += st.compiles
            out["compile_s"] += st.compile_s
            out["calls"] += st.calls
            out["dispatch_s"] += st.dispatch_s
        return out

    def reset(self):
        self.stats.clear()


_PROBES: List[JitProbe] = []


def instrument(fn: Callable, name: str, trace_key: Optional[str] = None,
               counts: Optional[Mapping] = None) -> JitProbe:
    """Wrap a jitted entry point in a :class:`JitProbe` (registered for
    :func:`stats` aggregation)."""
    return JitProbe(fn, name, trace_key=trace_key, counts=counts)


def probes() -> List[JitProbe]:
    return list(_PROBES)


def stats() -> Dict[str, Dict[str, float]]:
    """Per-probe compile/dispatch attribution (aggregated signatures)."""
    return {p.name: p.summary() for p in _PROBES}


def reset():
    """Clear all probe stats and the warm-compile marker."""
    for p in _PROBES:
        p.reset()


def total_compiles() -> int:
    return sum(p.summary()["compiles"] for p in _PROBES)


def total_dispatch_s() -> float:
    """Total probe-attributed jit wall (compile + steady-state dispatch)
    across every probe.  Deltas of this marker give the host seconds
    spent enqueueing (or compiling) programs inside a tick, which the
    serving-cost ledger pro-rates per request; the device's own time is
    not in it (the probe returns before the device finishes).  Only
    meaningful while tracing is on (probes forward untimed when off);
    callers fall back to tick wall otherwise."""
    total = 0.0
    for p in _PROBES:
        s = p.summary()
        total += s["compile_s"] + s["dispatch_s"]
    return total


def recompiles_since(marker: int) -> int:
    """Compiles measured since a ``total_compiles()`` marker — the
    queryable "recompiles after warmup" invariant."""
    return total_compiles() - marker


def device_get_stats() -> Dict[str, float]:
    """Totals of the service's tick fetches: ``jax.device_get`` calls,
    bytes brought to the host and seconds waited in them (zeros before
    the first tick)."""
    def val(name):
        m = REGISTRY.get(name)
        return m.get() if m is not None else 0.0
    return {"calls": int(val("device_get_calls")),
            "bytes": int(val("device_get_bytes")),
            "total_s": val("device_get_s")}
