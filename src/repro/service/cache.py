"""Two-level caching for the pricing service.

1. **Trace cache** — the compiled-kernel layer.  Every chunk/MC/search
   signature the service is configured to serve is compiled once at
   startup (or, for a signature first seen at admission time, compiled
   *at admission*, off the tick loop), so the hot path never pays a
   recompile: :class:`TraceCache` tracks which signatures are warm and
   counts any in-tick retrace as a violation the metrics/tests surface.
2. **Result cache** — an LRU over finished answers keyed on
   ``(space fingerprint, flow, mc signature, candidate-index digest)``.
   A repeated sweep (the common interactive pattern: re-rank the same
   shortlist after looking at a report) is served from the host with
   zero device work.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import pathlib
from collections import OrderedDict
from typing import Any, Dict, Hashable, Optional, Tuple

import jax
import numpy as np

from ..core.engine import TRACE_COUNTS
from ..dse.space import DesignSpace
from ..obs.trace import TRACER

# The checkout root: src/repro/service/cache.py -> parents[3].
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_compile_cache() -> str:
    """Keep JAX's persistent compilation cache in a fixed directory, so a
    cold start (which compiles every warmed lane) reuses what an earlier
    process of this checkout compiled.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is changed here.  Otherwise the cache goes to
    ``<checkout>/.jax_cache``: a fixed path, because the path is part of
    what the cache is found by.  Call it from a program's ``main`` before
    the first compile; never on import.  Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


# TRACE_COUNTS keys that indicate device-kernel (re)compilation relevant
# to the service's lanes.
_TRACE_KEYS = ("fused_chunk", "fused_chunk_mc", "gen_step", "re", "nre",
               "total", "mc", "mc_re")


def space_fingerprint(space: DesignSpace) -> str:
    """Stable digest of a space definition — the cache namespace.

    Two structurally identical spaces (same SKUs, IO dies, menus, flags)
    fingerprint identically regardless of object identity; a SKU without
    an IO die hashes as it did before IO dies existed."""
    payload = {
        "skus": [[s.name, s.module_area_mm2, s.quantity]
                 + ([s.io_die.name, s.io_die.area_mm2, s.io_die.process]
                    if s.io_die is not None else [])
                 for s in space.skus],
        "processes": list(space.processes),
        "integrations": list(space.integrations),
        "chiplet_counts": list(space.chiplet_counts),
        "allow_reuse": space.allow_reuse,
        "reuse_package_options": list(space.reuse_package_options),
        "reuse_within_sku": space.reuse_within_sku,
    }
    blob = json.dumps(payload, sort_keys=True).encode()
    return hashlib.sha1(blob).hexdigest()


def index_digest(idx: np.ndarray) -> str:
    """Digest of a candidate index vector (order-sensitive: the response
    rows are positional)."""
    a = np.ascontiguousarray(np.asarray(idx, np.int64))
    return hashlib.sha1(a.tobytes()).hexdigest()


class LRUCache:
    """Tiny ordered-dict LRU with hit/miss counters."""

    def __init__(self, max_entries: int = 256):
        self.max_entries = int(max_entries)
        self._d: "OrderedDict[Hashable, Any]" = OrderedDict()
        self.hits = 0
        self.misses = 0

    def __len__(self) -> int:
        return len(self._d)

    def get(self, key: Hashable):
        if key in self._d:
            self._d.move_to_end(key)
            self.hits += 1
            return self._d[key]
        self.misses += 1
        return None

    def put(self, key: Hashable, value: Any):
        if self.max_entries <= 0:
            return
        self._d[key] = value
        self._d.move_to_end(key)
        while len(self._d) > self.max_entries:
            self._d.popitem(last=False)

    @property
    def hit_rate(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0

    def stats(self) -> Dict[str, float]:
        return {"entries": len(self._d), "hits": self.hits,
                "misses": self.misses, "hit_rate": self.hit_rate}


class ResultCache:
    """LRU of finished :class:`EvalArrays` keyed on
    ``(space fingerprint, flow, mc signature, index digest)``.

    Only index-addressed sweeps are cached (price / mc_risk / rank share
    entries: a rank over cached arrays re-ranks on the host).  Entries
    above ``max_rows`` are not cached — a 1M-candidate sweep should not
    evict the interactive working set."""

    def __init__(self, max_entries: int = 256, max_rows: int = 65536):
        self.lru = LRUCache(max_entries)
        self.max_rows = int(max_rows)

    @staticmethod
    def key(fingerprint: str, flow: str, mc_sig: Optional[Tuple],
            idx: np.ndarray) -> Tuple:
        return (fingerprint, flow, mc_sig, index_digest(idx))

    def get(self, key: Tuple):
        return self.lru.get(key)

    def put(self, key: Tuple, arrays) -> bool:
        if len(arrays) > self.max_rows:
            return False
        self.lru.put(key, arrays)
        return True

    def stats(self) -> Dict[str, float]:
        return self.lru.stats()


@dataclasses.dataclass(frozen=True)
class LaneSignature:
    """The static jit-cache key of one service lane: what must be warm
    before requests of this shape hit the tick loop."""

    kind: str                                 # chunk | mc | gen | raw
    flow: str
    static: Tuple = ()                        # e.g. (draws, quantiles)


class TraceCache:
    """Tracks warmed kernel signatures + counts post-warmup retraces.

    The actual compiled executables live in jax's jit cache (module-level
    jits in ``repro.dse.evaluate`` / ``search`` / ``repro.core.engine``,
    shared with the direct APIs — that sharing is what makes service
    responses bit-exact against them).  This class records *which*
    signatures have been compiled and meters TRACE_COUNTS so the metrics
    can prove the hot path stayed recompile-free."""

    def __init__(self):
        self.warmed: Dict[LaneSignature, bool] = {}
        self._tick_recompiles = 0

    def is_warm(self, sig: LaneSignature) -> bool:
        return self.warmed.get(sig, False)

    def ensure(self, sig: LaneSignature, compile_fn,
               trace_id: str = "") -> bool:
        """Compile ``sig`` now (admission time) if cold.  Returns True if
        a compile actually happened.  ``trace_id`` labels the compile
        span with the request that forced the cold compile, so "why was
        this admission slow" is answerable from its trace tree."""
        if self.is_warm(sig):
            return False
        with TRACER.span("admission_compile", kind=sig.kind,
                         flow=sig.flow, trace_id=trace_id):
            compile_fn()
        self.warmed[sig] = True
        return True

    # -- tick-time recompile metering ---------------------------------------
    @staticmethod
    def counts() -> Dict[str, int]:
        return {k: TRACE_COUNTS.get(k, 0) for k in _TRACE_KEYS}

    def meter_tick(self, before: Dict[str, int]) -> int:
        """Record (and return) the number of traces taken during a tick —
        anything nonzero means a cold request leaked onto the hot path."""
        after = self.counts()
        delta = sum(after[k] - before.get(k, 0) for k in _TRACE_KEYS)
        self._tick_recompiles += delta
        return delta

    @property
    def tick_recompiles(self) -> int:
        return self._tick_recompiles

    def stats(self) -> Dict[str, Any]:
        return {"warmed_signatures": len(self.warmed),
                "tick_recompiles": self._tick_recompiles}
