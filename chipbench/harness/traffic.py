"""The one traffic generator: a workload file's mix -> requests.

A workload file names, per loop type, the request kinds with their
parameters:

* ``open``: ``rate_per_s`` (fixed, about 0.8 of ``knee_per_s``, the
  measured highest sustained rate) and a ``mix`` of ``{"share", "kind",
  "params"}``.  The window's arrivals are Poisson: ``rate * seconds``
  exponential gaps.  The gaps, the kinds and every request's size come
  from a stream fixed by the workload's name; ``--seed`` only permutes
  their order and draws what is inside each request (candidate indices,
  bases).  So every seed sends the same work in another order.
* ``closed``: ``{"clients", "kind", "params"}``; each client resubmits a
  new request of its kind as soon as the previous one returns.  Sizes
  come from the fixed stream of that client, contents from the seed.

A kind is a module ``requests/<kind>.py`` with ``shape(params, rng)``
(the seed-independent size) and ``make(space_size, params, shape,
rng)`` (the request).
"""
from __future__ import annotations

import dataclasses
import zlib
from typing import Any, Callable, Dict, Iterator, List

import numpy as np

# rng streams: the fixed one is keyed by the workload's name, the run's
# by --seed; each use has its own stream number.
OPEN, CONTENT, WARM, SAMPLE, CLOSED = 1, 2, 3, 4, 100


def fixed_rng(name: str, stream: int) -> np.random.Generator:
    return np.random.default_rng([zlib.crc32(name.encode()), stream])


def seed_rng(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), stream])


def draw_rows(spec: Any, rng: np.random.Generator) -> int:
    """A size: an int, ``{"min", "max"}`` (uniform, inclusive) or
    ``{"choices": [...]}`` (uniform over the list)."""
    if isinstance(spec, (int, float)):
        return int(spec)
    if "choices" in spec:
        return int(spec["choices"][int(rng.integers(len(spec["choices"])))])
    return int(rng.integers(int(spec["min"]), int(spec["max"]) + 1))


def indices(space_size: int, k: int, distinct: bool,
            rng: np.random.Generator) -> np.ndarray:
    if distinct:
        return np.sort(rng.choice(space_size, k, replace=False)).astype(
            np.int64)
    return rng.integers(0, space_size, k).astype(np.int64)


@dataclasses.dataclass
class Planned:
    kind: str
    request: Any
    due_s: float = 0.0          # offset from the window's start


def _counts(mix: List[Dict], n: int) -> List[int]:
    """Largest-remainder split of ``n`` requests by share."""
    shares = np.asarray([float(m["share"]) for m in mix])
    raw = shares / shares.sum() * n
    out = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - out), kind="stable")[:n - out.sum()]:
        out[i] += 1
    return out.tolist()


def open_plan(name: str, wl: Dict, seconds: float, seed: int,
              space_size: int, kinds: Callable[[str], Any]
              ) -> List[Planned]:
    """Every open-loop request of one window, with its due time."""
    o = wl.get("open")
    if not o:
        return []
    n = max(1, int(round(float(o["rate_per_s"]) * seconds)))
    fixed = fixed_rng(name, OPEN)
    gaps = fixed.exponential(1.0, n)
    gaps *= seconds / gaps.sum()
    shapes = []
    for m, c in zip(o["mix"], _counts(o["mix"], n)):
        mod = kinds(m["kind"])
        shapes += [(m, mod.shape(m.get("params", {}), fixed))
                   for _ in range(c)]
    order = seed_rng(seed, OPEN)
    gaps = gaps[order.permutation(n)]
    shapes = [shapes[i] for i in order.permutation(n)]
    due = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    content = seed_rng(seed, CONTENT)
    return [Planned(m["kind"], kinds(m["kind"]).make(
        space_size, m.get("params", {}), sh, content), float(t))
        for (m, sh), t in zip(shapes, due)]


def closed_clients(name: str, wl: Dict, seed: int, space_size: int,
                   kinds: Callable[[str], Any]) -> List[Iterator[Planned]]:
    """One endless request stream per closed-loop client."""
    out = []
    cid = 0
    for c in wl.get("closed", []):
        mod = kinds(c["kind"])
        params = c.get("params", {})
        for _ in range(int(c["clients"])):
            def stream(mod=mod, params=params, kind=c["kind"], i=cid):
                fixed = fixed_rng(name, CLOSED + i)
                content = seed_rng(seed, CLOSED + i)
                while True:
                    yield Planned(kind, mod.make(space_size, params,
                                                 mod.shape(params, fixed),
                                                 content))
            out.append(stream())
            cid += 1
    return out


def warmup_requests(wl: Dict, seed: int, space_size: int,
                    kinds: Callable[[str], Any]) -> List[Planned]:
    """One request of every (loop, kind) entry of the mix, from a stream
    of its own, for set-up: each lane and host path the window uses runs
    once before the window opens."""
    rng = seed_rng(seed, WARM)
    entries = list(wl.get("closed", []))
    entries += (wl.get("open") or {}).get("mix", [])
    out = []
    for e in entries:
        mod = kinds(e["kind"])
        params = e.get("params", {})
        out.append(Planned(e["kind"], mod.make(
            space_size, params, mod.shape(params, rng), rng)))
    return out
