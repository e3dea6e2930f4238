"""Fused, fixed-shape batched candidate pricing (repro.dse).

The hot path is **index-native and on-device**: a chunk of candidate
*indices* is decoded by :func:`~repro.dse.space.encode_arrays` into a
padded, NRE-grouped :class:`~repro.core.batch.SystemBatch` *inside* the
jit graph, priced by the un-jitted
:class:`~repro.core.engine.CostEngine` implementation, reduced to
per-candidate portfolio costs (and, optionally, Monte-Carlo risk
quantiles) in the same graph, and shipped to the host with exactly one
``jax.device_get`` per chunk.  Pricing 10k+ candidates is one retained
jit trace per (chunk-shape, flow, mc-config) and zero per-candidate
Python — the >=30x candidate-throughput path ``benchmarks/dse_bench.py``
pins.

The original host-packing path (``candidate_systems`` +
``SystemBatch.pack`` + :func:`~repro.core.batch.pad_batch`) is
retained behind ``fused=False`` as the parity oracle; both paths produce
chunks with identical array signatures and therefore share one compiled
engine trace.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batch import SystemBatch, pad_batch
from ..core.engine import (CostEngine, TRACE_COUNTS, _re_impl, finite_rows,
                           portfolio_totals)
from ..obs import jaxhooks
from ..obs.trace import TRACER as _TRACER
from .space import (Candidate, DesignSpace, EncoderMeta, candidate_systems,
                    encode_arrays, encoded_nre)
from .uncertainty import (Uncertainty, mc_re_totals_impl, mc_totals,
                          portfolio_draws, portfolio_risk_stats)


@dataclasses.dataclass(frozen=True)
class ChunkShape:
    """Worst-case array signature of one evaluation chunk."""

    candidates: int
    n_systems: int
    max_chips: int
    chip_entities: int
    pkg_entities: int
    mod_entities: int
    mod_instances: int
    d2d_entities: int
    d2d_instances: int

    def pad_kwargs(self) -> Dict[str, int]:
        d = dataclasses.asdict(self)
        d.pop("candidates")
        return d


def chunk_shape(space: DesignSpace, candidates_per_chunk: int) -> ChunkShape:
    """Upper-bound shapes for any ``candidates_per_chunk`` candidates.

    Per candidate: S systems (one per SKU), each at most ``max_chips``
    chips (an IO die counted); each chip carries one functional module
    and at most one D2D module instance; chip/module design entities are
    bounded by the chip instances, package entities by S, D2D entities by
    the nodes of the process menu and of the IO dies.
    Entity tables get one slack row so padded instances always have a
    zero-NRE row to point at.  The vectorized encoder emits exactly this
    signature, so fused and host-packed chunks share one engine trace.
    """
    k = int(candidates_per_chunk)
    s = len(space.skus)
    c = space.max_chips()
    per_cand_chips = s * c
    return ChunkShape(
        candidates=k,
        n_systems=k * s,
        max_chips=c,
        chip_entities=k * per_cand_chips + 1,
        pkg_entities=k * s + 1,
        mod_entities=k * per_cand_chips + 1,
        mod_instances=k * per_cand_chips,
        d2d_entities=k * len(space.d2d_processes()) + 1,
        d2d_instances=k * per_cand_chips,
    )


# ---------------------------------------------------------------------------
# The fused chunk kernels: decode -> price -> portfolio-reduce (-> risk)
# ---------------------------------------------------------------------------


def _fused_totals(tables, idx, *, meta: EncoderMeta, flow: str):
    """Decode + price one chunk: RE via the engine implementation, NRE via
    the layout's closed forms (no scatters) — (re, nre, total), each (N,).

    The ONE composition of the fused objective: both the evaluator chunk
    kernels and the search generation step price through this function
    (and :func:`_fused_risk_draws` for the Monte-Carlo tail), so their
    objectives are identical by construction.
    """
    batch = encode_arrays(tables, meta, idx)
    re_tot = _re_impl(batch, flow).total
    nre_tot = encoded_nre(tables, meta, idx).total
    return batch, re_tot, nre_tot, re_tot + nre_tot


def _fused_risk_draws(batch, nre_tot, qty, mc_key, sig, flow: str,
                      n_draws: int, n_skus: int):
    """(draws, K) Monte-Carlo portfolio costs for a priced fused chunk:
    RE-only scenario draws plus the once-per-batch NRE row (no perturbed
    parameter enters the NRE model)."""
    draws = mc_re_totals_impl(batch, mc_key, sig, flow, n_draws) \
        + nre_tot[None, :]                                   # (draws, K*S)
    return portfolio_draws(draws, qty, n_skus)


def _chunk_impl(tables, idx, qty, *, meta: EncoderMeta, flow: str):
    TRACE_COUNTS["fused_chunk"] += 1
    _, re_tot, nre_tot, total = _fused_totals(tables, idx, meta=meta,
                                              flow=flow)
    k, s = idx.shape[0], meta.n_skus
    unit = total.reshape(k, s)
    pf = portfolio_totals(unit, qty)
    # trailing element: (K,) in-graph numerical guardrail — True where
    # every per-row output is finite (see engine.finite_rows)
    return (unit, re_tot.reshape(k, s), nre_tot.reshape(k, s), pf,
            finite_rows(unit, pf))


def _chunk_mc_impl(tables, idx, qty, key, sig, *, meta: EncoderMeta,
                   flow: str, n_draws: int, quantiles: Tuple[float, ...]):
    TRACE_COUNTS["fused_chunk_mc"] += 1
    batch, re_tot, nre_tot, total = _fused_totals(tables, idx, meta=meta,
                                                  flow=flow)
    k, s = idx.shape[0], meta.n_skus
    unit = total.reshape(k, s)
    pf_draws = _fused_risk_draws(batch, nre_tot, qty, key, sig, flow,
                                 n_draws, s)                 # (draws, K)
    risk = portfolio_risk_stats(pf_draws, quantiles)
    pf = portfolio_totals(unit, qty)
    return (unit, re_tot.reshape(k, s), nre_tot.reshape(k, s), pf, risk,
            finite_rows(unit, pf, *risk.values()))


# Module-level jits with tables passed as (pytree) arguments, so every
# evaluator over a same-shaped space shares one compiled trace.  The obs
# probes attribute per-signature compile vs dispatch wall when tracing
# is enabled and forward transparently when it is not.
_CHUNK_JIT = jaxhooks.instrument(
    jax.jit(_chunk_impl, static_argnames=("meta", "flow")),
    "dse.chunk", trace_key="fused_chunk", counts=TRACE_COUNTS)
_CHUNK_MC_JIT = jaxhooks.instrument(
    jax.jit(_chunk_mc_impl,
            static_argnames=("meta", "flow", "n_draws", "quantiles")),
    "dse.chunk_mc", trace_key="fused_chunk_mc", counts=TRACE_COUNTS)


@dataclasses.dataclass
class EvalArrays:
    """Struct-of-arrays result of the fused pipeline: one row per
    candidate index, everything already on the host (single transfer)."""

    idx: np.ndarray               # (K,) candidate indices
    sku_unit_total: np.ndarray    # (K, S) USD per unit, RE + amortized NRE
    sku_unit_re: np.ndarray       # (K, S)
    sku_unit_nre: np.ndarray      # (K, S)
    portfolio_cost: np.ndarray    # (K,) sum_i quantity_i * unit_total_i
    risk: Optional[Dict[str, np.ndarray]] = None   # each (K,)
    finite: Optional[np.ndarray] = None   # (K,) bool; False = NaN/Inf row

    def __len__(self) -> int:
        return self.idx.shape[0]

    def objective(self, key: str = "cost") -> np.ndarray:
        if key == "cost":
            return self.portfolio_cost
        if self.risk is None or key not in self.risk:
            raise KeyError(f"no risk stat {key!r}; evaluate with mc_key set")
        return self.risk[key]


@dataclasses.dataclass
class CandidateResult:
    """Priced candidate: per-SKU unit economics + the portfolio total."""

    candidate: Candidate
    label: str
    sku_names: Sequence[str]
    sku_unit_total: np.ndarray   # (S,) USD per unit, RE + amortized NRE
    sku_unit_re: np.ndarray      # (S,)
    sku_unit_nre: np.ndarray     # (S,)
    portfolio_cost: float        # sum_i quantity_i * unit_total_i, USD
    risk: Optional[Dict[str, float]] = None  # filled by uncertainty pass

    def objective(self, key: str = "cost") -> float:
        """Scalar ranking objective: 'cost' or a risk stat (e.g. 'q90')."""
        if key == "cost":
            return self.portfolio_cost
        if self.risk is None or key not in self.risk:
            raise KeyError(f"no risk stat {key!r} on {self.label}; "
                           "evaluate with mc_key set")
        return self.risk[key]


class ChunkedEvaluator:
    """Prices candidate streams in constant-shape chunks.

    >>> ev = ChunkedEvaluator(space, candidates_per_chunk=64)
    >>> arrays = ev.evaluate_indices(np.arange(10_000))   # fused hot path
    >>> results = ev.evaluate(space.sample(rng, 100))     # object API
    >>> ev.candidates_per_sec

    ``fused=True`` (default) runs the on-device pipeline; ``fused=False``
    keeps the host-packing reference path (same chunk signature, same
    compiled engine trace — the parity oracle).
    """

    def __init__(self, space: DesignSpace, candidates_per_chunk: int = 64,
                 engine: Optional[CostEngine] = None,
                 flow: str = "chip-last", fused: bool = True):
        self.space = space
        self.engine = engine or CostEngine()
        self.flow = flow
        self.fused = bool(fused)
        self.shape = chunk_shape(space, candidates_per_chunk)
        self.encoder = space.encoder() if self.fused else None
        self._qty32 = jnp.asarray([sk.quantity for sk in space.skus],
                                  jnp.float32)
        self.reset_stats()

    # -- throughput bookkeeping ---------------------------------------------
    def reset_stats(self):
        self.n_candidates = 0
        self.n_systems = 0
        self.n_chunks = 0
        self.elapsed_s = 0.0

    @property
    def candidates_per_sec(self) -> float:
        return self.n_candidates / max(self.elapsed_s, 1e-12)

    @property
    def systems_per_sec(self) -> float:
        return self.n_systems / max(self.elapsed_s, 1e-12)

    def stats(self) -> Dict[str, float]:
        return {"n_candidates": self.n_candidates,
                "n_systems": self.n_systems, "n_chunks": self.n_chunks,
                "elapsed_s": self.elapsed_s,
                "candidates_per_sec": self.candidates_per_sec,
                "systems_per_sec": self.systems_per_sec}

    # -- fused index-native path --------------------------------------------
    def evaluate_indices(self, idx, mc_key=None, mc_draws: int = 128,
                         mc_sigmas=None,
                         mc_quantiles: Sequence[float] = (0.5, 0.9),
                         ) -> EvalArrays:
        """Price candidate *indices* through the fused on-device pipeline.

        The stream is cut into constant-shape chunks (the final partial
        chunk is padded by repeating its first index; padded rows are
        dropped).  Every chunk is one jitted decode->price->reduce call,
        dispatched asynchronously; the whole stream then syncs with a
        single ``jax.device_get`` — no per-chunk (let alone
        per-candidate) device->host round-trips.  With ``mc_key`` set the
        same call also returns Monte-Carlo portfolio risk stats computed
        in-graph under common random numbers (the same key for every
        chunk).
        """
        if not self.fused:
            raise RuntimeError("evaluate_indices requires fused=True")
        idx = np.asarray(idx, np.int64)
        if idx.ndim != 1 or idx.size == 0:
            raise ValueError("need a 1-D, non-empty index vector")
        if idx.min() < 0 or idx.max() >= self.space.size():
            raise IndexError("candidate index out of range")
        k = self.shape.candidates
        sig = quantiles = None
        if mc_key is not None:
            sig = (mc_sigmas or Uncertainty()).as_array()
            quantiles = tuple(float(q) for q in mc_quantiles)
        t0 = time.perf_counter()
        pending, reals = [], []
        for lo in range(0, idx.size, k):
            with _TRACER.span("chunk", lo=lo):
                chunk = idx[lo:lo + k]
                n_real = chunk.size
                if n_real < k:
                    chunk = np.concatenate(
                        [chunk, np.full(k - n_real, chunk[0], chunk.dtype)])
                dev = jnp.asarray(chunk, jnp.int32)
                if mc_key is None:
                    out = _CHUNK_JIT(self.encoder.tables, dev, self._qty32,
                                     meta=self.encoder.meta, flow=self.flow)
                else:
                    out = _CHUNK_MC_JIT(self.encoder.tables, dev,
                                        self._qty32, mc_key, sig,
                                        meta=self.encoder.meta,
                                        flow=self.flow,
                                        n_draws=int(mc_draws),
                                        quantiles=quantiles)
                pending.append(out)
                reals.append(n_real)
        host = jax.device_get(pending)          # one sync for the stream
        self.elapsed_s += time.perf_counter() - t0
        outs = [jax.tree_util.tree_map(lambda a, nr=nr: a[:nr], o)
                for o, nr in zip(host, reals)]
        self.n_candidates += int(sum(reals))
        self.n_systems += int(sum(reals)) * len(self.space.skus)
        self.n_chunks += len(reals)

        def cat(i):
            return np.concatenate([o[i] for o in outs], axis=0)

        risk = None
        if mc_key is not None:
            risk = {kk: np.concatenate([o[4][kk] for o in outs], axis=0)
                    for kk in outs[0][4]}
        finite = np.concatenate([o[-1] for o in outs], axis=0)
        return EvalArrays(idx=idx, sku_unit_total=cat(0), sku_unit_re=cat(1),
                          sku_unit_nre=cat(2), portfolio_cost=cat(3),
                          risk=risk, finite=finite)

    def results_from_arrays(self, arrays: EvalArrays,
                            candidates: Optional[Sequence[Candidate]] = None,
                            ) -> List[CandidateResult]:
        """Materialize host :class:`CandidateResult` objects (labels and
        all) from fused pipeline output — the cold path, meant for
        winners/reports rather than the full stream."""
        if candidates is None:
            candidates = [self.space.candidate_at(int(i))
                          for i in arrays.idx]
        names = [sk.name for sk in self.space.skus]
        out = []
        for j, cand in enumerate(candidates):
            risk = None
            if arrays.risk is not None:
                risk = {kk: float(v[j]) for kk, v in arrays.risk.items()}
            out.append(CandidateResult(
                candidate=cand, label=cand.label(), sku_names=names,
                sku_unit_total=np.asarray(arrays.sku_unit_total[j],
                                          np.float64),
                sku_unit_re=np.asarray(arrays.sku_unit_re[j], np.float64),
                sku_unit_nre=np.asarray(arrays.sku_unit_nre[j], np.float64),
                portfolio_cost=float(arrays.portfolio_cost[j]), risk=risk))
        return out

    # -- object API ----------------------------------------------------------
    def evaluate(self, candidates: Sequence[Candidate],
                 mc_key=None, mc_draws: int = 128, mc_sigmas=None,
                 mc_quantiles: Sequence[float] = (0.5, 0.9),
                 ) -> List[CandidateResult]:
        """Price every candidate; optionally attach Monte Carlo risk stats.

        With ``mc_key`` set, each chunk is additionally priced under
        ``mc_draws`` correlated parameter scenarios (see
        :mod:`repro.dse.uncertainty`) — the *same* key (common random
        numbers) is reused for every chunk so candidates are compared
        under identical scenarios regardless of chunking.

        Candidates that are valid for ``candidate_systems`` but not
        members of this space's menus cannot be index-encoded; such a
        stream transparently falls back to the host-packing path.
        """
        candidates = list(candidates)
        if not candidates:
            return []
        if self.fused:
            try:
                idx = np.asarray([self.space.index_of(c)
                                  for c in candidates], np.int64)
            except ValueError:
                idx = None      # foreign-but-priceable candidates
            if idx is not None:
                arrays = self.evaluate_indices(
                    idx, mc_key=mc_key, mc_draws=mc_draws,
                    mc_sigmas=mc_sigmas, mc_quantiles=mc_quantiles)
                return self.results_from_arrays(arrays, candidates)
        return self._evaluate_legacy(candidates, mc_key, mc_draws,
                                     mc_sigmas, mc_quantiles)

    # -- legacy host-packing path (parity oracle) ---------------------------
    def pack_chunk(self, chunk: Sequence[Candidate]) -> SystemBatch:
        """Pack <= candidates_per_chunk candidates into one padded batch
        via the host ``System`` route (reference path): packed and padded
        on the host, then moved to the device in one transfer."""
        if len(chunk) > self.shape.candidates:
            raise ValueError(f"chunk of {len(chunk)} exceeds "
                             f"{self.shape.candidates} candidates")
        systems, groups = [], []
        for j, cand in enumerate(chunk):
            grp = candidate_systems(self.space, cand)
            systems += grp
            groups += [j] * len(grp)
        batch = SystemBatch.pack(systems, share_nre=groups,
                                 max_chips=self.shape.max_chips)
        return pad_batch(batch, **self.shape.pad_kwargs()).to_device()

    def _legacy_chunk_host(self, chunk: Sequence[Candidate], mc_key,
                           mc_draws: int, mc_sigmas) -> Tuple:
        """Price one candidate chunk through the host-packing path.

        Returns float64 host arrays ``(total, re, nre, pf_draws)`` with
        the first three ``(len(chunk) * S,)`` per-system rows and
        ``pf_draws`` a ``(draws, len(chunk))`` portfolio-cost matrix (or
        None without ``mc_key``).  This is op-for-op the math of the
        legacy parity oracle — :meth:`_evaluate_legacy` builds its
        ``CandidateResult`` objects from exactly these values — and it
        is what the service's degraded mode prices through, so fallback
        responses are bit-exact float32 casts of oracle float64s.
        Per-row values are chunk-composition-independent (cost-neutral
        padding; MC draws are systematic scalar multipliers), so how a
        tick re-chunks the rows cannot change them.
        """
        s = len(self.space.skus)
        qty = np.asarray([sk.quantity for sk in self.space.skus], np.float64)
        batch = self.pack_chunk(chunk)
        dev = [self.engine.total(batch, flow=self.flow)]
        if mc_key is not None:
            draws = mc_totals(batch, mc_key, n_draws=mc_draws,
                              flow=self.flow, sigmas=mc_sigmas)
            # fold the real (unpadded) rows into per-candidate
            # portfolio costs: (draws, len(chunk))
            dev.append(portfolio_draws(draws[:, :len(chunk) * s], qty, s))
        # every device->host transfer of the chunk in one batched get
        host = jax.device_get(tuple(dev))
        tc = host[0]
        pf_draws = np.asarray(host[1], np.float64) \
            if mc_key is not None else None
        return (np.asarray(tc.total, np.float64),
                np.asarray(tc.re.total, np.float64),
                np.asarray(tc.nre.total, np.float64), pf_draws)

    @staticmethod
    def _legacy_risk(pf_col: np.ndarray,
                     quantiles: Sequence[float]) -> Dict[str, float]:
        """Host risk stats of one candidate's draw column — shared by the
        oracle and the degraded path so the two stay bit-identical."""
        risk = {"mean": float(pf_col.mean()), "std": float(pf_col.std())}
        for q in quantiles:
            risk[f"q{int(round(q * 100))}"] = float(np.quantile(pf_col, q))
        return risk

    def _evaluate_legacy(self, candidates, mc_key, mc_draws, mc_sigmas,
                         mc_quantiles) -> List[CandidateResult]:
        s = len(self.space.skus)
        qty = np.asarray([sk.quantity for sk in self.space.skus], np.float64)
        names = [sk.name for sk in self.space.skus]
        out: List[CandidateResult] = []
        k = self.shape.candidates
        for lo in range(0, len(candidates), k):
            chunk = candidates[lo:lo + k]
            t0 = time.perf_counter()
            total, re_tot, nre_tot, pf_draws = self._legacy_chunk_host(
                chunk, mc_key, mc_draws, mc_sigmas)
            self.elapsed_s += time.perf_counter() - t0
            for j, cand in enumerate(chunk):
                rows = slice(j * s, (j + 1) * s)
                unit = total[rows]
                risk = self._legacy_risk(pf_draws[:, j], mc_quantiles) \
                    if pf_draws is not None else None
                out.append(CandidateResult(
                    candidate=cand, label=cand.label(), sku_names=names,
                    sku_unit_total=unit, sku_unit_re=re_tot[rows],
                    sku_unit_nre=nre_tot[rows],
                    portfolio_cost=float((qty * unit).sum()), risk=risk))
            self.n_candidates += len(chunk)
            self.n_systems += len(chunk) * s
            self.n_chunks += 1
        return out

    def evaluate_indices_legacy(self, idx, mc_key=None, mc_draws: int = 128,
                                mc_sigmas=None,
                                mc_quantiles: Sequence[float] = (0.5, 0.9),
                                ) -> EvalArrays:
        """Index-native pricing through the **legacy host-packing path**.

        Same signature and :class:`EvalArrays` contract as
        :meth:`evaluate_indices`, but every chunk goes host ``System``
        packing -> engine -> host, no fused decode.  This is the
        degraded-mode evaluator the pricing service falls back to when
        fused dispatch fails: slow (per-candidate Python packing) but
        correct, with results equal to float32 casts of the legacy
        oracle's float64 values by construction (shared
        :meth:`_legacy_chunk_host` / :meth:`_legacy_risk`).  Works with
        ``fused=False`` evaluators too — no encoder needed.
        """
        idx = np.asarray(idx, np.int64)
        if idx.ndim != 1 or idx.size == 0:
            raise ValueError("need a 1-D, non-empty index vector")
        if idx.min() < 0 or idx.max() >= self.space.size():
            raise IndexError("candidate index out of range")
        s = len(self.space.skus)
        qty = np.asarray([sk.quantity for sk in self.space.skus], np.float64)
        quantiles = tuple(float(q) for q in mc_quantiles)
        n, k = idx.size, self.shape.candidates
        unit = np.empty((n, s), np.float32)
        re_a = np.empty((n, s), np.float32)
        nre_a = np.empty((n, s), np.float32)
        pf = np.empty((n,), np.float32)
        risk = None
        if mc_key is not None:
            risk = {kk: np.empty((n,), np.float32)
                    for kk in ("mean", "std")
                    + tuple(f"q{int(round(q * 100))}" for q in quantiles)}
        t0 = time.perf_counter()
        for lo in range(0, n, k):
            with _TRACER.span("legacy_chunk", lo=lo):
                chunk = [self.space.candidate_at(int(i))
                         for i in idx[lo:lo + k]]
                total, re_tot, nre_tot, pf_draws = self._legacy_chunk_host(
                    chunk, mc_key, mc_draws, mc_sigmas)
                for j in range(len(chunk)):
                    rows = slice(j * s, (j + 1) * s)
                    u = total[rows]
                    unit[lo + j] = u
                    re_a[lo + j] = re_tot[rows]
                    nre_a[lo + j] = nre_tot[rows]
                    pf[lo + j] = float((qty * u).sum())
                    if pf_draws is not None:
                        for kk, v in self._legacy_risk(
                                pf_draws[:, j], quantiles).items():
                            risk[kk][lo + j] = v
        self.elapsed_s += time.perf_counter() - t0
        self.n_candidates += n
        self.n_systems += n * s
        self.n_chunks += -(-n // k)
        finite = np.isfinite(unit).all(-1) & np.isfinite(pf)
        if risk is not None:
            for v in risk.values():
                finite &= np.isfinite(v)
        return EvalArrays(idx=idx, sku_unit_total=unit, sku_unit_re=re_a,
                          sku_unit_nre=nre_a, portfolio_cost=pf, risk=risk,
                          finite=finite)


def evaluate_direct(space: DesignSpace, cand: Candidate,
                    engine: Optional[CostEngine] = None,
                    flow: str = "chip-last") -> CandidateResult:
    """Unchunked, unpadded single-candidate pricing (reference path).

    Builds the candidate's group as its own ``share_nre=True`` batch and
    prices it directly — the cross-check the padded-chunk parity tests
    compare against.
    """
    engine = engine or CostEngine()
    grp = candidate_systems(space, cand)
    tc = jax.device_get(engine.total(
        SystemBatch.from_systems(grp, share_nre=True), flow=flow))
    qty = np.asarray([sk.quantity for sk in space.skus], np.float64)
    unit = np.asarray(tc.total, np.float64)
    return CandidateResult(
        candidate=cand, label=cand.label(),
        sku_names=[sk.name for sk in space.skus], sku_unit_total=unit,
        sku_unit_re=np.asarray(tc.re.total, np.float64),
        sku_unit_nre=np.asarray(tc.nre.total, np.float64),
        portfolio_cost=float((qty * unit).sum()))
