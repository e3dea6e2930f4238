"""The fused DSE pipeline: vectorized candidate encoder parity (full
enumeration, zero rel err vs the host System packing), closed-form NRE vs
the engine's segment-sum path, array-native batch construction, and the
single-trace contract of the jitted search generation step."""
import jax
import numpy as np
import pytest

from repro.core import CostEngine, SystemBatch
from repro.core.engine import TRACE_COUNTS, portfolio_totals
from repro.dse import (ChunkedEvaluator, DesignSpace, RiskConfig, SKU,
                       encode_batch, mc_totals, portfolio_search)
from repro.dse.space import encoded_nre
from repro.dse.uncertainty import mc_re_totals_impl

ENGINE = CostEngine()


def _space(**kw):
    d = dict(skus=(SKU("laptop", 200.0, 2e6), SKU("server", 400.0, 5e5)),
             processes=("7nm", "12nm"), integrations=("MCM",),
             chiplet_counts=(1, 2, 4), allow_reuse=True,
             reuse_package_options=(False, True))
    d.update(kw)
    return DesignSpace(**d)


@pytest.fixture(scope="module")
def space():
    return _space()


# ---------------------------------------------------------------------------
# Encoder: full-enumeration parity with the host packing path
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("kw", [{}, {"reuse_within_sku": False},
                                {"allow_reuse": False},
                                {"integrations": ("MCM", "2.5D")}])
def test_encode_batch_full_enumeration_bit_parity(kw):
    """Every candidate in the space, encoded from indices, prices exactly
    (zero relative error) like the candidate_systems + from_systems +
    pad_batch chunk it replaces."""
    sp = _space(**kw)
    idx = np.arange(sp.size())
    encoded = encode_batch(sp, idx)
    legacy = ChunkedEvaluator(sp, candidates_per_chunk=sp.size(),
                              fused=False).pack_chunk(
        list(sp.enumerate_candidates()))
    assert encoded.chip_area.shape == legacy.chip_area.shape
    for flow in ("chip-last", "chip-first"):
        te = jax.device_get(ENGINE.total(encoded, flow=flow))
        tl = jax.device_get(ENGINE.total(legacy, flow=flow))
        for part in ("re", "nre"):
            np.testing.assert_array_equal(
                np.asarray(getattr(te, part).total),
                np.asarray(getattr(tl, part).total))
        np.testing.assert_array_equal(np.asarray(te.total),
                                      np.asarray(tl.total))


def test_index_of_is_the_inverse_of_candidate_at(space):
    assert [space.index_of(space.candidate_at(i))
            for i in range(space.size())] == list(range(space.size()))
    three = _space(skus=(SKU("a", 100.0, 1.0), SKU("b", 200.0, 1.0),
                         SKU("c", 400.0, 1.0)))
    with pytest.raises(ValueError):
        space.index_of(three.candidate_at(0))   # foreign candidate


def test_encoded_nre_matches_engine_segment_sums(space):
    rng = np.random.default_rng(3)
    idx = rng.integers(0, space.size(), 64)
    enc = space.encoder()
    batch = encode_batch(space, idx)
    ana = jax.device_get(encoded_nre(enc.tables, enc.meta, idx))
    gen = jax.device_get(ENGINE.nre(batch))
    for part in ("modules", "chips", "packages", "d2d", "total"):
        a = np.asarray(getattr(ana, part))
        g = np.asarray(getattr(gen, part))
        scale = np.maximum(np.abs(g), 1e-9)
        assert float(np.max(np.abs(a - g) / scale)) < 1e-6, part


# ---------------------------------------------------------------------------
# SystemBatch.from_arrays
# ---------------------------------------------------------------------------


def test_from_arrays_roundtrip_and_validation(space):
    b = encode_batch(space, np.arange(4))
    leaves = {f: getattr(b, f) for f in SystemBatch._LEAVES}
    rb = SystemBatch.from_arrays(**leaves)
    np.testing.assert_array_equal(np.asarray(ENGINE.total(rb).total),
                                  np.asarray(ENGINE.total(b).total))
    with pytest.raises(ValueError):
        SystemBatch.from_arrays(**{k: v for k, v in leaves.items()
                                   if k != "quantity"})
    with pytest.raises(ValueError):
        SystemBatch.from_arrays(**leaves, extra_leaf=leaves["quantity"])
    bad = dict(leaves)
    bad["quantity"] = leaves["quantity"][:-1]
    with pytest.raises(ValueError):
        SystemBatch.from_arrays(**bad)


# ---------------------------------------------------------------------------
# Fused evaluator: index path == object path == legacy path
# ---------------------------------------------------------------------------


def test_evaluate_indices_matches_object_api_and_legacy(space):
    rng = np.random.default_rng(0)
    idx = np.asarray(sorted({int(i) for i in
                             rng.integers(0, space.size(), 24)}))
    fused = ChunkedEvaluator(space, candidates_per_chunk=8)
    arrays = fused.evaluate_indices(idx)
    assert len(arrays) == idx.size
    obj = fused.evaluate([space.candidate_at(int(i)) for i in idx])
    np.testing.assert_array_equal(
        arrays.portfolio_cost, np.asarray([r.portfolio_cost for r in obj],
                                          arrays.portfolio_cost.dtype))
    legacy = ChunkedEvaluator(space, candidates_per_chunk=8,
                              fused=False).evaluate(
        [space.candidate_at(int(i)) for i in idx])
    worst = max(abs(a.portfolio_cost - b.portfolio_cost) / b.portfolio_cost
                for a, b in zip(obj, legacy))
    assert worst < 1e-6
    with pytest.raises(RuntimeError):
        ChunkedEvaluator(space, fused=False).evaluate_indices(idx)
    with pytest.raises(IndexError):
        fused.evaluate_indices(np.asarray([space.size()]))


def test_fused_risk_stats_match_legacy_quantiles(space):
    rng = np.random.default_rng(1)
    cands = [space.candidate_at(int(i))
             for i in rng.integers(0, space.size(), 6)]
    key = jax.random.PRNGKey(11)
    kw = dict(mc_key=key, mc_draws=64, mc_quantiles=(0.5, 0.9))
    fused = ChunkedEvaluator(space, candidates_per_chunk=8).evaluate(
        cands, **kw)
    legacy = ChunkedEvaluator(space, candidates_per_chunk=8,
                              fused=False).evaluate(cands, **kw)
    for f, l in zip(fused, legacy):
        for stat in ("mean", "q50", "q90"):
            assert f.risk[stat] == pytest.approx(l.risk[stat], rel=1e-5)


def test_mc_re_draws_plus_nre_equals_full_mc(space):
    """NRE is scenario-invariant: RE-only draws plus the one NRE row
    reproduce the full Monte-Carlo totals.  The identity is exact in real
    arithmetic only: the two graphs sum RE and NRE in different orders,
    and XLA (more so on a TPU) may reassociate those float32 adds, so the
    results agree to an ulp or so, not bit for bit."""
    batch = encode_batch(space, np.arange(6))
    key = jax.random.PRNGKey(2)
    sig = np.asarray([0.2, 0.1, 0.25, 0.2], np.float32)
    full = np.asarray(mc_totals(batch, key, n_draws=32))
    re_only = np.asarray(jax.jit(
        lambda b, k: mc_re_totals_impl(b, k, sig, "chip-last", 32))(
        batch, key))
    nre = np.asarray(ENGINE.nre(batch).total)
    np.testing.assert_allclose(full, re_only + nre[None, :], rtol=1e-6)


# ---------------------------------------------------------------------------
# Search: one generation-step trace across generations and runs
# ---------------------------------------------------------------------------


def test_multi_generation_search_compiles_one_generation_step(space):
    kw = dict(population=10, generations=5, elite=3)
    ev = ChunkedEvaluator(space, candidates_per_chunk=8)
    before = dict(TRACE_COUNTS)
    r1 = portfolio_search(space, jax.random.PRNGKey(42), evaluator=ev, **kw)
    after = dict(TRACE_COUNTS)
    assert after.get("gen_step", 0) - before.get("gen_step", 0) == 1, \
        "5 generations must share exactly one generation-step trace"
    # a second same-shaped search (different key) adds zero traces at all
    r2 = portfolio_search(space, jax.random.PRNGKey(43), evaluator=ev, **kw)
    assert dict(TRACE_COUNTS) == after
    assert len(r1.history) == len(r2.history) == 5


def test_portfolio_totals_reduction(space):
    vals = np.asarray([[1.0, 2.0], [3.0, 4.0]], np.float32)
    out = np.asarray(portfolio_totals(vals.reshape(-1), [10.0, 100.0]))
    np.testing.assert_allclose(out, [210.0, 430.0])


def test_risk_search_objective_consistent_with_gen_step(space):
    """The generation step's on-device quantile objective and the final
    materialized risk stats come from the same fused computation — the
    winner's objective must equal the minimum over the ranked list."""
    sr = portfolio_search(space, jax.random.PRNGKey(9), population=8,
                          generations=3, elite=3,
                          risk=RiskConfig(n_draws=32, quantile=0.8))
    assert sr.objective_key == "q80"
    assert sr.best.objective("q80") == min(r.objective("q80")
                                           for r in sr.ranked)
    assert sr.history[-1]["best_objective"] >= sr.best.objective("q80") - 1e-6
