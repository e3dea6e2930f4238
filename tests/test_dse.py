"""repro.dse: padded-chunk parity with the direct engine path, constant
trace counts across chunk boundaries, seeded search determinism, and the
exhaustive-enumeration cross-check of the portfolio optimizer."""
import json
import pathlib

import jax
import numpy as np
import pytest

from repro.core import CostEngine, SystemBatch, pad_batch, split_system
from repro.core.system import spec
from repro.core.engine import TRACE_COUNTS
from repro.dse import (Candidate, ChunkedEvaluator, DesignSpace, SKU,
                       Uncertainty, candidate_systems, chunk_shape,
                       detail_rows, evaluate_direct, exhaustive_search,
                       mc_summary, mc_totals, portfolio_search, result_rows,
                       RiskConfig, sensitivities, search_summary, to_json)

ENGINE = CostEngine()


def _space(**kw):
    d = dict(skus=(SKU("laptop", 200.0, 2e6), SKU("server", 400.0, 5e5)),
             processes=("7nm", "12nm"), integrations=("MCM",),
             chiplet_counts=(1, 2, 4), allow_reuse=True)
    d.update(kw)
    return DesignSpace(**d)


# One module-scoped evaluator so every test reuses the same chunk shape
# (and therefore the same compiled trace) — mirrors real usage.
@pytest.fixture(scope="module")
def space():
    return _space()


@pytest.fixture(scope="module")
def evaluator(space):
    return ChunkedEvaluator(space, candidates_per_chunk=8)


# ---------------------------------------------------------------------------
# Space algebra
# ---------------------------------------------------------------------------


def test_space_is_countable_and_decodable(space):
    cands = list(space.enumerate_candidates())
    assert len(cands) == space.size()
    assert cands == [space.candidate_at(i) for i in range(space.size())]
    # valid reuse slices: every SKU area is an in-range integer multiple
    for r in space.reuse_choices():
        counts = space.reuse_counts(r)
        for sku, k in zip(space.skus, counts):
            assert k in space.chiplet_counts
            assert sku.module_area_mm2 == pytest.approx(
                k * r.slice_area_mm2, rel=1e-6)


def test_candidate_systems_reuse_shares_one_design(space):
    r = space.reuse_choices()[0]
    systems = candidate_systems(space, Candidate(reuse=r))
    names = {c.name for s in systems for c in s.chips}
    assert len(names) == 1                      # one chiplet design
    assert [s.n_chips for s in systems] == list(space.reuse_counts(r))
    assert [s.quantity for s in systems] == [s2.quantity
                                             for s2 in space.skus]


def test_space_rejects_bad_configs():
    with pytest.raises(ValueError):
        _space(integrations=("SoC",))
    with pytest.raises(ValueError):
        _space(skus=(SKU("a", 100.0, 1.0), SKU("a", 200.0, 1.0)))
    with pytest.raises(KeyError):
        _space(processes=("3nm",))
    with pytest.raises(ValueError):
        _space(processes=())
    with pytest.raises(ValueError):
        _space(integrations=())


def test_foreign_reuse_candidate_and_short_names_are_rejected(space):
    from repro.core import portfolio_reuse_systems
    from repro.dse import ReuseChoice
    # a slice that does not tile the SKU inventories must not price
    with pytest.raises(ValueError):
        candidate_systems(space, Candidate(reuse=ReuseChoice(
            70.0, "7nm", "MCM")))
    with pytest.raises(ValueError):
        portfolio_reuse_systems(100.0, "7nm", "MCM", counts=[1, 2],
                                quantities=[1e6, 5e5], names=["only_one"])


def test_result_rows_top_zero_means_zero(space, evaluator):
    res = evaluator.evaluate([space.candidate_at(0)])
    assert result_rows(res, top=0) == []
    assert len(result_rows(res)) == 1


def test_mismatched_candidate_and_evaluator_are_rejected(space):
    three = _space(skus=(SKU("a", 100.0, 1.0), SKU("b", 200.0, 1.0),
                         SKU("c", 400.0, 1.0)))
    foreign = three.candidate_at(0)          # 3 per-SKU choices
    with pytest.raises(ValueError):
        candidate_systems(space, foreign)    # 2-SKU space
    ev = ChunkedEvaluator(three, candidates_per_chunk=4)
    with pytest.raises(ValueError):
        exhaustive_search(space, evaluator=ev)
    with pytest.raises(ValueError):
        portfolio_search(space, jax.random.PRNGKey(0),
                         evaluator=ChunkedEvaluator(space),
                         flow="chip-first")  # evaluator bound chip-last


# ---------------------------------------------------------------------------
# pad_batch — cost-neutral padding
# ---------------------------------------------------------------------------


def test_pad_batch_preserves_real_rows_and_zeroes_padding():
    a = split_system("a", 400.0, "7nm", 2, "MCM", quantity=1e6)
    b = split_system("b", 600.0, "5nm", 3, "2.5D", quantity=5e5)
    batch = SystemBatch.from_systems([a, b], share_nre=True)
    tc = ENGINE.total(batch)
    padded = pad_batch(batch, n_systems=5, max_chips=6, chip_entities=9,
                       pkg_entities=6, mod_entities=9, mod_instances=12,
                       d2d_entities=5, d2d_instances=12)
    tp = ENGINE.total(padded)
    for i in range(2):
        assert float(tp.total[i]) == pytest.approx(float(tc.total[i]),
                                                   rel=1e-6)
    for i in range(2, 5):
        assert float(tp.total[i]) == 0.0
    assert padded.names[2:] == ("__pad0", "__pad1", "__pad2")


def test_pad_batch_refuses_to_shrink_or_strand_instances():
    batch = SystemBatch.from_systems(
        [split_system("a", 400.0, "7nm", 2, "MCM")])
    with pytest.raises(ValueError):
        pad_batch(batch, n_systems=0)
    with pytest.raises(ValueError):
        # more instances but nowhere harmless to park them
        pad_batch(batch, mod_instances=batch.mod_sys.shape[0] + 2)


# The five raw spec groups of the benchmark's Fig. 8 points cell, padded to
# that cell's raw-lane signature (raw_slots 16, raw_max_chips 4).
_FIG8_GROUPS = next(
    m["params"]["groups"] for m in json.loads(
        (pathlib.Path(__file__).resolve().parents[1] / "chipbench"
         / "workloads" / "scms_fig8.points.json").read_text())["open"]["mix"]
    if m["kind"] == "price_systems")
_RAW_PAD = dict(n_systems=16, max_chips=4, chip_entities=65, pkg_entities=17,
                mod_entities=129, mod_instances=128, d2d_entities=65,
                d2d_instances=64)


def _mixed_systems():
    """D2D modules, chiplet and package reuse inside each of two NRE
    groups that also share entity names across the groups."""
    def reuse(name, n, integration):
        return spec({"kind": "chips", "name": name, "integration": integration,
                     "quantity": 2e5 * n, "package_name": "pkg",
                     "package_area": 900.0,
                     "chips": [{"name": "tile", "area": 150.0,
                                "process": "7nm"}] * n})
    group = [reuse("r1", 1, "MCM"), reuse("r3", 3, "MCM"),
             split_system("h", 500.0, "5nm", 2, "2.5D", quantity=4e5),
             spec({"kind": "soc", "name": "m", "area": 120.0,
                   "process": "12nm", "quantity": 1e6})]
    return group + group, [0] * 4 + [1] * 4


_PACK_CASES = [
    pytest.param([spec(dict(d)) for d in g], [0] * len(g), _RAW_PAD,
                 id=f"fig8_group{i}")
    for i, g in enumerate(_FIG8_GROUPS)] + [
    pytest.param(*_mixed_systems(),
                 dict(n_systems=11, max_chips=5, chip_entities=10,
                      pkg_entities=8, mod_entities=10, mod_instances=17,
                      d2d_entities=6, d2d_instances=15), id="mixed")]


@pytest.mark.parametrize("systems,groups,pad", _PACK_CASES)
def test_host_pack_and_pad_equal_the_device_path(systems, groups, pad):
    """Packing and padding on the host gives, leaf for leaf, the arrays of
    the device path (device leaves from ``from_systems``, padded through
    ``pad_batch``'s fetch and put): same dtypes, values and names."""
    host = pad_batch(SystemBatch.pack(systems, share_nre=groups, max_chips=4),
                     **pad)
    dev = pad_batch(SystemBatch.from_systems(systems, share_nre=groups,
                                             max_chips=4), **pad)
    assert host.names == dev.names
    for f in SystemBatch._LEAVES:
        a, b = getattr(host, f), getattr(dev, f)
        assert isinstance(a, np.ndarray) and isinstance(b, jax.Array), f
        b = np.asarray(jax.device_get(b))
        assert a.dtype == b.dtype and a.dtype in (np.float32, np.int32), f
        assert np.array_equal(a, b), f
    # every axis really was padded
    assert host.chip_area.shape == (pad["n_systems"], pad["max_chips"])
    assert host.mod_sys.shape[0] == pad["mod_instances"]
    assert host.d2d_sys.shape[0] == pad["d2d_instances"]
    if groups[-1]:
        with pytest.raises(ValueError):     # only float32/int32 travel
            host.replace(quantity=host.quantity.astype(np.float64)) \
                .to_device()
        # "tile" is one design inside each group, two across the groups
        ids = host.chip_entity_id
        assert ids[0, 0] == ids[1, 2] != ids[4, 0] == ids[5, 2]
        assert host.pkg_entity_id[0] != host.pkg_entity_id[4]


def test_share_nre_groups_match_independent_shared_batches():
    a = split_system("a", 400.0, "7nm", 2, "MCM", quantity=1e6)
    b = split_system("b", 600.0, "5nm", 3, "MCM", quantity=5e5)
    grouped = ENGINE.total(
        SystemBatch.from_systems([a, b, a, b], share_nre=[0, 0, 1, 1]))
    ref = ENGINE.total(SystemBatch.from_systems([a, b], share_nre=True))
    for i in range(4):
        assert float(grouped.total[i]) == pytest.approx(
            float(ref.total[i % 2]), rel=1e-6)
    with pytest.raises(ValueError):   # duplicate name inside one group
        SystemBatch.from_systems([a, a], share_nre=[0, 0])
    with pytest.raises(ValueError):   # group list length mismatch
        SystemBatch.from_systems([a, b], share_nre=[0])


# ---------------------------------------------------------------------------
# Chunked evaluation: parity + single-trace contract
# ---------------------------------------------------------------------------


def test_padded_chunk_pricing_matches_direct_engine_total(space, evaluator):
    cands = list(space.enumerate_candidates())
    results = evaluator.evaluate(cands)
    assert len(results) == len(cands)
    stride = max(1, len(results) // 11)
    for r in results[::stride]:
        direct = evaluate_direct(space, r.candidate)
        np.testing.assert_allclose(r.sku_unit_total, direct.sku_unit_total,
                                   rtol=1e-5)
        assert r.portfolio_cost == pytest.approx(direct.portfolio_cost,
                                                 rel=1e-5)


def test_trace_counts_constant_across_chunk_boundaries(space, evaluator):
    cands = list(space.enumerate_candidates())
    k = evaluator.shape.candidates
    assert len(cands) > 3 * k          # the stream really spans chunks
    evaluator.evaluate(cands[:k])      # warm (or reuse) the chunk trace
    before = dict(TRACE_COUNTS)
    evaluator.evaluate(cands)          # full + partially-filled chunks
    assert dict(TRACE_COUNTS) == before


def test_chunk_shape_bounds_are_sufficient(space):
    # the widest candidates must fit the declared signature
    sh = chunk_shape(space, 4)
    ev = ChunkedEvaluator(space, candidates_per_chunk=4)
    widest = sorted(space.enumerate_candidates(),
                    key=lambda c: -sum(s.n_chips
                                       for s in candidate_systems(space, c)))
    batch = ev.pack_chunk(widest[:4])
    assert batch.chip_area.shape == (sh.n_systems, sh.max_chips)
    assert batch.mod_sys.shape[0] == sh.mod_instances


# ---------------------------------------------------------------------------
# Uncertainty: Monte Carlo + sensitivities
# ---------------------------------------------------------------------------


def test_mc_is_deterministic_and_median_preserving(space):
    batch = SystemBatch.from_systems(
        candidate_systems(space, space.candidate_at(0)), share_nre=True)
    key = jax.random.PRNGKey(7)
    d1 = np.asarray(mc_totals(batch, key, n_draws=96))
    d2 = np.asarray(mc_totals(batch, key, n_draws=96))
    np.testing.assert_array_equal(d1, d2)
    assert d1.shape == (96, len(batch))
    s = mc_summary(batch, key, n_draws=96, quantiles=(0.05, 0.5, 0.95))
    nominal = np.asarray(ENGINE.total(batch).total)
    # lognormal multipliers are median-preserving: q50 ~ nominal
    np.testing.assert_allclose(np.asarray(s["q50"]), nominal, rtol=0.08)
    assert np.all(np.asarray(s["q5"]) <= np.asarray(s["q95"]))
    # zero sigmas collapse the distribution onto the nominal model
    z = Uncertainty(0.0, 0.0, 0.0, 0.0)
    dz = np.asarray(mc_totals(batch, key, n_draws=8, sigmas=z))
    np.testing.assert_allclose(dz, np.broadcast_to(nominal, dz.shape),
                               rtol=1e-5)


def test_sensitivities_signs_and_shapes(space):
    batch = SystemBatch.from_systems(
        candidate_systems(space, space.candidate_at(1)), share_nre=True)
    g = sensitivities(batch)
    n = len(batch)
    for k, v in g.items():
        assert v.shape == (n,), k
        assert bool(np.all(np.isfinite(np.asarray(v)))), k
    # more defects / pricier wafers cost money; better bond yield saves it
    assert np.all(np.asarray(g["chip_defect"]) > 0.0)
    assert np.all(np.asarray(g["chip_wafer_cost"]) > 0.0)
    assert np.all(np.asarray(g["y2_chip_bond"]) <= 0.0)


# ---------------------------------------------------------------------------
# Search: exhaustive cross-check + seeded determinism
# ---------------------------------------------------------------------------


def test_search_recovers_exhaustive_best(space, evaluator):
    ex = exhaustive_search(space, evaluator=evaluator)
    assert ex.n_evaluated == space.size()
    # independent cross-check of the exhaustive winner via the direct,
    # unchunked engine path
    direct_best = min((evaluate_direct(space, c)
                       for c in space.enumerate_candidates()),
                      key=lambda r: (r.portfolio_cost, r.label))
    assert ex.best.label == direct_best.label
    assert ex.best.portfolio_cost == pytest.approx(
        direct_best.portfolio_cost, rel=1e-5)

    sr = portfolio_search(space, jax.random.PRNGKey(0), population=12,
                          generations=6, elite=4, evaluator=evaluator)
    assert sr.best.label == ex.best.label
    assert sr.best.portfolio_cost == pytest.approx(ex.best.portfolio_cost,
                                                   rel=1e-6)
    assert sr.n_evaluated <= space.size()


def test_search_same_key_same_winner(space, evaluator):
    key = jax.random.PRNGKey(123)
    r1 = portfolio_search(space, key, population=10, generations=4,
                          elite=3, evaluator=evaluator)
    r2 = portfolio_search(space, key, population=10, generations=4,
                          elite=3, evaluator=evaluator)
    assert r1.best.label == r2.best.label
    assert r1.best.portfolio_cost == r2.best.portfolio_cost
    assert [h["best_label"] for h in r1.history] == \
        [h["best_label"] for h in r2.history]
    assert r1.n_evaluated == r2.n_evaluated


def test_risk_aware_search_produces_quantile_objective_and_front(space):
    ev = ChunkedEvaluator(space, candidates_per_chunk=8)
    sr = portfolio_search(space, jax.random.PRNGKey(5), population=10,
                          generations=3, elite=3, evaluator=ev,
                          risk=RiskConfig(n_draws=48, quantile=0.9))
    assert sr.objective_key == "q90"
    assert sr.best.risk is not None
    assert sr.best.risk["q90"] >= sr.best.risk["q50"] - 1e-6
    assert sr.pareto and all("q90" in p for p in sr.pareto)
    # the common-random-numbers quantile ordering is internally consistent
    assert sr.best.objective("q90") == min(
        r.objective("q90") for r in sr.ranked)
    # same search key => identical MC scenarios in the exhaustive run, so
    # the quantile objectives of shared candidates agree exactly
    ex = exhaustive_search(space, evaluator=ev, key=jax.random.PRNGKey(5),
                           risk=RiskConfig(n_draws=48, quantile=0.9))
    ex_by_label = {r.label: r for r in ex.ranked}
    for r in sr.ranked:
        assert r.risk["q90"] == pytest.approx(
            ex_by_label[r.label].risk["q90"], rel=1e-6)
    assert ex.best.objective("q90") <= sr.best.objective("q90") + 1e-6


# ---------------------------------------------------------------------------
# Reporting
# ---------------------------------------------------------------------------


def test_report_rows_and_json(space, evaluator):
    res = evaluator.evaluate([space.candidate_at(0), space.candidate_at(1)])
    rows = result_rows(res)
    assert len(rows) == 2
    for sku in space.skus:
        assert f"{sku.name}:unit" in rows[0]
    # detail rows follow the CostEngine.as_rows column contract
    det = detail_rows(space, res[0].candidate)
    assert [r["system"] for r in det] == [s.name for s in space.skus]
    assert {"raw_chips", "nre_total", "re_total", "total"} <= set(det[0])
    sr = exhaustive_search(space, evaluator=evaluator)
    js = to_json(search_summary(sr, top=3))
    assert sr.best.label in js
