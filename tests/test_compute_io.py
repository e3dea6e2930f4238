"""Compute/IO design spaces (the paper's Fig. 5 AMD line): SKUs whose
multi-chip systems hold an IO die on its own node beside their compute
chiplets.  The encoder's batch prices exactly like the host packing of
``candidate_systems``; the closed-form NRE amortizes each IO die over the
SKUs that share it, as the engine and the scalar model do; the service's
chunk, MC and what-if lanes serve such a space and agree with its raw
lane; the fingerprint, the journal and the admission counters see the IO
dies."""
import dataclasses
import json

import jax
import numpy as np
import pytest

from repro.core import CostEngine, re_cost
from repro.core.nre_cost import amortized_costs
from repro.core.system import Module, System, make_chip
from repro.dse import (ArchChoice, Candidate, ChunkedEvaluator, DesignSpace,
                       IODie, ReuseChoice, SKU, candidate_systems,
                       chunk_shape, encode_batch, exhaustive_search,
                       portfolio_search)
from repro.dse.space import encoded_nre
from repro.service import (McSpec, MCRiskRequest, PriceRequest,
                           PriceSystemsRequest, ServiceConfig,
                           WhatIfRequest, serve)
from repro.service.cache import space_fingerprint
from repro.service.durability import RequestJournal, request_to_wire

ENGINE = CostEngine()
CIOD = IODie("cIOD", 125.0, "12nm")
SIOD = IODie("sIOD", 416.0, "12nm")
XIOD = IODie("xIOD", 60.0, "14nm")


def _space(**kw):
    d = dict(skus=(SKU("r8", 74.0, 1e6, CIOD), SKU("r16", 148.0, 5e5, CIOD),
                   SKU("e32", 296.0, 2e5, SIOD), SKU("x", 148.0, 1e5, XIOD)),
             processes=("7nm",), integrations=("MCM", "2.5D"),
             chiplet_counts=(1, 2, 4), allow_reuse=True,
             reuse_package_options=(False, True))
    d.update(kw)
    return DesignSpace(**d)


def _amd(**kw):
    """The four-SKU AMD line of the benchmark's ``fig5_amd``."""
    d = dict(skus=(SKU("r8", 74.0, 1e6, CIOD), SKU("r16", 148.0, 5e5, CIOD),
                   SKU("e32", 296.0, 2e5, SIOD),
                   SKU("e64", 592.0, 1e5, SIOD)),
             processes=("7nm", "5nm"), integrations=("MCM", "InFO", "2.5D"),
             chiplet_counts=(1, 2, 4, 8), allow_reuse=True,
             reuse_package_options=(False, True))
    d.update(kw)
    return DesignSpace(**d)


@pytest.fixture(scope="module")
def space():
    return _space()


# two SKUs share an IO die (r8, r16: cIOD on MCM) and two do not
SHARED = Candidate(choices=(ArchChoice(2, "7nm", "MCM"),
                            ArchChoice(4, "7nm", "MCM"),
                            ArchChoice(2, "7nm", "MCM"),
                            ArchChoice(2, "7nm", "MCM")))
# r8 and r16 on different packagings: no IO die shared
APART = Candidate(choices=(ArchChoice(2, "7nm", "MCM"),
                           ArchChoice(2, "7nm", "2.5D"),
                           ArchChoice(1, "7nm", "SoC"),
                           ArchChoice(4, "7nm", "2.5D")))


def test_geometry_counts_the_io_die(space):
    amd = _amd()
    assert (amd.size(), amd.max_chips()) == (160_012, 9)
    assert amd.d2d_processes() == ("7nm", "5nm", "12nm")
    assert amd.reuse_slice_areas() == [74.0]
    assert space.max_chips() == 5
    assert space.d2d_processes() == ("7nm", "12nm", "14nm")
    shape = chunk_shape(space, 8)
    assert (shape.max_chips, shape.d2d_entities) == (5, 8 * 3 + 1)
    # the SoC holds compute and IO content on one compute-node die
    soc = candidate_systems(space, space.candidate_at(0))
    assert [s.chips[0].module_area_mm2 for s in soc] == [199.0, 273.0,
                                                         712.0, 208.0]
    # a split holds its compute chiplets, then the IO die on its own node
    r8, r16, e32, x = candidate_systems(space, SHARED)
    assert [c.process for c in r16.chips] == ["7nm"] * 4 + ["12nm"]
    assert r8.chips[-1].name == r16.chips[-1].name == "cIOD_MCM"
    assert x.chips[-1].process == "14nm"


def test_io_die_is_one_design():
    with pytest.raises(ValueError):
        _space(skus=(SKU("a", 74.0, 1.0, CIOD),
                     SKU("b", 74.0, 1.0, IODie("cIOD", 99.0, "12nm"))))
    with pytest.raises(KeyError):             # not a node of the model
        _space(skus=(SKU("a", 74.0, 1.0, IODie("io", 10.0, "3nm")),))


def test_reuse_package_is_sized_for_the_largest_system(space):
    r = ReuseChoice(74.0, "7nm", "2.5D", package_reuse=True)
    systems = candidate_systems(space, Candidate(reuse=r))
    biggest = max(s.silicon_area_mm2 for s in systems)
    assert {s.package_area for s in systems} == {
        biggest * systems[0].tech.package_area_factor}
    assert biggest == systems[2].silicon_area_mm2     # e32 + its sIOD
    assert [s.n_chips for s in systems] == [2, 3, 5, 3]


@pytest.mark.parametrize("kw", [
    {}, {"reuse_within_sku": False},
    {"skus": _space().skus[:3], "processes": ("7nm", "12nm")},
    {"skus": _space().skus[:3], "integrations": ("InFO",),
     "chiplet_counts": (1, 2, 4, 8)}])
def test_encode_batch_full_enumeration_bit_parity(kw):
    """Every candidate, encoded from its index, prices bit for bit like
    the host packing of its ``candidate_systems`` (IO dies on a node of
    the compute menu, 12 nm, share the D2D design with compute dies)."""
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


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b) / np.maximum(np.abs(b), 1e-9)))


def test_encoded_nre_matches_engine_and_scalar_model(space):
    rng = np.random.default_rng(16)
    cands = [SHARED, APART] + [space.candidate_at(int(i)) for i in
                               rng.integers(0, space.size(), 40)]
    cands += list(space.enumerate_candidates())[-4:]     # reuse schemes
    idx = np.asarray([space.index_of(c) for c in cands])
    enc = space.encoder()
    ana = jax.device_get(encoded_nre(enc.tables, enc.meta, idx))
    gen = jax.device_get(ENGINE.nre(encode_batch(space, idx)))
    for part in ("modules", "chips", "packages", "d2d", "total"):
        assert _rel(getattr(ana, part), getattr(gen, part)) < 1e-6, part
    scalar = {"modules": [], "chips": [], "packages": [], "d2d": []}
    for c in cands:
        for u in amortized_costs(candidate_systems(space, c)).values():
            for part in scalar:
                scalar[part].append(getattr(u, "nre_" + part))
    for part, want in scalar.items():
        assert _rel(getattr(ana, part), want) < 2e-6, part
    # r8 is 2x/7nm/MCM in both: in SHARED its cIOD design is split with
    # r16's units, in APART it is r8's alone
    io = candidate_systems(space, SHARED)[0].chips[-1]
    nre_io = (io.node.nre_chip_per_mm2 * io.area_mm2
              + io.node.nre_fixed_per_chip)
    chips = np.asarray(ana.chips)
    assert chips[0] - chips[4] == pytest.approx(
        nre_io / 1.5e6 - nre_io / 1e6, rel=1e-4)


def test_encoder_refuses_general_mixed_dies(space):
    enc = space.encoder()
    a = make_chip("a", [Module("a_m", 50.0, "7nm")], "7nm", "MCM")
    b = make_chip("b", [Module("b_m", 80.0, "5nm")], "5nm", "MCM")
    mixed = System("mixed", (a, b, a), "MCM", quantity=1.0)
    tab = {k: np.zeros_like(np.asarray(v)) for k, v in enc.tables.items()}
    with pytest.raises(ValueError, match="general mixed dies"):
        enc._fill(tab, 0, 0, mixed)


def test_fig5_tie_reuse_candidate_matches_fig5_script():
    """The shipped line (one 74 mm^2 7 nm CCD on MCM, no package reuse),
    at the Fig. 5 script's early-ramp defect densities, costs what
    ``benchmarks/fig5_amd.py`` prices for its 8/16/32-core MCM parts."""
    from benchmarks import fig5_amd

    amd = _amd()
    systems = candidate_systems(amd, Candidate(
        reuse=ReuseChoice(74.0, "7nm", "MCM", package_reuse=False)))
    early = [dataclasses.replace(s, chips=tuple(
        dataclasses.replace(c, early_defects=True) for c in s.chips))
        for s in systems]
    rows = {r["cores"]: r for r in fig5_amd.run()}
    for cores, s in zip((8, 16, 32), early):
        assert re_cost(s).total == pytest.approx(rows[cores]["mcm_total"],
                                                 rel=1e-12)


def test_search_finds_the_exhaustive_best(space):
    """The evolutionary search breeds compute/IO candidates unchanged: it
    finds what pricing all of them finds (here AMD's own answer, one
    74 mm^2 7 nm CCD on MCM shared by every SKU)."""
    ev = ChunkedEvaluator(space, candidates_per_chunk=64)
    best = exhaustive_search(space, evaluator=ev).best
    found = portfolio_search(space, jax.random.PRNGKey(0), population=32,
                             generations=6, elite=4, evaluator=ev).best
    assert found.label == best.label == "reuse[74mm2/7nm/MCM]"
    assert found.portfolio_cost == best.portfolio_cost


CFG = ServiceConfig(chunk=16, split=4, warm_mc=((64, (0.5, 0.9)),))


def _chip_spec(s: System):
    return {"kind": "chips", "name": s.name, "chips": list(s.chips),
            "integration": s.integration, "quantity": s.quantity,
            "package_name": s.package_name,
            "package_area": s.package_area_mm2}


def test_chunk_lane_equals_raw_lane_and_every_lane_serves(space):
    rng = np.random.default_rng(3)
    cands = [SHARED, APART, list(space.enumerate_candidates())[-1]]
    idx = [space.index_of(c) for c in cands]
    idx += [int(i) for i in rng.integers(0, space.size(), 5)]
    reqs = [PriceRequest(indices=idx)]
    reqs += [PriceSystemsRequest(specs=tuple(
        _chip_spec(s) for s in candidate_systems(space,
                                                  space.candidate_at(i))))
        for i in idx]
    reqs += [MCRiskRequest(indices=idx[:4], mc=McSpec(draws=64,
                                                      quantiles=(0.5, 0.9))),
             WhatIfRequest(base=idx[0])]
    resps, svc = serve(space, reqs, CFG)
    assert all(r.ok for r in resps), [r.error for r in resps if not r.ok]
    chunk = resps[0].result
    for j, r in enumerate(resps[1:1 + len(idx)]):
        raw = np.asarray([row["total"] for row in r.result.rows])
        np.testing.assert_allclose(chunk.sku_unit_total[j], raw, rtol=1e-6)
    assert resps[-1].result.rows                  # the what-if grid
    np.testing.assert_allclose(resps[-2].result.portfolio_cost,
                               chunk.portfolio_cost[:4], rtol=1e-6)
    # the admission counters: real chips and padded slots of chunk rows
    rows = idx + idx[:4] + [idx[0]] + [
        space.index_of(svc._swap_tech(space.candidate_at(idx[0]), p, t))
        for p in space.processes for t in space.integrations]
    chips = sum(s.n_chips for i in rows
                for s in candidate_systems(space, space.candidate_at(i)))
    assert svc.metrics.chunk_chips == chips
    assert svc.metrics.chunk_chip_slots == len(rows) * 4 * 5


def test_fingerprint_sees_the_io_dies(space):
    other = _space(skus=space.skus[:3] + (
        SKU("x", 148.0, 1e5, IODie("xIOD", 61.0, "14nm")),))
    assert space_fingerprint(other) != space_fingerprint(space)
    no_io = _space(skus=tuple(dataclasses.replace(s, io_die=None)
                              for s in space.skus))
    assert space_fingerprint(no_io) != space_fingerprint(space)
    # a space without IO dies keeps its digest
    import hashlib
    payload = {"skus": [[s.name, s.module_area_mm2, s.quantity]
                        for s in no_io.skus],
               "processes": ["7nm"], "integrations": ["MCM", "2.5D"],
               "chiplet_counts": [1, 2, 4], "allow_reuse": True,
               "reuse_package_options": [False, True],
               "reuse_within_sku": True}
    assert space_fingerprint(no_io) == hashlib.sha1(json.dumps(
        payload, sort_keys=True).encode()).hexdigest()


def test_journal_round_trip_of_a_compute_io_request(space, tmp_path):
    cands = (SHARED, APART)
    j = RequestJournal(tmp_path, fingerprint=space_fingerprint(space))
    j.admit(1, request_to_wire(PriceRequest(candidates=cands), space))
    j.close()
    j2 = RequestJournal(tmp_path, fingerprint=space_fingerprint(space))
    (entry,) = j2.replay()
    j2.close()
    assert [space.candidate_at(i) for i in entry.request.indices] \
        == list(cands)


def test_encoder_build_span_in_a_profiler_trace(tmp_path):
    """Building the encoder (at service start) is a ``repro.encoder_build``
    span on the profiler's clock."""
    from jax.profiler import ProfileData

    with jax.profiler.trace(str(tmp_path)):
        _space(chiplet_counts=(1, 2)).encoder()
    (path,) = tmp_path.rglob("*.xplane.pb")
    names = {e.name for plane in ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events}
    assert "repro.encoder_build" in names
