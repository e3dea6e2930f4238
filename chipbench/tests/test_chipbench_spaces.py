"""The ``grid`` space kind reads the candidate order as the program does:
its ``Decoder`` gives the same candidates, systems, indices and what-if
moves as ``DesignSpace.candidate_at``, ``candidate_systems``,
``index_of`` and the service's swap, at seeded indices of every
configuration of the benchmark."""
import json

import numpy as np
import pytest

from chipbench_cases import BENCH

from harness import runner
from harness.catalog import Catalog
from repro.dse.space import candidate_systems
from repro.service.server import PricingService

CONFIGS = sorted(p.stem for p in (BENCH / "configs").glob("*.json"))


def _as_ref(cand):
    """A program ``Candidate`` in the decoder's form."""
    if cand.reuse is not None:
        r = cand.reuse
        return ("reuse", (r.slice_area_mm2, r.process, r.integration,
                          r.package_reuse))
    return ("arch", tuple((c.n_chiplets, c.process, c.integration)
                          for c in cand.choices))


def _system(s):
    """A program ``System`` as the fields the reference prices."""
    return {"name": s.name, "integration": s.integration,
            "quantity": s.quantity, "package_id": s.package_id,
            "package_area": s.package_area,
            "chips": [(c.name, c.process, c.early_defects,
                       [(m.name, m.area_mm2) for m in c.modules])
                      for c in s.chips]}


def _ref_system(s):
    return {"name": s["name"], "integration": s["integration"],
            "quantity": s["quantity"], "package_id": s["package_id"],
            "package_area": s["package_area"],
            "chips": [(c["name"], c["process"], c["early"],
                       [(m[0], m[1]) for m in c["modules"]])
                      for c in s["chips"]]}


def _close(a, b):
    if isinstance(a, float):
        return b == pytest.approx(a, rel=1e-12)
    if isinstance(a, (list, tuple)):
        return len(a) == len(b) and all(_close(x, y) for x, y in zip(a, b))
    if isinstance(a, dict):
        return a.keys() == b.keys() and all(_close(a[k], b[k]) for k in a)
    return a == b


@pytest.fixture(scope="module", params=CONFIGS)
def spaces(request):
    cat = Catalog(BENCH)
    cfg = cat.config(request.param)
    kind = cfg["space"].get("kind", "grid")
    return (runner.design_space(cfg),
            cat.space_kind(kind).Decoder(cfg["space"]))


def _indices(space, n=48):
    size = space.size()
    idx = np.random.default_rng(20260).integers(0, size, n).tolist()
    return sorted(set(idx) | {0, size - 1})


def test_size_and_menus(spaces):
    space, dec = spaces
    assert dec.size == space.size()
    assert dec.processes == list(space.processes)
    assert dec.integrations == list(space.integrations)


def test_candidate_and_index(spaces):
    space, dec = spaces
    for i in _indices(space):
        cand = space.candidate_at(i)
        assert dec.candidate(i) == _as_ref(cand), i
        assert dec.index(dec.candidate(i)) == i == space.index_of(cand)
    with pytest.raises(IndexError):
        dec.candidate(dec.size)


def test_systems(spaces):
    space, dec = spaces
    for i in _indices(space):
        got = [_ref_system(s) for s in dec.systems(dec.candidate(i))]
        want = [_system(s) for s in candidate_systems(
            space, space.candidate_at(i))]
        assert _close(want, got), i


def test_swap(spaces):
    space, dec = spaces
    for i in _indices(space, 16):
        cand = space.candidate_at(i)
        for p in space.processes:
            for t in space.integrations:
                try:
                    want = space.index_of(PricingService._swap_tech(cand, p,
                                                                    t))
                except (ValueError, KeyError):
                    want = None
                assert dec.index(dec.swap(dec.candidate(i), p, t)) == want


def test_configs_name_their_kind_files():
    """Every configuration's space kind resolves to a file with ``build``
    and ``Decoder``; a missing kind means ``grid``."""
    cat = Catalog(BENCH)
    for name in CONFIGS:
        space = cat.config(name)["space"]
        mod = cat.space_kind(space.get("kind", "grid"))
        assert callable(mod.build) and callable(mod.Decoder)
    with pytest.raises(FileNotFoundError):
        cat.space_kind("no_such_kind")
    assert json.loads((BENCH / "configs" / "fig4_grid.json").read_text())[
        "space"].get("kind") is None
