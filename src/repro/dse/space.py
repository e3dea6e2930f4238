"""Declarative multi-chiplet design-space definition (repro.dse).

A :class:`DesignSpace` describes a *product portfolio* — the SKUs a
vendor ships, each with a module inventory (total functional area) and a
production volume — together with the architectural freedoms the search
may exercise: allowed process nodes, integration technologies, chiplet
counts, and cross-SKU chiplet-reuse (the paper's SCMS scheme generalized
to arbitrary per-SKU socket counts via
:func:`repro.core.reuse.portfolio_reuse_systems`).

A :class:`Candidate` is one fully concrete point of that space: either a
per-SKU tuple of :class:`ArchChoice` (independent architectures) or a
:class:`ReuseChoice` (one shared chiplet design collocated across the
whole portfolio).  ``candidate_systems`` lowers a candidate to the
:class:`~repro.core.system.System` group that
:class:`~repro.core.batch.SystemBatch` packs and the engine prices.

A SKU may carry an :class:`IODie` (the paper's Fig. 5 compute/IO split):
every multi-chip system of that SKU then holds its compute chiplets plus
the IO die on the IO die's own node, and the monolithic SoC holds the
compute and IO content on one die of the compute node.

The space is countable: ``size()`` / ``candidate_at(i)`` give a total
order, so exhaustive enumeration, uniform sampling and index-based
decoding all agree — the property the seeded-determinism tests pin.
"""
from __future__ import annotations

import dataclasses
import functools
import itertools
from typing import Dict, Iterator, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from ..core.batch import SystemBatch
from ..core.engine import NREBreakdown
from ..core.reuse import portfolio_reuse_systems
from ..core.system import Chip, Module, System, make_chip, spec
from ..core.technology import node, tech
from ..obs.trace import TRACER as _TRACER

_REL_TOL = 1e-6


@dataclasses.dataclass(frozen=True)
class IODie:
    """A SKU's IO die: one die of ``area_mm2`` on its own ``process``,
    beside the compute chiplets of every multi-chip architecture.  Its
    design is named by ``name`` and the integration: within a candidate,
    the SKUs whose IO dies share both share one design."""

    name: str
    area_mm2: float
    process: str


@dataclasses.dataclass(frozen=True)
class SKU:
    """One product in the portfolio: a module inventory (the compute
    content, which the architecture choices split), its volume and an
    optional IO die."""

    name: str
    module_area_mm2: float
    quantity: float
    io_die: Optional[IODie] = None


@dataclasses.dataclass(frozen=True)
class ArchChoice:
    """Architecture of a single SKU: ``n_chiplets`` even slices of the
    module area on ``process``, packaged with ``integration``.

    ``n_chiplets == 1`` always means the monolithic SoC baseline
    (integration "SoC", no D2D overhead), as in the paper's Fig. 4.
    """

    n_chiplets: int
    process: str
    integration: str

    def label(self) -> str:
        if self.n_chiplets == 1:
            return f"soc/{self.process}"
        return f"{self.n_chiplets}x/{self.process}/{self.integration}"


@dataclasses.dataclass(frozen=True)
class ReuseChoice:
    """One shared chiplet design across the whole portfolio (SCMS-style):
    every SKU is ``round(area / slice_area_mm2)`` copies of the slice."""

    slice_area_mm2: float
    process: str
    integration: str
    package_reuse: bool = False

    def label(self) -> str:
        pkg = "+pkg" if self.package_reuse else ""
        return (f"reuse[{self.slice_area_mm2:g}mm2/{self.process}"
                f"/{self.integration}{pkg}]")


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One concrete portfolio architecture (hashable — search dedup key)."""

    choices: Tuple[ArchChoice, ...] = ()
    reuse: Optional[ReuseChoice] = None

    def __post_init__(self):
        if (self.reuse is None) == (not self.choices):
            raise ValueError("candidate needs choices xor a reuse scheme")

    @property
    def is_reuse(self) -> bool:
        return self.reuse is not None

    def label(self) -> str:
        if self.reuse is not None:
            return self.reuse.label()
        return " | ".join(c.label() for c in self.choices)


@dataclasses.dataclass(frozen=True)
class DesignSpace:
    """The searchable portfolio design space.

    ``chiplet_counts`` containing 1 enables the monolithic-SoC option per
    SKU; counts > 1 combine with every (process, integration) pair.
    ``allow_reuse`` adds SCMS-style candidates whose slice areas are
    derived from the SKU areas (a slice is valid iff every SKU area is an
    in-range integer multiple of it).  ``reuse_within_sku`` gives the
    slices of one non-reuse split a single design name (chiplet NRE paid
    once per SKU); the paper's Fig. 4 no-reuse assumption is
    ``reuse_within_sku=False``.
    """

    skus: Tuple[SKU, ...]
    processes: Tuple[str, ...] = ("7nm",)
    integrations: Tuple[str, ...] = ("MCM",)
    chiplet_counts: Tuple[int, ...] = (1, 2, 3, 4)
    allow_reuse: bool = True
    reuse_package_options: Tuple[bool, ...] = (False,)
    reuse_within_sku: bool = True

    def __post_init__(self):
        if not self.skus:
            raise ValueError("design space needs at least one SKU")
        names = [s.name for s in self.skus]
        if len(set(names)) != len(names):
            raise ValueError("SKU names must be unique")
        if not self.processes:
            raise ValueError("design space needs at least one process node")
        if not self.integrations and max(self.chiplet_counts) > 1:
            raise ValueError(
                "chiplet counts > 1 need at least one integration tech")
        for p in self.processes:
            node(p)
        for t in self.integrations:
            if t == "SoC":
                raise ValueError(
                    "integrations are multi-chip technologies; the SoC "
                    "baseline is the n_chiplets=1 option")
            tech(t)
        if not self.chiplet_counts or min(self.chiplet_counts) < 1:
            raise ValueError("chiplet_counts must be positive")
        io_dies: Dict[str, IODie] = {}
        for s in self.skus:
            if s.io_die is None:
                continue
            node(s.io_die.process)
            if io_dies.setdefault(s.io_die.name, s.io_die) != s.io_die:
                raise ValueError(
                    f"IO die {s.io_die.name!r} is one design: every SKU "
                    "that names it must give the same area and node")

    @property
    def has_io_dies(self) -> bool:
        return any(s.io_die is not None for s in self.skus)

    def d2d_processes(self) -> Tuple[str, ...]:
        """The D2D interface namespace of one candidate: the process menu,
        then the IO dies' nodes that are not on it."""
        extra = [s.io_die.process for s in self.skus
                 if s.io_die is not None
                 and s.io_die.process not in self.processes]
        return tuple(self.processes) + tuple(dict.fromkeys(extra))

    # -- choice inventories (cached: the space is frozen, and the search
    # loop asks for them on every sample/mutate/crossover) -------------------
    @functools.cached_property
    def _arch_choices(self) -> Tuple[ArchChoice, ...]:
        out = []
        if 1 in self.chiplet_counts:
            out += [ArchChoice(1, p, "SoC") for p in self.processes]
        out += [ArchChoice(n, p, t)
                for n in sorted(set(self.chiplet_counts)) if n > 1
                for p in self.processes for t in self.integrations]
        return tuple(out)

    @functools.cached_property
    def _reuse_choices(self) -> Tuple[ReuseChoice, ...]:
        if not self.allow_reuse:
            return ()
        return tuple(ReuseChoice(a, p, t, pkg)
                     for a in self.reuse_slice_areas()
                     for p in self.processes for t in self.integrations
                     for pkg in self.reuse_package_options)

    def arch_choices(self) -> List[ArchChoice]:
        """Per-SKU architecture options (same menu for every SKU)."""
        return list(self._arch_choices)

    def reuse_slice_areas(self) -> List[float]:
        """Slice areas under which every SKU is an in-range integer
        multiple — the valid cross-SKU reuse granularities."""
        counts = sorted(set(self.chiplet_counts))
        cands = sorted({s.module_area_mm2 / n
                        for s in self.skus for n in counts}, reverse=True)
        out: List[float] = []
        for a in cands:
            ok = True
            for s in self.skus:
                k = s.module_area_mm2 / a
                if abs(k - round(k)) > _REL_TOL * max(k, 1.0) \
                        or int(round(k)) not in counts:
                    ok = False
                    break
            if ok and not any(abs(a - b) <= _REL_TOL * a for b in out):
                out.append(a)
        return out

    def reuse_choices(self) -> List[ReuseChoice]:
        return list(self._reuse_choices)

    def reuse_counts(self, r: ReuseChoice) -> Tuple[int, ...]:
        """Per-SKU socket counts under ``r`` — rejects a slice that does
        not implement the SKU inventories (wrong area or out-of-range
        count), so foreign/hand-built reuse candidates cannot be silently
        lowered to the wrong silicon."""
        counts = []
        for s in self.skus:
            k = s.module_area_mm2 / r.slice_area_mm2
            if abs(k - round(k)) > _REL_TOL * max(k, 1.0) \
                    or int(round(k)) not in self.chiplet_counts:
                raise ValueError(
                    f"slice {r.slice_area_mm2:g} mm^2 does not tile SKU "
                    f"{s.name!r} ({s.module_area_mm2:g} mm^2) within the "
                    f"allowed chiplet counts {self.chiplet_counts}")
            counts.append(int(round(k)))
        return tuple(counts)

    # -- countable enumeration ----------------------------------------------
    def size(self) -> int:
        return (len(self._arch_choices) ** len(self.skus)
                + len(self._reuse_choices))

    def candidate_at(self, i: int) -> Candidate:
        """Decode index ``i`` (0 <= i < size()) into a candidate."""
        arch = self._arch_choices
        n_arch = len(arch) ** len(self.skus)
        if i < 0 or i >= self.size():
            raise IndexError(f"candidate index {i} out of range")
        if i < n_arch:
            # match enumerate_candidates(): SKU 0 is the most significant
            # digit of the mixed-radix index
            digits = []
            for _ in self.skus:
                i, d = divmod(i, len(arch))
                digits.append(arch[d])
            return Candidate(choices=tuple(reversed(digits)))
        return Candidate(reuse=self._reuse_choices[i - n_arch])

    def enumerate_candidates(self) -> Iterator[Candidate]:
        for combo in itertools.product(self._arch_choices,
                                       repeat=len(self.skus)):
            yield Candidate(choices=combo)
        for r in self._reuse_choices:
            yield Candidate(reuse=r)

    def sample(self, rng: np.random.Generator, n: int) -> List[Candidate]:
        """Uniform-with-replacement sample of ``n`` candidates."""
        return [self.candidate_at(int(i))
                for i in rng.integers(0, self.size(), size=n)]

    # -- search neighborhood -------------------------------------------------
    def mutate(self, rng: np.random.Generator, cand: Candidate,
               jump_prob: float = 0.15) -> Candidate:
        """A random neighbor: tweak one SKU's choice (or hop between the
        reuse and independent families); occasionally jump anywhere."""
        if rng.random() < jump_prob:
            return self.candidate_at(int(rng.integers(0, self.size())))
        reuse = self._reuse_choices
        if cand.is_reuse:
            if len(reuse) > 1 and rng.random() < 0.7:
                others = [r for r in reuse if r != cand.reuse]
                return Candidate(reuse=others[int(rng.integers(len(others)))])
            return self.candidate_at(
                int(rng.integers(0, len(self._arch_choices)
                                 ** len(self.skus))))
        arch = self._arch_choices
        if reuse and rng.random() < 0.15:
            return Candidate(reuse=reuse[int(rng.integers(len(reuse)))])
        i = int(rng.integers(len(self.skus)))
        others = [a for a in arch if a != cand.choices[i]]
        if not others:
            return cand
        new = list(cand.choices)
        new[i] = others[int(rng.integers(len(others)))]
        return Candidate(choices=tuple(new))

    def crossover(self, rng: np.random.Generator, a: Candidate,
                  b: Candidate) -> Candidate:
        """Per-SKU uniform crossover; reuse candidates fall back to
        mutation (they have no per-SKU genes)."""
        if a.is_reuse or b.is_reuse:
            return self.mutate(rng, a)
        picks = rng.integers(0, 2, size=len(self.skus))
        return Candidate(choices=tuple(
            (a if p == 0 else b).choices[i] for i, p in enumerate(picks)))

    # -- batching bounds -----------------------------------------------------
    def max_chips(self) -> int:
        """Widest system any candidate can produce (padding bound), an IO
        die counted beside the compute chiplets."""
        io = [int(s.io_die is not None) for s in self.skus]
        m = max(self.chiplet_counts)
        split = [n for n in self.chiplet_counts if n > 1]
        if split:
            m = max(m, max(split) + max(io))
        for r in self._reuse_choices:
            m = max(m, max(k + i for k, i in zip(self.reuse_counts(r), io)))
        return m

    # -- index algebra (inverse of candidate_at) ----------------------------
    @functools.cached_property
    def _arch_index(self) -> Dict[ArchChoice, int]:
        return {a: i for i, a in enumerate(self._arch_choices)}

    @functools.cached_property
    def _reuse_index(self) -> Dict[ReuseChoice, int]:
        return {r: i for i, r in enumerate(self._reuse_choices)}

    def index_of(self, cand: Candidate) -> int:
        """The unique index with ``candidate_at(index_of(c)) == c`` — the
        bridge from candidate objects to the array-native fused pipeline."""
        try:
            if cand.reuse is not None:
                return (len(self._arch_choices) ** len(self.skus)
                        + self._reuse_index[cand.reuse])
            if len(cand.choices) != len(self.skus):
                raise KeyError(cand)
            i = 0
            base = len(self._arch_choices)
            for c in cand.choices:       # SKU 0 is the most significant digit
                i = i * base + self._arch_index[c]
            return i
        except KeyError:
            raise ValueError(
                f"candidate {cand.label()} is not a member of this "
                "design space") from None

    def encoder(self) -> "CandidateEncoder":
        """The cached vectorized candidate encoder for this space."""
        return self._encoder

    @functools.cached_property
    def _encoder(self) -> "CandidateEncoder":
        return CandidateEncoder(self)


def candidate_systems(space: DesignSpace, cand: Candidate) -> List[System]:
    """Lower one candidate to its per-SKU :class:`System` group.

    The group is meant to be priced with NRE shared *within* the
    candidate (one ``share_nre`` group): reuse candidates then amortize
    the single chiplet design over the whole portfolio volume.
    """
    if cand.choices and len(cand.choices) != len(space.skus):
        raise ValueError(
            f"candidate has {len(cand.choices)} per-SKU choices but the "
            f"space has {len(space.skus)} SKUs")
    if cand.reuse is not None:
        r = cand.reuse
        counts = space.reuse_counts(r)
        systems = [_with_io_die(sys, sku, r.integration)
                   for sys, sku in zip(portfolio_reuse_systems(
                       r.slice_area_mm2, r.process, r.integration,
                       counts=list(counts),
                       quantities=[s.quantity for s in space.skus],
                       names=[s.name for s in space.skus],
                       package_reuse=r.package_reuse), space.skus)]
        if r.package_reuse:
            # the shared package is sized for the largest system's
            # silicon, IO die included
            area = (max(s.chips[0].area_mm2 * k
                        + sum(c.area_mm2 for c in s.chips[k:])
                        for s, k in zip(systems, counts))
                    * tech(r.integration).package_area_factor)
            systems = [dataclasses.replace(s, package_area_mm2=area)
                       for s in systems]
        return systems
    out = []
    for sku, c in zip(space.skus, cand.choices):
        if c.n_chiplets == 1:
            # the monolithic SoC holds the IO content on the compute die
            io = sku.io_die.area_mm2 if sku.io_die is not None else 0.0
            out.append(spec({"kind": "soc", "name": sku.name,
                             "area": sku.module_area_mm2 + io,
                             "process": c.process,
                             "quantity": sku.quantity}))
        else:
            out.append(_with_io_die(spec({
                "kind": "split", "name": sku.name,
                "area": sku.module_area_mm2, "process": c.process,
                "n": c.n_chiplets, "integration": c.integration,
                "quantity": sku.quantity,
                "reuse_chiplet": space.reuse_within_sku}), sku,
                c.integration))
    return out


def _io_chip(io: IODie, integration: str) -> Chip:
    """The IO die as a chip under ``integration`` (its D2D module on its
    own node).  One design per (IO die, integration)."""
    name = f"{io.name}_{integration}"
    return make_chip(name, [Module(f"{name}_modules", io.area_mm2,
                                   io.process)], io.process,
                     integration=integration)


def _with_io_die(system: System, sku: SKU, integration: str) -> System:
    """``system`` with ``sku``'s IO die after its compute chiplets."""
    if sku.io_die is None:
        return system
    return dataclasses.replace(
        system, chips=system.chips + (_io_chip(sku.io_die, integration),))


# ---------------------------------------------------------------------------
# Vectorized candidate encoder — the on-device half of candidate_systems.
# ---------------------------------------------------------------------------

# Per-(SKU, extended choice) float tables the encoder gathers from.  Every
# value is read off the *actual* System objects candidate_systems builds
# (same float64 -> float32 cast as SystemBatch.from_systems), so the
# encoded batch is bit-identical to the host-packed one.
_CHIP_TABLE_FIELDS = (
    # chip slots
    "chip_area", "mod_area", "chip_defect", "wafer_cost",
    "cluster", "wafer_yield", "sort_cost", "bump_cost",
    # chip/module NRE coefficients
    "nre_chip_k", "nre_chip_fixed", "nre_mod_k",
    # D2D interface
    "has_d2d", "d2d_pidx",
)
_CHOICE_TABLE_FIELDS = ("n_chips",) + _CHIP_TABLE_FIELDS + (
    # per-system / package
    "package_area", "package_area_factor", "substrate_cost",
    "substrate_layer", "interposer_cost", "interposer_defect",
    "interposer_area_factor", "interposer_cluster", "y2_chip_bond",
    "y3_substrate_bond", "assembly_yield", "bond_cost_per_chip",
    "pkg_k", "pkg_fixed",
)
# The IO die's columns, in spaces whose SKUs carry one: whether the system
# holds it (in slot n, after its n compute dies), the id of its design
# (equal ids are one design; -1 where there is none) and its chip fields.
_IO_TABLE_FIELDS = ("io_chips", "io_design") + tuple(
    "io_" + f for f in _CHIP_TABLE_FIELDS)


@dataclasses.dataclass(frozen=True)
class EncoderMeta:
    """Static (hashable) geometry of a space's encoder — the part of the
    encoding that participates in jit cache keys."""

    n_skus: int
    max_chips: int
    n_arch_choices: int      # A: per-SKU architecture menu size
    n_reuse_choices: int     # R: cross-SKU reuse candidates
    n_processes: int         # P: D2D entity namespace width per candidate
    n_arch: int              # A ** n_skus (first reuse index)
    size: int                # total candidate count
    reuse_within_sku: bool
    # Systems may hold an IO die in slot n.  Where none can, the programs
    # leave the IO terms out: they add only zeros there, yet took an
    # IO-less chunk program to 1.54x its device time on a TPU v5e.
    io_dies: bool = False


class CandidateEncoder:
    """Pure-array lowering of candidate *indices* to a :class:`SystemBatch`.

    Construction walks every (SKU, architecture choice) and every reuse
    choice ONCE through :func:`candidate_systems` (the parity oracle) and
    records the resulting per-system / per-chip floats in dense
    ``(S, A + R)`` tables.  :meth:`encode` is then pure ``jnp``: decoding
    a ``(K,)`` index vector into a padded, NRE-grouped ``(K * S)``-system
    batch is all gathers and broadcasts, traceable inside an outer jit —
    zero per-candidate Python, which is what moves the DSE inner loop
    on-device (see :mod:`repro.dse.evaluate` / ``search``).

    Every system is ``n`` copies of one compute die, plus at most one IO
    die in slot ``n``: the compute die has one set of columns and, in a
    space whose SKUs carry IO dies, the IO die a second (``io_*``).

    The NRE entity layout is canonical rather than discovery-ordered:
    candidate ``j`` owns chip/module entity rows ``1 + j*S*C .. ``,
    package rows ``1 + j*S ..`` and D2D rows ``1 + j*P ..`` (row 0 of
    every table is a shared zero-NRE sink for padded slots).  An IO die's
    chip/module row is its own slot in the first system of the candidate
    that holds the same IO design.  Shapes match
    :func:`repro.dse.evaluate.chunk_shape` exactly, so encoded and
    host-packed chunks share one compiled engine trace.
    """

    def __init__(self, space: DesignSpace):
        if space.size() > np.iinfo(np.int32).max:
            raise ValueError(
                f"space has {space.size()} candidates; the int32 index "
                "encoding supports at most 2**31 - 1")
        with _TRACER.span("encoder_build"):
            self._build(space)

    def _build(self, space: DesignSpace):
        self.space = space
        s, c = len(space.skus), space.max_chips()
        a, r = len(space._arch_choices), len(space._reuse_choices)
        self._d2d = space.d2d_processes()
        self.meta = EncoderMeta(
            n_skus=s, max_chips=c, n_arch_choices=a, n_reuse_choices=r,
            n_processes=len(self._d2d), n_arch=a ** s, size=space.size(),
            reuse_within_sku=space.reuse_within_sku,
            io_dies=space.has_io_dies)

        fields = _CHOICE_TABLE_FIELDS + (_IO_TABLE_FIELDS if
                                         space.has_io_dies else ())
        tab = {f: np.zeros((s, a + r), np.float32) for f in fields}
        if space.has_io_dies:
            tab["io_design"][:] = -1.0
        self._io_designs: Dict[str, int] = {}
        pkg_shared = np.zeros((a + r,), np.float32)
        for e in range(a + r):
            if e < a:
                cand = Candidate(choices=(space._arch_choices[e],) * s)
            else:
                ch = space._reuse_choices[e - a]
                pkg_shared[e] = 1.0 if ch.package_reuse else 0.0
                cand = Candidate(reuse=ch)
            for i, sys in enumerate(candidate_systems(space, cand)):
                self._fill(tab, i, e, sys)
        # chips of each candidate on the host, for admission counts: the
        # sums over the high and the low half of the SKU digits, and over
        # each reuse scheme's systems
        chips = (tab["n_chips"] + tab.get("io_chips", 0.0)).astype(np.int64)
        h = s // 2
        self._lo_base = a ** h
        self._hi_chips = _digit_sums(chips[:s - h, :a])
        self._lo_chips = _digit_sums(chips[s - h:, :a])
        self._reuse_chips = chips[:, a:].sum(0)
        self.tables: Dict[str, jnp.ndarray] = {
            k: jnp.asarray(v) for k, v in tab.items()}
        self.tables["pkg_shared"] = jnp.asarray(pkg_shared)
        # static per-node D2D NRE menu (row values are candidate-free)
        self.tables["d2d_nre"] = jnp.asarray(
            [node(p_).nre_d2d for p_ in self._d2d], jnp.float32)
        self.tables["quantity"] = jnp.asarray(
            [sk.quantity for sk in space.skus], jnp.float32)
        # mixed-radix digit extractors, SKU 0 most significant
        self.tables["digit_pow"] = jnp.asarray(
            [a ** (s - 1 - i) for i in range(s)], jnp.int32)

    def _chip_values(self, chip: Chip) -> Dict[str, float]:
        nd = chip.node
        d2d = any(m.is_d2d for m in chip.modules)
        return {
            "chip_area": chip.area_mm2, "mod_area": chip.module_area_mm2,
            "chip_defect": chip.defect_density,
            "wafer_cost": nd.wafer_cost, "cluster": nd.cluster_param,
            "wafer_yield": nd.wafer_yield, "sort_cost": nd.wafer_sort_cost,
            "bump_cost": nd.bump_cost_per_mm2,
            "nre_chip_k": nd.nre_chip_per_mm2,
            "nre_chip_fixed": nd.nre_fixed_per_chip,
            "nre_mod_k": nd.nre_module_per_mm2,
            "has_d2d": 1.0 if d2d else 0.0,
            "d2d_pidx": self._d2d.index(chip.process) if d2d else 0,
        }

    def _fill(self, tab, i: int, e: int, sys: System):
        chips, io = sys.chips, None
        if self.space.skus[i].io_die is not None and sys.n_chips > 1:
            chips, io = chips[:-1], chips[-1]
        chip = chips[0]
        for other in chips[1:]:     # even slices / reuse copies only
            if (other.area_mm2 != chip.area_mm2
                    or other.process != chip.process):
                raise ValueError(
                    "the encoder takes n copies of one compute die plus at "
                    f"most one IO die per system; {sys.name} holds compute "
                    "dies of different areas or nodes, and a system of "
                    "general mixed dies cannot be encoded")
        t = sys.tech
        v = {
            "n_chips": len(chips), **self._chip_values(chip),
            "package_area": sys.package_area,
            "package_area_factor": t.package_area_factor,
            "substrate_cost": t.substrate_cost_per_mm2,
            "substrate_layer": t.substrate_layer_factor,
            "interposer_cost": t.interposer_cost_per_mm2,
            "interposer_defect": t.interposer_defect_density,
            "interposer_area_factor": t.interposer_area_factor,
            "interposer_cluster": node(t.interposer_node).cluster_param,
            "y2_chip_bond": t.y2_chip_bond,
            "y3_substrate_bond": t.y3_substrate_bond,
            "assembly_yield": t.assembly_yield,
            "bond_cost_per_chip": t.bond_cost_per_chip,
            "pkg_k": t.nre_package_per_mm2,
            "pkg_fixed": t.nre_fixed_per_package,
        }
        if io is not None:
            v["io_chips"] = 1.0
            v["io_design"] = self._io_designs.setdefault(
                io.name, len(self._io_designs))
            v.update({"io_" + k: x for k, x in
                      self._chip_values(io).items()})
        for k, val in v.items():
            tab[k][i, e] = val

    def encode(self, idx) -> SystemBatch:
        """Lower a ``(K,)`` int vector of candidate indices to a padded
        ``SystemBatch`` (one NRE group per candidate) — pure jnp."""
        return encode_arrays(self.tables, self.meta, idx)

    def chip_slots(self, idx) -> Tuple[int, int]:
        """``(chips, slots)`` of candidates ``idx``: the real chips of
        their systems and the chip slots the chunk programs give them
        (``S * max_chips`` each), on the host, with no device work."""
        m = self.meta
        idx = np.asarray(idx, np.int64)
        slots = int(idx.size) * m.n_skus * m.max_chips
        reuse = idx >= m.n_arch
        chips = 0
        if reuse.any():
            chips += int(self._reuse_chips[idx[reuse] - m.n_arch].sum())
            idx = idx[~reuse]
        hi, lo = np.divmod(idx, self._lo_base)
        chips += int(self._hi_chips[hi].sum() + self._lo_chips[lo].sum())
        return chips, slots


def _digit_sums(rows: np.ndarray) -> np.ndarray:
    """Sum of ``rows[i, digit_i]`` for every mixed-radix number of
    ``len(rows)`` digits (row 0 most significant), in index order."""
    out = np.zeros((1,), np.int64)
    for r in rows:
        out = (out[:, None] + r[None, :]).reshape(-1)
    return out


def _decode(tables: Dict[str, jnp.ndarray], meta: EncoderMeta, idx):
    """Shared index decode: (K,) indices -> (is_reuse (K,), ext (K, S))
    where ``ext`` is each SKU's extended-choice column (arch digit, or
    ``A + r`` for reuse candidates)."""
    a = meta.n_arch_choices
    idx = jnp.asarray(idx, jnp.int32)
    is_reuse = idx >= meta.n_arch                                    # (K,)
    arch_i = jnp.where(is_reuse, 0, idx)
    digits = (arch_i[:, None] // tables["digit_pow"][None, :]) % a   # (K,S)
    r = jnp.where(is_reuse, idx - meta.n_arch, 0)
    ext = jnp.where(is_reuse[:, None], a + r[:, None], digits)       # (K,S)
    return is_reuse, ext


def _io_shared(tables: Dict[str, jnp.ndarray], ext):
    """(K, S, S) bool: system ``j`` of a candidate holds the IO design of
    system ``i`` (``[k, i, j]``) — the IO die's sharing set, an equality
    reduce over the candidate's S systems."""
    srange = jnp.arange(ext.shape[1], dtype=jnp.int32)
    d = tables["io_design"][srange[None, :], ext]
    held = tables["io_chips"][srange[None, :], ext] > 0.0
    return (d[:, :, None] == d[:, None, :]) & held[:, None, :]


def encode_arrays(tables: Dict[str, jnp.ndarray], meta: EncoderMeta,
                  idx) -> SystemBatch:
    """Pure-array candidate decode (traceable; see :class:`CandidateEncoder`).

    ``tables`` may be traced or concrete; ``meta`` is static.  Out-of-range
    indices are undefined behavior (clipped gathers), mirroring
    ``candidate_at``'s host-side range check which callers enforce.
    """
    s, c, p = meta.n_skus, meta.max_chips, meta.n_processes
    is_reuse, ext = _decode(tables, meta, idx)
    k = ext.shape[0]
    n = k * s

    srange = jnp.arange(s, dtype=jnp.int32)

    def g(name):
        """(K, S) per-system gather, flattened to (N,)."""
        return tables[name][srange[None, :], ext].reshape(n)

    n_chips = g("n_chips")                          # compute dies
    mask = (jnp.arange(c, dtype=jnp.float32)[None, :]
            < n_chips[:, None]).astype(jnp.float32)                  # (N,C)
    io_slot = None
    if meta.io_dies:
        comp_mask = mask
        io_slot = ((jnp.arange(c, dtype=jnp.float32)[None, :]
                    == n_chips[:, None])
                   & (g("io_chips")[:, None] > 0.0))                 # (N,C)
        mask = comp_mask + io_slot.astype(jnp.float32)

    def chip(name, pad=0.0):
        if io_slot is None:
            val = g(name)[:, None] * mask
        else:
            val = jnp.where(io_slot, g("io_" + name)[:, None],
                            g(name)[:, None] * comp_mask)
        return val if pad == 0.0 else val + pad * (1.0 - mask)

    # -- canonical NRE entity layout (see class docstring) -----------------
    sys_i = jnp.arange(n, dtype=jnp.int32)
    cand_of_sys = sys_i // s
    is_reuse_sys = jnp.repeat(is_reuse, s)
    slot = jnp.arange(c, dtype=jnp.int32)[None, :]
    own_row = 1 + (sys_i * c)[:, None] + slot                        # (N,C)
    sku_row = 1 + (sys_i * c)[:, None] + 0 * slot
    cand_row = 1 + (cand_of_sys * (s * c))[:, None] + 0 * slot
    arch_row = sku_row if meta.reuse_within_sku else own_row
    rows = jnp.where(is_reuse_sys[:, None], cand_row, arch_row)
    if io_slot is not None:
        # the IO die's own slot in the first system holding its design
        first = jnp.argmax(_io_shared(tables, ext), axis=-1)         # (K,S)
        n_first = jnp.take_along_axis(
            n_chips.reshape(k, s).astype(jnp.int32), first, axis=1)
        io_row = 1 + ((jnp.arange(k, dtype=jnp.int32)[:, None] * s
                       + first) * c + n_first).reshape(n)
        rows = jnp.where(io_slot, io_row[:, None], rows)
    chip_ids = jnp.where(mask > 0.0, rows, 0).astype(jnp.int32)

    def ent(values_2d):
        """Prefix a zero sink row and flatten (N, C) slot values."""
        return jnp.concatenate(
            [jnp.zeros((1,), jnp.float32), values_2d.reshape(-1)])

    pkg_shared = (tables["pkg_shared"][ext[:, 0]] > 0.0)             # (K,)
    pkg_shared_sys = jnp.repeat(pkg_shared, s)
    pkg_ids = jnp.where(pkg_shared_sys, 1 + cand_of_sys * s,
                        1 + sys_i).astype(jnp.int32)

    inst_sys = jnp.repeat(sys_i, c)                                  # (N*C,)
    if io_slot is None:
        has_d2d = (g("has_d2d")[:, None] * mask) > 0.0
        d2d_row = (1 + (cand_of_sys * p)[:, None]
                   + g("d2d_pidx").astype(jnp.int32)[:, None] + 0 * slot)
    else:
        has_d2d = chip("has_d2d") > 0.0
        d2d_row = (1 + (cand_of_sys * p)[:, None]
                   + chip("d2d_pidx").astype(jnp.int32))
    d2d_ids = jnp.where(has_d2d, d2d_row, 0).astype(jnp.int32)

    quantity = jnp.tile(tables["quantity"], k)
    zero1 = jnp.zeros((1,), jnp.float32)
    return SystemBatch.from_arrays(
        chip_area=chip("chip_area"),
        chip_defect=chip("chip_defect"),
        chip_wafer_cost=chip("wafer_cost"),
        chip_cluster=chip("cluster", pad=1.0),
        chip_wafer_yield=chip("wafer_yield", pad=1.0),
        chip_sort_cost=chip("sort_cost"),
        chip_bump_cost=chip("bump_cost"),
        chip_mask=mask,
        package_area=g("package_area"),
        package_area_factor=g("package_area_factor"),
        substrate_cost=g("substrate_cost"),
        substrate_layer=g("substrate_layer"),
        interposer_cost=g("interposer_cost"),
        interposer_defect=g("interposer_defect"),
        interposer_area_factor=g("interposer_area_factor"),
        interposer_cluster=g("interposer_cluster"),
        y2_chip_bond=g("y2_chip_bond"),
        y3_substrate_bond=g("y3_substrate_bond"),
        assembly_yield=g("assembly_yield"),
        bond_cost_per_chip=g("bond_cost_per_chip"),
        quantity=quantity,
        chip_entity_id=chip_ids,
        chip_entity_area=ent(chip("chip_area")),
        chip_entity_k=ent(chip("nre_chip_k")),
        chip_entity_fixed=ent(chip("nre_chip_fixed")),
        pkg_entity_id=pkg_ids,
        pkg_entity_area=jnp.concatenate([zero1, g("package_area")]),
        pkg_entity_k=jnp.concatenate([zero1, g("pkg_k")]),
        pkg_entity_fixed=jnp.concatenate([zero1, g("pkg_fixed")]),
        mod_sys=inst_sys,
        mod_entity=chip_ids.reshape(-1),
        mod_entity_area=ent(chip("mod_area")),
        mod_entity_k=ent(chip("nre_mod_k")),
        d2d_sys=inst_sys,
        d2d_entity=d2d_ids.reshape(-1),
        d2d_entity_nre=jnp.concatenate([zero1,
                                        jnp.tile(tables["d2d_nre"], k)]),
    )


def encode_batch(space: DesignSpace, idx) -> SystemBatch:
    """Vectorized ``candidate_at`` + ``candidate_systems`` + packing: turn
    a ``(K,)`` vector of candidate indices into the padded, NRE-grouped
    :class:`SystemBatch` the engine prices — entirely in array ops, so it
    composes with an outer ``jax.jit`` (the fused DSE pipeline)."""
    return space.encoder().encode(idx)


def encoded_nre(tables: Dict[str, jnp.ndarray], meta: EncoderMeta,
                idx) -> NREBreakdown:
    """Closed-form per-unit NRE for encoder-canonical candidate batches.

    The generic engine amortizes design entities with ``segment_sum``
    scatters — correct for arbitrary batches, but scatter-adds serialize
    on CPU and dominate the sweep wall-clock.  The encoder's canonical
    layout makes every Eq. (6)-(8) denominator *closed-form*:

    * within-SKU sharing: the SKU's ``n`` chips (and module instances)
      share one design over ``q * n`` uses -> per-unit ``NRE_e / q``
      (``reuse_within_sku=False``: ``n`` distinct designs, ``n*NRE_e/q``);
    * cross-SKU reuse: one design over ``sum_s q_s * n_s`` uses;
    * packages: own design over ``q`` (shared: over ``sum_s q_s``);
    * D2D: one interface per (candidate, node) over the
      ``q_s * n_s`` of the SKUs that use it (a one-hot reduce over the
      P-wide node namespace, not a scatter), an IO die's one use counted
      at its own node;
    * IO dies: one design per (IO die, integration) over the ``q_s`` of
      the candidate's SKUs that hold it (an equality reduce over the S
      systems, :func:`_io_shared`).

    Returns the engine's :class:`~repro.core.engine.NREBreakdown` with
    ``(K * S,)`` fields, matching ``CostEngine.nre`` on the same encoded
    batch to float32 rounding (pinned <= 1e-6 relative by
    ``tests/test_fused.py``) — the fused pipeline's NRE stage.
    """
    s, p = meta.n_skus, meta.n_processes
    eps = jnp.float32(1e-30)
    is_reuse, ext = _decode(tables, meta, idx)
    k = ext.shape[0]
    srange = jnp.arange(s, dtype=jnp.int32)

    def g(name):                                     # (K, S) gathers
        return tables[name][srange[None, :], ext]

    q = jnp.broadcast_to(tables["quantity"][None, :], (k, s))
    n = g("n_chips")
    reuse_col = is_reuse[:, None]

    # chip + module designs (Eq. 7/8)
    chip_nre = g("nre_chip_k") * g("chip_area") + g("nre_chip_fixed")
    mod_nre = g("nre_mod_k") * g("mod_area")
    denom_c = jnp.maximum((q * n).sum(-1, keepdims=True), eps)
    mult = 1.0 if meta.reuse_within_sku else n
    chips = jnp.where(reuse_col, n * chip_nre / denom_c,
                      mult * chip_nre / jnp.maximum(q, eps))
    modules = jnp.where(reuse_col, n * mod_nre / denom_c,
                        mult * mod_nre / jnp.maximum(q, eps))

    # package designs: own per system unless the reuse scheme shares one
    pkg_nre = g("pkg_k") * g("package_area") + g("pkg_fixed")
    shared = tables["pkg_shared"][ext] > 0.0
    denom_p = jnp.maximum(q.sum(-1, keepdims=True), eps)
    packages = jnp.where(shared, pkg_nre / denom_p,
                         pkg_nre / jnp.maximum(q, eps))

    # D2D interfaces: one per (candidate, node) across the candidate
    has = g("has_d2d")
    pidx = g("d2d_pidx").astype(jnp.int32)
    w = has * q * n                                          # (K, S) uses
    onehot = (pidx[:, :, None]
              == jnp.arange(p, dtype=jnp.int32)[None, None, :])
    denom_d = (w[:, :, None] * onehot).sum(1)                # (K, P)
    if meta.io_dies:
        io_has = g("io_chips")
        io_d2d = g("io_has_d2d") * io_has
        io_p = g("io_d2d_pidx").astype(jnp.int32)
        io_onehot = (io_p[:, :, None]
                     == jnp.arange(p, dtype=jnp.int32)[None, None, :])
        denom_d = denom_d + ((io_d2d * q)[:, :, None] * io_onehot).sum(1)
    den_sys = jnp.take_along_axis(denom_d, pidx, axis=1)     # (K, S)
    d2d = has * n * tables["d2d_nre"][pidx] / jnp.maximum(den_sys, eps)

    if meta.io_dies:
        # IO die designs over the SKUs of the candidate that hold them
        denom_io = jnp.maximum(
            (_io_shared(tables, ext) * q[:, None, :]).sum(-1), eps)
        chips = chips + io_has * (
            g("io_nre_chip_k") * g("io_chip_area")
            + g("io_nre_chip_fixed")) / denom_io
        modules = modules + io_has * (
            g("io_nre_mod_k") * g("io_mod_area")) / denom_io
        d2d = d2d + io_d2d * tables["d2d_nre"][io_p] / jnp.maximum(
            jnp.take_along_axis(denom_d, io_p, axis=1), eps)

    flat = k * s
    return NREBreakdown(modules=modules.reshape(flat),
                        chips=chips.reshape(flat),
                        packages=packages.reshape(flat),
                        d2d=d2d.reshape(flat))
