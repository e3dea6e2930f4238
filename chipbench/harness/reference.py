"""Plain reference of the Chiplet Actuary cost model (arXiv:2203.12268).

The benchmark's yardstick for ``correct``: a straightforward, per-system
implementation of the paper's equations that imports nothing of the
program under test.  Its parameters are its own copy
(``technology.json`` beside this file), and it decodes candidate indices
itself, so a served row is compared against what that index means.

* Eq. (1): negative-binomial die yield; Eq. (2): dies per wafer with
  edge loss and scribe lanes, sort and bump folded into the raw die.
* Eq. (4), chip-last flow: interposer, substrate and bond costs, the
  package defects and the known-good dies that packaging destroys.
* Eqs. (6)-(8): NRE of every design entity (module, chip, package, D2D
  interface per node) amortized over the units that use it in a group.
* Sec. 5: a design space's candidates are per-SKU architectures (mixed
  radix, SKU 0 most significant) followed by one-chiplet reuse schemes.
* Monte-Carlo risk: lognormal multipliers on defect density, wafer
  price, bond failure rates and interposer defects, one scenario per
  draw shared by every candidate, drawn from JAX's PRNG key of the
  request's seed (``split(key, draws)``, then ``split(k, 5)`` per draw).

``Reference(space)`` computes in float64 with NumPy.  ``xp``/``dtype``
let the same arithmetic run in a lower precision (``jax.numpy`` in
bfloat16) for the control that the comparison has to reject.
"""
from __future__ import annotations

import json
import pathlib
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

TECH = json.loads(
    (pathlib.Path(__file__).with_name("technology.json")).read_text())
_REL_TOL = 1e-6
D2D = "__d2d__"


# ---------------------------------------------------------------------------
# Systems as plain dicts
# ---------------------------------------------------------------------------

def _chip(name: str, modules: List[Tuple[str, float, str]], process: str,
          integration: str, early: bool = False,
          d2d_overhead: Optional[float] = None) -> Dict:
    """A die; multi-chip integrations add a D2D module that takes
    ``overhead`` of the final die area (Sec. 3.2)."""
    ovh = (TECH["integrations"][integration]["d2d_area_overhead"]
           if d2d_overhead is None else d2d_overhead)
    mods = [(n, float(a), p, False) for n, a, p in modules]
    if ovh > 0.0:
        func = sum(m[1] for m in mods)
        mods.append((D2D + process, func * ovh / (1.0 - ovh), process, True))
    return {"name": name, "process": process, "early": bool(early),
            "modules": mods, "area": sum(m[1] for m in mods)}


def _system(name: str, chips: List[Dict], integration: str, quantity: float,
            package_name: Optional[str] = None,
            package_area: Optional[float] = None) -> Dict:
    silicon = sum(c["area"] for c in chips)
    factor = TECH["integrations"][integration]["package_area_factor"]
    return {"name": name, "chips": chips, "integration": integration,
            "quantity": float(quantity),
            "package_id": package_name or f"pkg:{name}",
            "package_area": (float(package_area) if package_area is not None
                             else silicon * factor)}


def spec_system(d: Dict) -> Dict:
    """A system from a raw ``spec()`` dict (kinds soc, split, chips)."""
    d = dict(d)
    kind = d.pop("kind", None) or ("chips" if "chips" in d else "split"
                                   if {"n", "fractions", "processes"} & set(d)
                                   else "soc")
    name = d.get("name", "sys")
    qty = float(d.get("quantity", 1.0))
    early = bool(d.get("early", d.get("early_defects", False)))
    area = d.get("area", d.get("area_mm2", d.get("module_area_mm2")))
    if kind == "soc":
        chip = _chip(f"{name}_die", [(f"{name}_modules", area, d["process"])],
                     d["process"], "SoC", early)
        return _system(name, [chip], "SoC", qty)
    if kind == "split":
        integ = d["integration"]
        fr, procs = d.get("fractions"), d.get("processes")
        n = int(d.get("n", d.get("n_chiplets", len(fr) if fr is not None
                                 else len(procs) if procs else 0)))
        fr = [1.0 / n] * n if fr is None else [f / sum(fr) for f in fr]
        procs = procs or [d["process"]] * n
        reuse = bool(d.get("reuse_chiplet", False))
        chips = []
        for i, (f, p) in enumerate(zip(fr, procs)):
            cname = f"{name}_slice" if reuse else f"{name}_slice{i}"
            chips.append(_chip(cname, [(f"{cname}_modules", area * f, p)], p,
                               integ, early, d.get("d2d_overhead")))
        return _system(name, chips, integ, qty)
    if kind == "chips":
        integ = d["integration"]
        chips = []
        for i, c in enumerate(d["chips"]):
            cname = c.get("name", f"{name}_chip{i}")
            carea = c.get("area", c.get("area_mm2", c.get("module_area_mm2")))
            chips.append(_chip(cname, [(f"{cname}_modules", carea,
                                        c["process"])], c["process"], integ,
                               c.get("early", c.get("early_defects", early)),
                               c.get("d2d_overhead")))
        return _system(name, chips, integ, qty, d.get("package_name"),
                       d.get("package_area", d.get("package_area_mm2")))
    raise ValueError(f"unknown spec kind {kind!r}")


# ---------------------------------------------------------------------------
# The design space's candidate order
# ---------------------------------------------------------------------------

class Space:
    """Candidate decoding of a design space given as the configuration's
    ``space`` block (SKUs, menus, reuse flags)."""

    def __init__(self, cfg: Dict):
        self.skus = [(s["name"], float(s["area"]), float(s["quantity"]))
                     for s in cfg["skus"]]
        self.processes = list(cfg["processes"])
        self.integrations = list(cfg["integrations"])
        self.counts = sorted(set(cfg["chiplet_counts"]))
        self.within_sku = bool(cfg.get("reuse_within_sku", True))
        self.arch = ([(1, p, "SoC") for p in self.processes]
                     if 1 in self.counts else [])
        self.arch += [(n, p, t) for n in self.counts if n > 1
                      for p in self.processes for t in self.integrations]
        self.reuse = []
        if cfg.get("allow_reuse", True):
            self.reuse = [(a, p, t, bool(pkg)) for a in self._slices()
                          for p in self.processes for t in self.integrations
                          for pkg in cfg.get("reuse_package_options",
                                             [False])]
        self.n_arch = len(self.arch) ** len(self.skus)
        self.size = self.n_arch + len(self.reuse)

    def _tiles(self, area: float, a: float) -> Optional[int]:
        k = area / a
        if abs(k - round(k)) > _REL_TOL * max(k, 1.0) \
                or int(round(k)) not in self.counts:
            return None
        return int(round(k))

    def _slices(self) -> List[float]:
        out: List[float] = []
        for a in sorted({s[1] / n for s in self.skus for n in self.counts},
                        reverse=True):
            if all(self._tiles(s[1], a) for s in self.skus) \
                    and not any(abs(a - b) <= _REL_TOL * a for b in out):
                out.append(a)
        return out

    def candidate(self, i: int):
        """``("arch", ((n, process, integration), ...))`` or
        ``("reuse", (slice_mm2, process, integration, package_reuse))``."""
        if not 0 <= i < self.size:
            raise IndexError(i)
        if i >= self.n_arch:
            return ("reuse", self.reuse[i - self.n_arch])
        digits = []
        for _ in self.skus:
            i, d = divmod(i, len(self.arch))
            digits.append(self.arch[d])
        return ("arch", tuple(reversed(digits)))

    def index(self, cand) -> Optional[int]:
        """Index of a candidate, or None where it is not in the space."""
        kind, body = cand
        if kind == "reuse":
            return (self.n_arch + self.reuse.index(body)
                    if body in self.reuse else None)
        i = 0
        for c in body:
            if c not in self.arch:
                return None
            i = i * len(self.arch) + self.arch.index(c)
        return i

    def systems(self, cand) -> List[Dict]:
        kind, body = cand
        if kind == "reuse":
            a, p, t, pkg = body
            counts = [self._tiles(s[1], a) for s in self.skus]
            cname = f"reuse_{p}_{t}_{a:g}mm2"
            chip = _chip(cname, [(f"{cname}_modules", a, p)], p, t)
            pname = parea = None
            if pkg:
                pname = f"{cname}_pkg{max(counts)}s"
                parea = (chip["area"] * max(counts)
                         * TECH["integrations"][t]["package_area_factor"])
            return [_system(nm, [chip] * k, t, q, pname, parea)
                    for (nm, _, q), k in zip(self.skus, counts)]
        out = []
        for (nm, area, q), (n, p, t) in zip(self.skus, body):
            if n == 1:
                out.append(spec_system({"kind": "soc", "name": nm,
                                        "area": area, "process": p,
                                        "quantity": q}))
            else:
                out.append(spec_system({"kind": "split", "name": nm,
                                        "area": area, "process": p, "n": n,
                                        "integration": t, "quantity": q,
                                        "reuse_chiplet": self.within_sku}))
        return out

    @staticmethod
    def swap(cand, process: str, integration: str):
        """The what-if grid's move: same architecture, another node and
        packaging (a monolithic SKU stays SoC)."""
        kind, body = cand
        if kind == "reuse":
            return ("reuse", (body[0], process, integration, body[3]))
        return ("arch", tuple((n, process, "SoC" if n == 1 else integration)
                              for n, _, _ in body))


# ---------------------------------------------------------------------------
# Costs
# ---------------------------------------------------------------------------

class Reference:
    """Per-unit RE + amortized NRE of system groups, in one precision."""

    def __init__(self, space_cfg: Optional[Dict] = None, xp=np,
                 dtype=np.float64):
        self.space = Space(space_cfg) if space_cfg is not None else None
        self.xp, self.dt = xp, dtype
        self._cand: Dict[int, Dict] = {}
        self._mult: Dict[Tuple, Dict] = {}

    def c(self, v):
        return self.xp.asarray(v, self.dt)

    # -- Eqs. (1)-(2) --------------------------------------------------------
    def _nb_yield(self, area, d0, cluster):
        return (1.0 + d0 * (area / 100.0) / cluster) ** (-cluster)

    def _dies_per_wafer(self, area):
        xp, w = self.xp, TECH["wafer"]
        d = self.c(w["diameter_mm"] - 2.0 * w["edge_exclusion_mm"])
        s = (xp.sqrt(area) + w["scribe_mm"]) ** 2
        dpw = xp.pi * (d / 2.0) ** 2 / s - xp.pi * d / xp.sqrt(2.0 * s)
        return xp.maximum(dpw, 1.0)

    def _kgd(self, chip: Dict, m: Dict):
        n = TECH["processes"][chip["process"]]
        area = self.c(chip["area"])
        dpw = self._dies_per_wafer(area)
        raw = (self.c(n["wafer_cost"]) * m["wafer"] / dpw
               + self.c(n["wafer_sort_cost"]) / dpw
               + self.c(n["bump_cost_per_mm2"]) * area)
        d0 = n["defect_density_early" if chip["early"] else "defect_density"]
        y = self._nb_yield(area, self.c(d0) * m["defect"],
                           self.c(n["cluster_param"])) \
            * self.c(n["wafer_yield"])
        return raw / y

    # -- Eq. (4), chip-last ----------------------------------------------------
    def re(self, system: Dict, m: Optional[Dict] = None):
        xp = self.xp
        m = m or {k: 1.0 for k in ("defect", "wafer", "bond", "substrate",
                                   "interposer")}
        t = TECH["integrations"][system["integration"]]
        n_chips = len(system["chips"])
        kgd = sum(self._kgd(c, m) for c in system["chips"])
        parea = self.c(system["package_area"])
        c_int, y1 = self.c(0.0), self.c(1.0)
        if t["interposer_area_factor"] > 0.0:
            area = (parea / self.c(t["package_area_factor"])
                    * self.c(t["interposer_area_factor"]))
            c_int = area * self.c(t["interposer_cost_per_mm2"])
            cl = TECH["processes"][t["interposer_node"]]["cluster_param"]
            y1 = self._nb_yield(area, self.c(t["interposer_defect_density"])
                                * m["interposer"], self.c(cl))
        c_sub = (parea * self.c(t["substrate_cost_per_mm2"])
                 * self.c(t["substrate_layer_factor"]))
        c_bond = self.c(t["bond_cost_per_chip"]) * n_chips
        y2 = xp.clip(1.0 - (1.0 - self.c(t["y2_chip_bond"])) * m["bond"],
                     1e-3, 1.0)
        y3s = xp.clip(1.0 - (1.0 - self.c(t["y3_substrate_bond"]))
                      * m["substrate"], 1e-3, 1.0)
        y2n = y2 ** n_chips
        y3 = y3s * self.c(t["assembly_yield"])
        pkg = c_int + c_sub + c_bond
        pkg_defects = (c_int * (1.0 / (y1 * y2n * y3) - 1.0)
                       + (c_sub + c_bond) * (1.0 / y3 - 1.0))
        wasted = kgd * (1.0 / (y2n * y3) - 1.0)
        return kgd + pkg + pkg_defects + wasted

    # -- Eqs. (6)-(8) ----------------------------------------------------------
    def nre(self, systems: Sequence[Dict]) -> List:
        """Per-unit amortized NRE of every system of one group."""
        cost: Dict[Tuple[str, str], object] = {}
        uses: Dict[Tuple[str, str], float] = {}
        per_sys = []
        for s in systems:
            t = TECH["integrations"][s["integration"]]
            counts: Dict[Tuple[str, str], int] = {}
            pk = ("pkg", s["package_id"])
            cost.setdefault(pk, self.c(t["nre_package_per_mm2"])
                            * self.c(s["package_area"])
                            + self.c(t["nre_fixed_per_package"]))
            counts[pk] = 1
            for c in s["chips"]:
                n = TECH["processes"][c["process"]]
                ck = ("chip", c["name"])
                cost.setdefault(ck, self.c(n["nre_chip_per_mm2"])
                                * self.c(c["area"])
                                + self.c(n["nre_fixed_per_chip"]))
                counts[ck] = counts.get(ck, 0) + 1
                for name, area, proc, is_d2d in c["modules"]:
                    pm = TECH["processes"][proc]
                    mk = ("d2d", proc) if is_d2d else ("mod", name)
                    cost.setdefault(mk, self.c(pm["nre_d2d"]) if is_d2d
                                    else self.c(pm["nre_module_per_mm2"])
                                    * self.c(area))
                    counts[mk] = counts.get(mk, 0) + 1
            for k, v in counts.items():
                uses[k] = uses.get(k, 0.0) + v * s["quantity"]
            per_sys.append(counts)
        return [sum(cost[k] * self.c(v) / self.c(uses[k])
                    for k, v in counts.items()) for counts in per_sys]

    def group(self, systems: Sequence[Dict]) -> Dict[str, List]:
        """``re``/``nre``/``total`` per unit of each system of a group."""
        re = [self.re(s) for s in systems]
        nre = self.nre(systems)
        return {"re": re, "nre": nre,
                "total": [a + b for a, b in zip(re, nre)]}

    # -- candidates ------------------------------------------------------------
    def candidate(self, i: int) -> Dict[str, np.ndarray]:
        """``unit``/``re``/``nre`` (S,) and ``pf`` (portfolio cost) of
        candidate ``i``, as float64 NumPy (memoized)."""
        got = self._cand.get(i)
        if got is None:
            g = self.group(self.space.systems(self.space.candidate(i)))
            got = {k: np.asarray([float(x) for x in v]) for k, v in g.items()}
            got["unit"] = got.pop("total")
            qty = [self.c(s[2]) for s in self.space.skus]
            got["pf"] = float(sum(q * self.c(u) for q, u in
                                  zip(qty, got["unit"])))
            self._cand[i] = got
        return got

    def candidates(self, idx) -> Dict[str, np.ndarray]:
        rows = [self.candidate(int(i)) for i in idx]
        return {k: np.asarray([r[k] for r in rows])
                for k in ("unit", "re", "nre", "pf")}

    # -- Monte-Carlo risk ------------------------------------------------------
    def multipliers(self, seed: int, draws: int, sigmas: Sequence[float]):
        key = (int(seed), int(draws), tuple(float(s) for s in sigmas))
        if key not in self._mult:
            self._mult[key] = mc_multipliers(*key)
        return self._mult[key]

    def mc_quantiles(self, i: int, mult: Dict, quantiles: Sequence[float]):
        """Portfolio-cost quantiles of candidate ``i`` over the draws in
        ``mult`` (each a (draws,) multiplier array, see ``mc_multipliers``)."""
        systems = self.space.systems(self.space.candidate(i))
        nre = self.nre(systems)
        m = {k: self.c(v) for k, v in mult.items()}
        pf = sum(self.c(s["quantity"]) * (self.re(s, m) + n)
                 for s, n in zip(systems, nre))
        qs = self.xp.quantile(pf, self.c(list(quantiles)))
        return np.asarray(qs, np.float64)


def mc_multipliers(seed: int, draws: int, sigmas: Sequence[float]
                   ) -> Dict[str, np.ndarray]:
    """One scenario per draw: ``exp(sigma * z)`` per uncertain parameter,
    ``z`` standard normal from JAX's threefry key of ``seed``.  Runs on
    the host CPU; returns float64 (draws,) arrays."""
    import jax

    with jax.default_device(jax.devices("cpu")[0]):
        keys = jax.random.split(jax.random.PRNGKey(seed), draws)

        def one(k):
            return jax.numpy.stack([jax.random.normal(kk, ())
                                    for kk in jax.random.split(k, 5)])

        z = np.asarray(jax.vmap(one)(keys), np.float64)      # (draws, 5)
    d, w, b, i = (float(s) for s in sigmas)
    return {"defect": np.exp(d * z[:, 0]), "wafer": np.exp(w * z[:, 1]),
            "bond": np.exp(b * z[:, 2]), "substrate": np.exp(b * z[:, 3]),
            "interposer": np.exp(i * z[:, 4])}
