"""Finds a cell's files by the names in ``BENCHMARK.json``.

Everything that belongs to one configuration, traffic mix, request kind
or per-layer metric is a file of its own under ``chipbench/``:

* ``configs/<config>.json``      design space + service settings
* ``workloads/<cell>.json``      traffic mix, loop types, rate, sampling
* ``requests/<kind>.py``         generator and comparison of one kind
* ``metrics/<metric>.py``        reader of one per-layer metric; a split
  quantity (``tick_ms.tput``, ``tick_ms.lat``) may share ``tick_ms.py``
* ``spaces/<kind>.py``           schema of a configuration's ``space``
  block, found by its ``kind`` (``grid`` where it names none)

so a later cell, kind, metric or space schema is added as files, with no
edit here.

A space kind file holds two things:

* ``build(space_cfg)``: the program's space object (the one
  ``PricingService`` serves), importing the program inside the function;
* ``Decoder(space_cfg)``: the reference's own reading of the candidate
  order, importing nothing of the program.  It has ``size`` (candidates),
  ``processes`` and ``integrations`` (the what-if menus),
  ``candidate(i)`` (a hashable description of index ``i``),
  ``index(cand)`` (its inverse, None outside the space),
  ``systems(cand)`` (the candidate's systems as the plain dicts
  ``reference.py`` prices, in SKU order) and ``swap(cand, process,
  integration)`` (the what-if grid's move).
"""
from __future__ import annotations

import importlib.util
import json
import pathlib
from types import ModuleType
from typing import Dict, List

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]


def load(bench_dir: pathlib.Path, path: pathlib.Path) -> ModuleType:
    """Runs the file ``path`` under ``bench_dir`` as a module."""
    name = "chipbench_" + "_".join(path.relative_to(bench_dir).with_suffix(
        "").parts).replace(".", "_").replace("-", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def space_kind_file(kind: str, bench_dir: pathlib.Path) -> pathlib.Path:
    path = pathlib.Path(bench_dir) / "spaces" / f"{kind}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no space kind file {path}")
    return path


def space_kind(kind: str) -> ModuleType:
    """The space schema ``spaces/<kind>.py`` of this benchmark."""
    return load(BENCH_DIR, space_kind_file(kind, BENCH_DIR))


class Catalog:
    def __init__(self, bench_dir: pathlib.Path = BENCH_DIR):
        self.dir = pathlib.Path(bench_dir)
        self.root = self.dir.parent
        self.spec = json.loads((self.root / "BENCHMARK.json").read_text())
        self._modules: Dict[pathlib.Path, ModuleType] = {}

    def _json(self, sub: str, name: str) -> Dict:
        path = self.dir / sub / f"{name}.json"
        if not path.is_file():
            raise FileNotFoundError(f"no {sub[:-1]} file {path}")
        return json.loads(path.read_text())

    def _module(self, path: pathlib.Path) -> ModuleType:
        mod = self._modules.get(path)
        if mod is None:
            mod = self._modules[path] = load(self.dir, path)
        return mod

    def cell(self, name: str) -> Dict:
        """The ``workloads`` entry of ``BENCHMARK.json`` named ``name``."""
        for w in self.spec["workloads"]:
            if w["name"] == name:
                return w
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")

    def workload(self, name: str) -> Dict:
        return self._json("workloads", name)

    def config(self, name: str) -> Dict:
        return self._json("configs", name)

    def request_kind(self, kind: str) -> ModuleType:
        path = self.dir / "requests" / f"{kind}.py"
        if not path.is_file():
            raise FileNotFoundError(f"no request kind file {path}")
        return self._module(path)

    def space_kind(self, kind: str) -> ModuleType:
        return self._module(space_kind_file(kind, self.dir))

    def metric_reader(self, name: str) -> ModuleType:
        own = self.dir / "metrics" / f"{name}.py"
        shared = self.dir / "metrics" / f"{name.split('.')[0]}.py"
        for path in (own, shared):
            if path.is_file():
                return self._module(path)
        raise FileNotFoundError(f"no metric reader {own} or {shared}")

    def metrics_of(self, cell: str, section: str) -> List[Dict]:
        """The ``end_to_end`` or ``per_layer`` metrics a cell reports: those
        listing it under ``workloads``, and those without the key whose
        ``moves`` metric (or, end to end, which) the cell reports."""
        e2e = [m for m in self.spec["end_to_end"]
               if cell in m.get("workloads", [cell])]
        if section == "end_to_end":
            return e2e
        names = {m["name"] for m in e2e}
        return [m for m in self.spec["per_layer"]
                if cell in m.get("workloads", [cell] if m["moves"] in names
                                 else [])]
