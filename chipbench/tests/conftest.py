"""Fixtures of the benchmark's CPU tests: the harness and the program on
the import path, and a scratch checkout to which a test adds cells."""
import pathlib
import shutil
import sys

import pytest

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
for _p in (str(ROOT / "src"), str(BENCH), str(BENCH / "tests")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from chipbench_cases import Checkout  # noqa: E402


@pytest.fixture
def checkout(tmp_path):
    dst = tmp_path / "checkout"
    shutil.copytree(BENCH, dst / "chipbench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", dst / "BENCHMARK.json")
    return Checkout(dst)
