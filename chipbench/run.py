"""Run one cell of the pricing service's on-chip benchmark.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace <0|1>

The cells, their metrics and bounds are in ``BENCHMARK.json`` at the
root of the checkout; each cell's configuration and traffic are files
under ``chipbench/configs`` and ``chipbench/workloads``.  The last line
of stdout is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, and the
compared numbers under ``checks``); the numbers compared, each beside
its limit, are also the last lines of stderr.  Without a TPU that is in
the device table (``harness/runner.py``) it exits with code 2 and
prints no result.
"""
import pathlib
import sys
import time

T_START = time.perf_counter()
HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))

from harness.runner import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(t_start=T_START))
