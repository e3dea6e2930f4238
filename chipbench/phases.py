"""Print the service's phases and the device's idle time by phase in the
trace a traced run left behind.

  python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
      --trace 1
  python3 chipbench/phases.py .chipbench_trace/<cell>

One JSON line: ``phases`` (calls and host seconds of each ``repro.*``
annotation inside the traced window) and ``idle_by_phase`` (the window's
device idle seconds, each gap labelled by the innermost ``repro.*``
annotation at its midpoint, else as the result line's ``idle_gaps``
labels it); see ``harness/phases.py``.
"""
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

from harness.phases import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
