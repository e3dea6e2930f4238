"""Readings that the limits of ``correct`` are set from.

  python3 chipbench/calibrate.py --workload <cell> --seeds 1,2,3 \
      [--seconds 5]

One process; per seed, a fresh service serves a window of the cell's own
traffic at its own load, and the sampled answers are compared with the
float64 reference twice: as served (the program's reading, the lower
end of a limit) and with the same reference computed in bfloat16 put in
the program's place (the control's reading, the upper end).  Prints one
JSON line per seed.  The benchmark's own runs never run the control.
"""
import asyncio
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))

from harness import check, runner  # noqa: E402
from harness.catalog import Catalog  # noqa: E402
from harness.reference import Reference  # noqa: E402


def readings(cat, workload, seed, seconds, devs, clock, control_dtype):
    """The program's numbers and, with ``control_dtype``, the control's."""
    import jax
    import jax.numpy as jnp

    cell = runner.prepare(cat, workload, seed, seconds)
    sv = asyncio.run(runner.serve(cell, seconds, time.perf_counter(), devs,
                                  clock))
    plan = cell.wl.get("check", {})
    ref = Reference(cell.cfg["space"])
    prog, n = check.compare(sv.win.records, cat.request_kind, ref, seed,
                            plan)
    prog["missing"] = sv.win.unanswered
    out = {"workload": workload, "seed": seed, "compared": n,
           "program": prog, "device": devs[0].device_kind}
    if control_dtype is not None:
        with jax.default_device(jax.devices("cpu")[0]):
            low = Reference(cell.cfg["space"], xp=jnp, dtype=control_dtype)
            out["control"], _ = check.compare(
                sv.win.records, cat.request_kind, ref, seed, plan,
                control=low)
    return out


def main(argv=None) -> int:
    import argparse

    import jax.numpy as jnp

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control-seeds", type=int, default=3,
                    help="run the control on the first N seeds")
    args = ap.parse_args(argv)
    cat = Catalog()
    devs = runner.devices(int(cat.cell(args.workload)["chips"]))
    runner.use_cache()
    with runner.CompileClock() as clock:
        for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
            low = jnp.bfloat16 if i < args.control_seeds else None
            print(json.dumps(readings(cat, args.workload, seed, args.seconds,
                                      devs, clock, low)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
