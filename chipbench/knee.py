"""Find an open-loop cell's knee: the highest rate the service sustains.

  python3 chipbench/knee.py --workload <cell> --rates 100,200,400 \
      [--seconds 10] [--seed 1]

One process, one set-up; for each rate one window of the cell's traffic
(its closed-loop clients too) with the open-loop rate replaced.  Prints
one JSON line per rate: offered and completed rates, p50/p95 latency
from the due time, how late the generator ran, and ``drain_s``, how long
after the window's close the last answer came.  Where the backlog grows
all through the window, p95 approaches the window's length and drain_s
grows with the rate: the knee is the highest rate before that.  Used
once when a cell is defined; the cell's file then keeps a fixed rate.
"""
import asyncio
import copy
import json
import pathlib
import sys

HERE = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

from harness import drive, runner, traffic  # noqa: E402
from harness.catalog import Catalog  # noqa: E402


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    cat = Catalog()
    cell = cat.cell(args.workload)
    devs = runner.devices(int(cell["chips"]))
    runner.use_cache()
    wl = cat.workload(args.workload)
    cfg = cat.config(cell["config"])
    space = runner.design_space(cfg)
    n = space.size()
    warm = traffic.warmup_requests(wl, args.seed, n, cat.request_kind)
    scfg = runner.service_config(cfg, [p.request for p in warm])

    from repro.service import PricingService

    async def main_():
        svc = PricingService(space, scfg)
        await svc.start()
        await asyncio.gather(*(svc.submit(p.request) for p in warm))
        for i, rate in enumerate(float(r) for r in args.rates.split(",")):
            w = copy.deepcopy(wl)
            w["open"]["rate_per_s"] = rate
            plan = traffic.open_plan(args.workload, w, args.seconds,
                                     args.seed + i, n, cat.request_kind)
            clients = traffic.closed_clients(args.workload, w, args.seed + i,
                                             n, cat.request_kind)
            win = drive.Window(svc, plan, clients, args.seconds)
            await win.run()
            timed = win.timed()
            ok = [r for r in timed if r.ok]
            lat = np.asarray([r.latency_s * 1e3 for r in ok])
            late = np.asarray([(r.t_sent - r.t_due) * 1e3 for r in timed])
            done_in = sum(1 for r in ok if r.t_done <= win.t_end)
            print(json.dumps({
                "workload": args.workload, "rate_per_s": rate,
                "offered": len(timed), "ok": len(ok),
                "completed_per_s": done_in / args.seconds,
                "p50_ms": float(np.percentile(lat, 50)) if ok else None,
                "p95_ms": float(np.percentile(lat, 95)) if ok else None,
                "late_p99_ms": float(np.percentile(late, 99)),
                "drain_s": max((r.t_done for r in ok), default=win.t_end)
                - win.t_end,
                "candidates_per_s": win.rows_in_window() / args.seconds,
                "device": devs[0].device_kind}), flush=True)
        await svc.stop()

    asyncio.run(main_())
    return 0


if __name__ == "__main__":
    sys.exit(main())
