"""Restart benchmark: SIGKILL a serving *process* mid-search, resume,
and prove recovery — the end-to-end durability oracle.

  PYTHONPATH=src python -m benchmarks.restart_bench [--fast]

The parent spawns a child interpreter (``--child``) that runs a
:class:`~repro.service.PricingService` with a durability directory and
submits one long search.  The parent polls the checkpoint tree until at
least ``--kill-after`` checkpoint steps have been published, then
SIGKILLs the child — a real process death, not an injected fault: no
atexit hooks, no flushes, whatever was mid-write stays mid-write.

It then recovers in-process over the same directory: a fresh service
rescans the journal, re-admits the orphaned search with replayed
provenance, restores the newest readable checkpoint, and finishes it.
The parent runs nothing on JAX until the child is dead: on an
accelerator only one process may hold the device.

Asserts (and writes BENCH_restart.json for
scripts/check_bench_regression.py):
  * ``search_bitexact`` — the recovered search's history AND ranking are
    bit-exact against the uninterrupted ``portfolio_search`` oracle
    (zero tolerance);
  * ``lost_requests`` — after recovery the journal holds no open
    admission: nothing the child acknowledged was silently dropped;
  * ``recovery_s`` — bounded restart-to-answer latency.
"""
import argparse
import asyncio
import os
import pathlib
import shutil
import signal
import subprocess
import sys
import tempfile
import time

from .common import REPO_ROOT, emit, write_bench_json

SEED, POP, ELITE = 3, 16, 4


def _cfg(directory: pathlib.Path):
    from repro.service import DurabilityConfig, SearchWarmup, ServiceConfig
    return ServiceConfig(
        chunk=32, split=8,
        warm_search=(SearchWarmup(population=POP, elite=ELITE),),
        durability=DurabilityConfig(directory=directory,
                                    checkpoint_every=1),
        sigterm_drain=True)


def child(directory: str, generations: int) -> None:
    """The victim: serve one long search until killed."""
    from repro.service import PricingService, SearchRequest

    from .dse_bench import SPACE

    async def _main():
        svc = PricingService(SPACE, _cfg(pathlib.Path(directory)))
        await svc.start()
        resp = await svc.submit(SearchRequest(
            seed=SEED, population=POP, generations=generations,
            elite=ELITE))
        await svc.stop()
        return resp

    resp = asyncio.run(_main())
    # Reaching this line means the parent never killed us — the run is
    # then meaningless, which the parent detects via our exit.
    print(f"# child finished unkilled: ok={resp.ok}")


def _published_steps(directory: pathlib.Path) -> int:
    root = directory / "checkpoints"
    if not root.exists():
        return 0
    return sum(1 for p in root.glob("search_*/step_*")
               if ".tmp-" not in p.name and (p / "manifest.json").exists())


def run(fast: bool = False, generations: int = 0, kill_after: int = 2,
        timeout_s: float = 900.0) -> dict:
    """``timeout_s`` bounds the wait for ``kill_after`` checkpoints; it
    covers the child's cold start, which compiles every warmed lane."""
    gens = generations or (300 if fast else 600)
    directory = pathlib.Path(tempfile.mkdtemp(prefix="repro_restart_"))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO_ROOT / "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else []))
    try:
        proc = subprocess.Popen(
            [sys.executable, "-m", "benchmarks.restart_bench", "--child",
             "--dir", str(directory), "--generations", str(gens)],
            cwd=REPO_ROOT, env=env)
        t_spawn = time.perf_counter()
        deadline = t_spawn + timeout_s
        first_ckpt_s = None
        while True:
            if proc.poll() is not None:
                raise RuntimeError(
                    f"child exited (rc={proc.returncode}) before the kill"
                    f" — raise --generations (got {gens})")
            steps = _published_steps(directory)
            if steps and first_ckpt_s is None:
                first_ckpt_s = time.perf_counter() - t_spawn
                print(f"# restart: child published its first checkpoint "
                      f"{first_ckpt_s:.2f}s after spawn")
            if steps >= kill_after:
                break
            if time.perf_counter() > deadline:
                proc.kill()
                proc.wait()
                raise RuntimeError(
                    f"no {kill_after} checkpoints within {timeout_s}s "
                    f"(saw {steps})")
            time.sleep(0.05)
        checkpoints_at_kill = steps
        proc.send_signal(signal.SIGKILL)
        proc.wait()

        # -- recovery: a fresh service over the same directory ----------
        import jax

        from repro.dse import portfolio_search
        from repro.service import PricingService, RequestJournal

        from .dse_bench import SPACE

        async def _recover():
            svc = PricingService(SPACE, _cfg(directory))
            t0 = time.perf_counter()
            await svc.start()
            replayed = await svc.drain_replayed()
            recovery_s = time.perf_counter() - t0
            await svc.stop()
            return svc, replayed, recovery_s

        svc, replayed, recovery_s = asyncio.run(_recover())
        snap = svc.snapshot()["durability"]
        search_resp = next((r for r in replayed
                            if r.kind == "search" and r.ok), None)
        oracle = portfolio_search(SPACE, jax.random.PRNGKey(SEED),
                                  population=POP, generations=gens,
                                  elite=ELITE)
        bitexact = int(
            search_resp is not None and search_resp.replayed
            and search_resp.result.history == oracle.history
            and [c.label for c in search_resp.result.ranked]
            == [c.label for c in oracle.ranked])
        j = RequestJournal(_cfg(directory).durability.journal_dir)
        lost = len(j.replay())
        j.close()
    finally:
        shutil.rmtree(directory, ignore_errors=True)

    summary = {
        "generations": gens,
        "checkpoints_at_kill": checkpoints_at_kill,
        "child_first_checkpoint_s": first_ckpt_s,
        "child_killed": 1,
        "replayed": len(replayed),
        "checkpoints_restored": snap["checkpoints_restored"],
        "search_bitexact": bitexact,
        "lost_requests": lost,
        "recovery_s": recovery_s,
        "fast": fast,
        "survived": 1.0,
    }
    emit("restart: SIGKILL mid-search -> resume", [{
        "generations": gens, "ckpts_at_kill": checkpoints_at_kill,
        "replayed": len(replayed),
        "ckpt_restored": summary["checkpoints_restored"],
        "bitexact": bitexact, "lost": lost, "recovery_s": recovery_s}])
    write_bench_json("restart", summary)

    assert bitexact == 1, \
        "recovered search is not bit-exact vs the uninterrupted oracle"
    assert lost == 0, f"{lost} journaled requests were silently lost"
    assert snap["checkpoints_restored"] >= 1, \
        "recovery did not restore a checkpoint (resumed from scratch?)"
    print(f"# restart: killed child at {checkpoints_at_kill} checkpoints,"
          f" resumed {len(replayed)} request(s) bit-exact in "
          f"{recovery_s:.2f}s, 0 lost")
    return summary


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--fast", action="store_true",
                    help="CI smoke: shorter search")
    ap.add_argument("--child", action="store_true",
                    help="internal: run the to-be-killed serving process")
    ap.add_argument("--dir", default="",
                    help="durability directory (child mode)")
    ap.add_argument("--generations", type=int, default=0)
    ap.add_argument("--kill-after", type=int, default=2,
                    help="published checkpoint steps before SIGKILL")
    args = ap.parse_args()
    from repro.service.cache import use_compile_cache
    use_compile_cache()
    if args.child:
        if not args.dir or not args.generations:
            ap.error("--child needs --dir and --generations")
        child(args.dir, args.generations)
        return
    run(fast=args.fast, generations=args.generations,
        kill_after=args.kill_after)


if __name__ == "__main__":
    main()
