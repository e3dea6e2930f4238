"""Cases of the benchmark's CPU tests: a scratch checkout
(``BENCHMARK.json`` + ``chipbench/``) to which a test adds cells, and the
cells' files cut to a size a test run can hold."""
import json
import pathlib
import time

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


class Checkout:
    """A copy of the benchmark; ``add_cell`` writes a configuration and a
    workload file and lists the cell in its ``BENCHMARK.json``."""

    def __init__(self, root: pathlib.Path):
        self.root = root
        self.bench = root / "chipbench"

    def write(self, rel: str, text: str):
        path = self.bench / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(text)

    def spec(self):
        return json.loads((self.root / "BENCHMARK.json").read_text())

    def save_spec(self, spec):
        (self.root / "BENCHMARK.json").write_text(json.dumps(spec, indent=1))

    def add_cell(self, name: str, config: str, cfg: dict, wl: dict,
                 e2e=("p50_ms", "p95_ms")):
        self.write(f"configs/{config}.json", json.dumps(cfg))
        self.write(f"workloads/{name}.json", json.dumps(wl))
        spec = self.spec()
        if config not in {c["name"] for c in spec["configs"]}:
            spec["configs"].append({"name": config, "source": "test",
                                    "file": f"chipbench/configs/{config}.json",
                                    "reduced": [], "why": "test"})
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": name.split(".", 1)[1],
                                  "chips": 1, "why": "test"})
        for m in spec["end_to_end"]:
            if m["name"] in e2e and "workloads" in m:
                m["workloads"].append(name)
        self.save_spec(spec)

    def run(self, name: str, seed: int = 7, seconds: float = 0.5,
            trace: bool = False):
        from harness import runner
        from harness.catalog import Catalog

        return runner.run(name, seed, seconds, trace, time.perf_counter(),
                          catalog=Catalog(self.bench), on_chip=False,
                          log=lambda s: None)


def small_scms(chunk: int = 64) -> dict:
    """The Fig. 8 configuration with a 64-slot chunk (CPU-sized)."""
    cfg = json.loads((BENCH / "configs" / "scms_fig8.json").read_text())
    cfg["service"].update(chunk=chunk, split=chunk // 4)
    return cfg


def small_points(rate: float = 120.0) -> dict:
    """The points mix at a CPU-sized rate."""
    wl = json.loads((BENCH / "workloads" / "scms_fig8.points.json")
                    .read_text())
    wl["open"]["rate_per_s"] = rate
    return wl


def small_risk(rate: float = 40.0) -> dict:
    """Monte-Carlo risk queries on the Fig. 8 space: one McSpec of 256
    draws, 16-64 uniform rows, so requests coalesce."""
    mc = {"draws": 256, "quantiles": [0.05, 0.5, 0.95], "seed": 0}
    return {"config": "scms_small", "why": "test",
            "open": {"rate_per_s": rate, "knee_per_s": rate / 0.8,
                     "mix": [{"share": 1.0, "kind": "mc_risk",
                              "params": {"rows": {"choices": [16, 32, 64]},
                                         "mc": mc}}]},
            "check": {"responses": 8, "rows": 4}}


def small_fig4(chunk: int = 64) -> dict:
    """The Fig. 4 configuration with a 64-slot chunk (CPU-sized)."""
    cfg = json.loads((BENCH / "configs" / "fig4_grid.json").read_text())
    cfg["service"].update(chunk=chunk, split=chunk // 4)
    return cfg


def small_fig4_risk(rate: float = 40.0) -> dict:
    """The Fig. 4 risk mix at a CPU-sized rate and 64 draws."""
    wl = json.loads((BENCH / "workloads" / "fig4_grid.risk.json")
                    .read_text())
    wl["config"] = "fig4_small"
    wl["open"]["rate_per_s"] = rate
    wl["open"]["mix"][0]["params"]["mc"]["draws"] = 64
    return wl
