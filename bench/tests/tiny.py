"""A throwaway benchmark for the CPU tests: a copy of `bench/` and
`BENCHMARK.json` in a temporary directory, with tiny configurations and
one tiny cell per cell of `BENCHMARK.json`, added as files and entries
only."""
from __future__ import annotations

import copy
import json
import os
import shutil
import time

from bench.lib import cell

SOURCES, DESTINATIONS, MAX_ITERATIONS = 2000, 10, 1500


def make(tmp_path) -> str:
    """The root of the throwaway checkout; its cells are `tiny-<cell>`."""
    root = str(tmp_path)
    bench = os.path.join(root, "bench")
    shutil.copytree(cell.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    benchmark = cell.load_json(cell.ROOT, "BENCHMARK.json")
    for cfg in list(benchmark["configs"]):
        body = cell.load_json(cell.BENCH_DIR, "configs",
                              cfg["name"] + ".json")
        body["generator"].update(num_sources=SOURCES,
                                 num_destinations=DESTINATIONS)
        body["solver"]["max_iterations"] = MAX_ITERATIONS
        name = "tiny-" + cfg["name"]
        _dump(body, bench, "configs", name)
        benchmark["configs"].append(dict(cfg, name=name))
    for entry in list(benchmark["workloads"]):
        body = cell.load_json(cell.BENCH_DIR, "workloads",
                              entry["name"] + ".json")
        name = "tiny-" + entry["name"]
        body["config"] = "tiny-" + body["config"]
        _dump(body, bench, "workloads", name)
        benchmark["workloads"].append(
            {"name": name, "config": body["config"],
             "traffic": body["traffic"], "chips": 1, "why": "CPU test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(benchmark, f)
    return root


def _dump(body: dict, bench: str, kind: str, name: str) -> None:
    with open(os.path.join(bench, kind, name + ".json"), "w") as f:
        json.dump(body, f)


def files(root: str, name: str, **traffic) -> cell.CellFiles:
    out = cell.resolve(name, bench_dir=os.path.join(root, "bench"),
                       root=root)
    out.traffic = dict(copy.deepcopy(out.traffic), **traffic)
    return out


def run(root: str, name: str, seed: int = 2**31 + 3, seconds: float = 1.0,
        **traffic) -> dict:
    """One run of a tiny cell on the CPU, the look for a chip skipped."""
    import jax
    from bench import run as harness
    return harness.execute(files(root, name, **traffic), seed, seconds,
                           False, time.perf_counter(), jax.devices())
