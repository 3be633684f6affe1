"""One run of one cell: the files it is made of, and what it records.

The harness is driven by data.  A cell named in `BENCHMARK.json` is found
by name under the benchmark's directory:

    workloads/<cell>.json     its configuration, traffic and check limits
    configs/<config>.json     the deployment: generator and solver settings
    traffic/<traffic>.json    the traffic mix: its driver and parameters
    drivers/<driver>.py       one general generator per kind of traffic
    metrics/<metric>.py       one reader per per-layer metric

so a later change adds a cell, a configuration, a traffic mix or a metric
by adding files and an entry, and edits none.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import time
from typing import Dict, Optional, Tuple

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """The Python file `<bench>/<kind>/<name>.py`, imported by path (metric
    names may hold dots)."""
    path = os.path.join(bench_dir, kind, name + ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class CellFiles:
    """Everything one cell is made of, resolved by name."""

    name: str
    entry: dict          # the cell's entry in BENCHMARK.json
    workload: dict       # workloads/<cell>.json
    config: dict         # configs/<config>.json
    traffic: dict        # traffic/<traffic>.json
    benchmark: dict      # the whole BENCHMARK.json
    bench_dir: str = BENCH_DIR

    def module(self, kind: str, name: str):
        """`drivers/<name>.py` or `metrics/<name>.py` of this benchmark."""
        return load_module(kind, name, self.bench_dir)


def resolve(name: str, bench_dir: str = BENCH_DIR,
            root: str = ROOT) -> CellFiles:
    """Find the cell `name` and its files; raise ValueError when the cell,
    or a file it names, is missing or disagrees with BENCHMARK.json."""
    benchmark = load_json(root, "BENCHMARK.json")
    entries = {w["name"]: w for w in benchmark["workloads"]}
    if name not in entries:
        raise ValueError(f"no cell {name!r} in BENCHMARK.json "
                         f"(cells: {sorted(entries)})")
    entry = entries[name]
    workload = load_json(bench_dir, "workloads", name + ".json")
    for key in ("config", "traffic"):
        if workload.get(key) != entry[key]:
            raise ValueError(
                f"workloads/{name}.json names {key} {workload.get(key)!r}, "
                f"BENCHMARK.json names {entry[key]!r}")
    return CellFiles(
        name=name, entry=entry, workload=workload,
        config=load_json(bench_dir, "configs", entry["config"] + ".json"),
        traffic=load_json(bench_dir, "traffic", entry["traffic"] + ".json"),
        benchmark=benchmark, bench_dir=bench_dir)


def metrics_of(benchmark: dict, cell: str, section: str):
    """The metrics of `section` ("end_to_end" or "per_layer") that the cell
    reports: those listing it, and those with no `workloads` key."""
    return [m for m in benchmark[section]
            if "workloads" not in m or cell in m["workloads"]]


class Run:
    """The state one run of a cell shares between the harness and its
    driver: arguments, the compile clock, the traced window, readings."""

    WINDOW = "bench.window"

    def __init__(self, files: CellFiles, seed: int, seconds: float,
                 trace: bool, t0: float, clock,
                 trace_dir: Optional[str] = None):
        self.files = files
        self.config = files.config
        self.traffic = files.traffic
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.t0 = t0
        self.clock = clock
        self.trace_dir = trace_dir
        self.readings: Dict[str, float] = {}
        self.end_to_end: Dict[str, float] = {}
        self.checks: Dict[str, Tuple[float, float]] = {}
        self.attempted = 0
        self.failed = 0
        self.memory_peak_bytes: Optional[int] = None
        self._annotation = None
        self._window_open: Optional[float] = None
        self._window_closed = False
        self.window_compiles: Optional[int] = None

    def check(self, name: str, value: float) -> None:
        """Record one compared number beside its limit from the cell's
        workload file."""
        self.checks[name] = (float(value),
                             float(self.files.workload["checks"][name]))

    @property
    def correct(self) -> bool:
        return bool(self.checks) and all(
            v <= lim for v, lim in self.checks.values())

    def open_window(self) -> float:
        """Mark the first timed instant; start the profiler on a traced
        run.  Returns the instant (host clock)."""
        if self._window_open is None:
            if self.trace:
                import jax
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
                self._annotation = jax.profiler.TraceAnnotation(self.WINDOW)
                self._annotation.__enter__()
            self._compiles_at_open = self.clock.count
            self.readings["compile_s"] = self.clock.seconds
            self._window_open = time.perf_counter()
        return self._window_open

    def close_window(self) -> None:
        """End the timed window (idempotent); stop the profiler."""
        if self._window_open is None or self._window_closed:
            return
        self._window_closed = True
        self.window_compiles = self.clock.count - self._compiles_at_open
        if self._annotation is not None:
            import jax
            self._annotation.__exit__(None, None, None)
            self._annotation = None
            jax.profiler.stop_trace()

    def read_memory_peak(self) -> None:
        """Peak device bytes of the fullest chip, read once the window has
        closed and before the reference runs."""
        import jax
        peaks = [d.memory_stats().get("peak_bytes_in_use", 0)
                 for d in jax.local_devices()
                 if d.memory_stats() is not None]
        self.memory_peak_bytes = int(max(peaks)) if peaks else 0
