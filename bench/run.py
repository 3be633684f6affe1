#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cell is found by name in
`BENCHMARK.json` and its files under `bench/` (see `bench/lib/cell.py`).
The run builds the cell from the seed, warms it up, measures for
`--seconds`, and checks what the timed path produced against the plain
reference (`bench/lib/reference.py`).  With `--trace 0` the result carries
the cell's end-to-end metrics; with `--trace 1` the profiler records the
window and the result carries the per-layer metrics read from it.

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics`, `device`, `breakdown` when traced, and
`checks` last, each compared number beside its limit.  The same numbers
are the last lines of standard error.  Without a TPU, with fewer chips
than the cell asks for, or without the program's `src/` beside `bench/`,
the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def parse(argv):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_accelerator(chips: int):
    """The TPU devices the cell runs on; raises RuntimeError when JAX finds
    no TPU or fewer than `chips`."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise RuntimeError(f"JAX finds no TPU (platform "
                           f"{devices[0].platform!r})")
    if len(devices) < chips:
        raise RuntimeError(f"the cell needs {chips} chips, JAX finds "
                           f"{len(devices)}")
    return devices


def execute(files, seed: int, seconds: float, trace: bool, t0: float,
            devices) -> dict:
    """Drive one run of the cell and return its result object."""
    from bench.lib import cell, program, trace as tracing
    clock = program.CompileClock()
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    run = cell.Run(files, seed, seconds, trace, t0, clock=clock,
                   trace_dir=trace_dir)
    run.readings["device_kind"] = devices[0].device_kind
    try:
        files.module("drivers", files.traffic["driver"]).run(run)
        breakdown = None
        if trace:
            red = tracing.reduce_dir(trace_dir, cell.Run.WINDOW)
            run.readings.update(red.readings())
            breakdown = red.breakdown()
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    if run.window_compiles:
        _say(f"[bench] {run.window_compiles} compiles inside the window")
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in cell.metrics_of(files.benchmark, files.name, section):
        if trace:
            value = files.module("metrics", m["name"]).read(run.readings)
        else:
            value = run.end_to_end.get(m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": run.memory_peak_bytes}
    if trace:
        device["busy_s"] = run.readings["busy_s"]
        device["window_s"] = run.readings["trace_window_s"]
    out = {"correct": run.correct, "attempted": run.attempted,
           "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {k: {"value": v if math.isfinite(v) else str(v),
                         "limit": lim} for k, (v, lim) in run.checks.items()}
    _say("[bench] readings " + json.dumps(
        {k: v for k, v in run.readings.items() if k != "op_seconds"},
        sort_keys=True))
    for k, (v, lim) in run.checks.items():
        _say(f"check {k} {v:.6g} limit {lim:.6g} "
             f"{'ok' if v <= lim else 'FAIL'}")
    return out


def main(argv=None) -> int:
    args = parse(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    try:
        import repro  # noqa: F401
        from bench.lib import cell
        files = cell.resolve(args.workload)
    except (ImportError, OSError, ValueError) as e:
        _say(f"bench: cannot run {args.workload!r}: {e}")
        return 2
    try:
        devices = find_accelerator(int(files.entry["chips"]))
    except RuntimeError as e:
        _say(f"bench: {e}")
        return 3
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    # keep every program, however quick to compile, so that only a cell's
    # first run in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    _say(f"[bench] {args.workload} seed {args.seed} seconds {args.seconds} "
         f"trace {args.trace} on {devices[0].device_kind} "
         f"x{len(devices)}; compile cache {cache_dir}")
    out = execute(files, args.seed, args.seconds, bool(args.trace), T0,
                  devices[:int(files.entry["chips"])])
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
