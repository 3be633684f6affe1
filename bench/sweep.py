#!/usr/bin/env python3
"""The one-off chip sweep that sized `matching-mid`.

    python bench/sweep.py --sources 50000 100000 --instance-seeds 0 [--out F]

For each number of sources and each instance seed of `matching-mid` (its
other settings as configured), one cold solve to tolerance after a warm-up
solve that compiles: iterations, stop reason, seconds and milliseconds per
iteration, to check that the cell's solve fits in half a window.  Each line
is printed as JSON, and appended to `--out` when given.  Needs a TPU, as
`bench/run.py` does.
"""
from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def sweep_solve(sources, instance_seeds, seed: int, emit) -> None:
    from bench.lib import cell, program
    files = cell.resolve("matching-mid.solve")
    for num_sources in sources:
        for inst in instance_seeds:
            files.config = copy.deepcopy(files.config)
            files.config["generator"].update(num_sources=num_sources,
                                             instance_seed=inst)
            run = cell.Run(files, seed, 0.0, False, time.perf_counter(),
                           clock=program.CompileClock())
            _, obj, mx = program.build_solve(run)
            mx.maximize(obj)
            t = time.perf_counter()
            res = mx.maximize(obj)
            dt = time.perf_counter() - t
            emit({"num_sources": num_sources, "instance_seed": inst,
                  "edges": run.readings["edges"],
                  "iterations": res.iterations_run,
                  "stop_reason": res.stop_reason.value, "solve_s": dt,
                  "ms_per_iteration": 1e3 * dt / max(res.iterations_run, 1),
                  "gen_s": run.readings["gen_s"],
                  "build_s": run.readings["build_s"]})
            del obj, mx


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sources", type=int, nargs="+", required=True)
    ap.add_argument("--instance-seeds", type=int, nargs="+", default=[0])
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.run import find_accelerator
    find_accelerator(1)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    def emit(line: dict) -> None:
        text = json.dumps(line, default=str)
        print(text, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(text + "\n")

    sweep_solve(args.sources, args.instance_seeds, args.seed, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
