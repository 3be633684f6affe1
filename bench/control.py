#!/usr/bin/env python3
"""Readings that the limits of `correct` are set from.

    python bench/control.py --workload <cell> --seconds <s> --seeds 11 12 13 \
        [--out readings.jsonl]

For each seed, in one process, runs the cell's set-up and a window of
`--seconds`, then prints one JSON line: the numbers the program reads
against the reference (the lower readings), and the numbers the control
reads, the reference computed in bfloat16 and put in the program's place
(the upper readings; the configuration states float32).  The benchmark's
own runs never run the control.  Lines are also appended to `--out` when
given.  Needs a TPU, as `bench/run.py` does.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def readings(files, seed: int, seconds: float) -> dict:
    """One seed's program and control readings for the cell `files`."""
    from bench.lib import cell, program
    run = cell.Run(files, seed, seconds, False, time.perf_counter(),
                   clock=program.CompileClock())
    driver = files.module("drivers", files.traffic["driver"])
    evidence = driver.measure(run)
    driver.judge(run, evidence)
    out = {"seed": seed, "correct": run.correct,
           "program": {k: v for k, (v, _) in run.checks.items()},
           "control": driver.control(evidence),
           "limits": {k: lim for k, (_, lim) in run.checks.items()},
           "end_to_end": run.end_to_end, "attempted": run.attempted,
           "failed": run.failed, "memory_peak_bytes": run.memory_peak_bytes,
           "readings": {k: v for k, v in run.readings.items()
                        if k != "op_seconds"}}
    del evidence
    gc.collect()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.lib import cell
    from bench.run import find_accelerator
    files = cell.resolve(args.workload)
    find_accelerator(int(files.entry["chips"]))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    for seed in args.seeds:
        line = json.dumps(readings(files, seed, args.seconds), default=str)
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as log:
                log.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
