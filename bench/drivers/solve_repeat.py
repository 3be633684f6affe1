"""Traffic `solve_repeat`: cold solves to tolerance, back to back.

Set-up builds the objective and the solver once and runs one solve, which
compiles.  The window then repeats cold solves from lambda = 0 on the same
instance with the same engine; it closes at the end of the first solve
that ends `--seconds` or more after it opened.  `solve_s` is the whole
window over the solves that converged; a solve that stops unconverged
counts as failed.  The reference then evaluates the dual at the point the
last solve's last iteration evaluated: the converged duals.
"""
from __future__ import annotations

import gc
import time

from bench.lib import compare, program


def _timed_solves(run):
    edges, obj, mx = program.build_solve(run)
    mx.maximize(obj)
    t0 = run.open_window()
    run.end_to_end["setup_s"] = t0 - run.t0
    iterations, converged = [], 0
    while True:
        result = mx.maximize(obj)
        iterations.append(result.iterations_run)
        converged += bool(result.converged)
        t1 = time.perf_counter()
        if t1 - t0 >= run.seconds:
            break
    run.close_window()
    run.attempted = len(iterations)
    run.failed = run.attempted - converged
    run.end_to_end["solve_s"] = (t1 - t0) / max(converged, 1)
    run.readings["window_iterations"] = sum(iterations)
    run.readings["window_s"] = t1 - t0
    run.readings["solves"] = len(iterations)
    run.readings["iters_per_solve"] = sum(iterations) / len(iterations)
    run.read_memory_peak()
    return edges, compare.point_of(result)


def measure(run):
    """Set-up and window; returns what `correct` is judged on."""
    evidence = _timed_solves(run)
    gc.collect()
    return evidence


judge = compare.judge_solve
control = compare.control_solve


def run(run):
    judge(run, measure(run))
