"""Traffic `solve_window`: one solve to tolerance, timed per iteration.

The window drives `Maximizer.maximize` (and through it `SolveEngine.solve`)
on the objective `solve_distributed` builds on a one-device mesh.  The
first chunk compiles and belongs to set-up; the window runs from the end
of the first chunk to the first chunk boundary at least `--seconds` later,
and `iter_ms` is its wall-clock over the iterations run in it.  Then the
reference evaluates the dual at the point the last iteration evaluated.
"""
from __future__ import annotations

import gc
import time

from bench.lib import compare, program


def _timed_solve(run):
    edges, obj, mx = program.build_solve(run)
    marks = []

    def on_check(rec):
        marks.append((time.perf_counter(), rec.it))
        if len(marks) == 1:
            run.open_window()

    def stop():
        if marks and time.perf_counter() - marks[0][0] >= run.seconds:
            run.close_window()
            return True
        return False

    result = mx.maximize(obj, diagnostics_fn=on_check, preempt_fn=stop)
    run.close_window()
    if len(marks) < 2:
        raise RuntimeError("the solve ended within its first chunk; no "
                           "window to time")
    (t0, it0), (t1, it1) = marks[0], marks[-1]
    run.end_to_end["setup_s"] = t0 - run.t0
    run.end_to_end["iter_ms"] = (t1 - t0) / (it1 - it0) * 1e3
    run.readings["window_iterations"] = it1 - it0
    run.readings["window_s"] = t1 - t0
    run.read_memory_peak()
    return edges, compare.point_of(result)


def measure(run):
    """Set-up and window; returns what `correct` is judged on."""
    evidence = _timed_solve(run)
    run.attempted, run.failed = 1, 0
    gc.collect()
    return evidence


judge = compare.judge_solve
control = compare.control_solve


def run(run):
    judge(run, measure(run))
