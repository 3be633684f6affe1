"""Iterations per solve to tolerance over the window's solves, as the
solver counts them (moves `solve_s`)."""


def read(r):
    return r.get("iters_per_solve")
