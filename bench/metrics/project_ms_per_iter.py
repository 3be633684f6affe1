"""Device milliseconds per iteration in the ops of the `sweep.project`
scope: the pre-projection point, the box-cut projection of each source's
row and the row's c.x and x.x (moves `iter_ms`)."""
from bench.lib.annotations import scope_ms_per_iter


def read(r):
    return scope_ms_per_iter(r, "sweep.project")
