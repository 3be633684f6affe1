"""Device milliseconds per iteration in the ops of the `sweep.ax` scope:
the Ax reduction by destination, its x gathers over the AxPlan buckets,
the products and the row assembly (moves `iter_ms`)."""
from bench.lib.annotations import scope_ms_per_iter


def read(r):
    return scope_ms_per_iter(r, "sweep.ax")
