"""Device milliseconds per iteration in the ops of the `sweep.lambda_gather`
scope: lambda read at each edge's destination and the a-lambda contraction
(moves `iter_ms`)."""
from bench.lib.annotations import scope_ms_per_iter


def read(r):
    return scope_ms_per_iter(r, "sweep.lambda_gather")
