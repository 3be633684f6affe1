"""Device milliseconds per iteration in the XLA gather fusions of the
objective sweep: lambda read at each edge's destination, x read at the
AxPlan's edge positions, and the final row assembly.  The TPU lowers each
gather to a custom fusion (`kind=kCustom` in the op's HLO text); the
sweep has no other custom fusion (moves `iter_ms`)."""
from bench.lib.readers import op_seconds, per_iteration_ms


def read(r):
    return per_iteration_ms(
        r, op_seconds(r, lambda name: "kind=kCustom" in name))
