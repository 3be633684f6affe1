"""Device-busy milliseconds per iteration in the traced window: the
objective sweep and the update (moves `iter_ms`)."""
from bench.lib.readers import per_iteration_ms


def read(r):
    return per_iteration_ms(r, r.get("busy_s"))
