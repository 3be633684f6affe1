"""Share of the traced window in which no operation ran on the device
(moves `iter_ms`)."""
from bench.lib.readers import idle_share_pct


def read(r):
    return idle_share_pct(r)
