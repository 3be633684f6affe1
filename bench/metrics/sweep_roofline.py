"""Share of the HBM roofline the iteration reaches: the least bytes an
iteration must move (`bench/lib/bytes.py`) at the chip's peak bandwidth,
over the device-busy time per iteration (moves `iter_ms`)."""
from bench.lib import bytes as least, peaks
from bench.lib.readers import per_iteration_ms


def read(r):
    ms = per_iteration_ms(r, r.get("busy_s"))
    if not ms or "edges" not in r:
        return None
    need = least.least_bytes_per_iteration(
        r["edges"], r["sources"], r["destinations"], r["families"])
    floor_s = need / peaks.peak(r["device_kind"], "hbm_bytes_per_s")
    return 100.0 * floor_s / (ms * 1e-3)
