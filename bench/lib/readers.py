"""Arithmetic shared by the per-layer metric readers in `bench/metrics/`.

A reader gets the readings of one traced run (the driver's counters and
host timings, plus the trace reduction) and returns its number, or None
when the run holds nothing for it to read: a share is never reported as 0
for want of a reading.
"""
from __future__ import annotations

from typing import Optional


def idle_share_pct(r: dict) -> Optional[float]:
    if "idle_share" not in r:
        return None
    return 100.0 * r["idle_share"]


def per_iteration_ms(r: dict, seconds: Optional[float]) -> Optional[float]:
    if seconds is None or not r.get("window_iterations"):
        return None
    return 1e3 * seconds / r["window_iterations"]


def op_seconds(r: dict, match) -> Optional[float]:
    """Device seconds of the ops whose name `match` accepts; None when no
    op matches."""
    ops = {k: v for k, v in r.get("op_seconds", {}).items() if match(k)}
    return sum(ops.values()) if ops else None
