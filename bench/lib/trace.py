"""Reduction of a profiler trace (`.xplane.pb`) to the benchmark's numbers.

The window is the host annotation the harness opens and closes around the
timed work.  On each device plane the operations of the `XLA Ops` line are
clipped to the window; their union is the time the device was busy, and
the idle share is one minus busy over the window.  Operations nest on that
line (a `lax.scan` is one `while` op around the ops of its body), so time
by operation is self time: an op's duration less that of the ops nested
in it.  Each idle gap is attributed to what the host was doing in it: of
the host events that overlap the gap, the one that overlaps it most (the
innermost on a tie).

On a TPU, the trace names each operation by its HLO text; XLA lowers a
gather to a custom fusion, whose text holds `kind=kCustom`.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
from typing import Dict, List, Sequence, Tuple

import numpy as np

Interval = Tuple[float, float]
Event = Tuple[str, float, float]     # (name, start s, end s)

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PREFIX = "/host:"
TOP = 10
SHORT_GAP_S = 10e-6     # gaps shorter than this are not attributed
SHORT_GAP = "between device ops (< 10 us)"
NAME_CHARS = 160        # op names are whole HLO instructions


def union(start: np.ndarray, end: np.ndarray) -> List[Interval]:
    """Merge overlapping intervals; sorted, disjoint."""
    if not len(start):
        return []
    order = np.argsort(start, kind="stable")
    s, e = start[order], end[order]
    reach = np.maximum.accumulate(e)
    new = np.r_[True, s[1:] > reach[:-1]]
    first = np.flatnonzero(new)
    last = np.r_[first[1:] - 1, len(s) - 1]
    return list(zip(s[first].tolist(), reach[last].tolist()))


def gaps(busy: Sequence[Interval], window: Interval) -> List[Interval]:
    """The parts of `window` that no interval of `busy` (disjoint, sorted)
    covers."""
    out, cur = [], window[0]
    for s, e in busy:
        if s > cur:
            out.append((cur, min(s, window[1])))
        cur = max(cur, e)
        if cur >= window[1]:
            break
    if cur < window[1]:
        out.append((cur, window[1]))
    return [(s, e) for s, e in out if e > s]


def self_times(start: np.ndarray, end: np.ndarray) -> np.ndarray:
    """Self seconds of nested intervals: each one's length less that of the
    intervals directly inside it."""
    own = end - start
    stack: List[int] = []
    order = np.lexsort((-end, start))
    for i in order.tolist():
        s = start[i]
        while stack and end[stack[-1]] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= end[i] - s
        stack.append(i)
    return own


class HostEvents:
    """Host events as arrays, for attributing gaps."""

    def __init__(self, events: Sequence[Event]):
        self.names = [ev[0] for ev in events]
        self.start = np.asarray([ev[1] for ev in events], np.float64)
        self.end = np.asarray([ev[2] for ev in events], np.float64)

    def attribute(self, gap: Interval) -> str:
        """What the host was doing during `gap`: the host event overlapping
        it most, the shortest on a tie; "host idle" when none does."""
        if not self.names:
            return "host idle"
        ov = np.minimum(self.end, gap[1]) - np.maximum(self.start, gap[0])
        if ov.max() <= 0:
            return "host idle"
        best = np.flatnonzero(ov == ov.max())
        return self.names[best[np.argmin((self.end - self.start)[best])]]


@dataclasses.dataclass
class Reduction:
    window: Interval
    busy_s: float                       # mean over the devices used
    op_seconds: Dict[str, float]        # device self time by op, mean
    idle_by_host: Dict[str, float]      # idle seconds by host activity
    devices: int

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    def readings(self) -> Dict[str, float]:
        return {"busy_s": self.busy_s, "trace_window_s": self.window_s,
                "idle_share": 1.0 - self.busy_s / self.window_s,
                "op_seconds": dict(self.op_seconds)}

    def breakdown(self) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:TOP]
        idle = sorted(self.idle_by_host.items(), key=lambda kv: -kv[1])[:TOP]
        return {"device_ops": [[k[:NAME_CHARS], v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in idle]}


def reduce(device_ops: Sequence[Sequence[Event]], host: Sequence[Event],
           window: Interval) -> Reduction:
    """The reduction from per-device op events and host events, all
    (name, start, end) in seconds on one clock."""
    used = [ops for ops in device_ops if ops]
    if not used:
        raise ValueError("the trace holds no device operation")
    busy, by_op, idle = 0.0, collections.Counter(), collections.Counter()
    host_ev = HostEvents([ev for ev in host
                          if ev[2] > window[0] and ev[1] < window[1]])
    for d, ops in enumerate(used):
        names, ids = {}, []
        for ev in ops:
            ids.append(names.setdefault(ev[0], len(names)))
        start = np.maximum(np.asarray([ev[1] for ev in ops]), window[0])
        end = np.minimum(np.asarray([ev[2] for ev in ops]), window[1])
        keep = end > start
        start, end, ids = start[keep], end[keep], np.asarray(ids)[keep]
        merged = union(start, end)
        busy += sum(e - s for s, e in merged)
        per = np.bincount(ids, weights=self_times(start, end),
                          minlength=len(names))
        for name, i in names.items():
            if per[i] > 0:
                by_op[name] += float(per[i])
        if d == 0:
            for g in gaps(merged, window):
                label = (host_ev.attribute(g) if g[1] - g[0] >= SHORT_GAP_S
                         else SHORT_GAP)
                idle[label] += g[1] - g[0]
    n = len(used)
    return Reduction(window=window, busy_s=busy / n,
                     op_seconds={k: v / n for k, v in by_op.items()},
                     idle_by_host=dict(idle), devices=n)


def load(path: str, window_name: str):
    """(device_ops, host_events, window) from one `.xplane.pb` file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops, host, window = [], [], None
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                               for e in line.events)
            device_ops.append((plane.name, evs))
        elif plane.name.startswith(HOST_PREFIX):
            for line in plane.lines:
                for e in line.events:
                    ev = (e.name, e.start_ns * 1e-9, e.end_ns * 1e-9)
                    if e.name != window_name:
                        host.append(ev)
                    elif window is None:
                        window = ev[1:]
    if window is None:
        raise ValueError(f"no {window_name!r} annotation in {path}")
    device_ops.sort(key=lambda kv: kv[0])
    return [evs for _, evs in device_ops], host, window


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_dir(trace_dir: str, window_name: str) -> Reduction:
    return reduce(*load(find_xplane(trace_dir), window_name))
