"""Per-layer readings from the program's own annotations, read in the
run's process once the run is over.

The program keeps two process-wide tables (`repro.obs`): `span_totals()`,
the host seconds of every span it closed, by name (the instance build's
`build.*` steps among them), and `op_scopes()`, the innermost solve-loop
scope (`sweep.lambda_gather`, `sweep.project`, `sweep.ax`, ...) of every
instruction of the programs its engine compiled.  On a TPU the profiler's
`XLA Ops` events carry the instruction's text and their timing only (no
op-name stat), so the scope of a device op is found by its instruction
name, the `%name` its event name starts with.

A program without these tables reads as nothing: each function returns
None, as every reader does when the run holds nothing for it.
"""
from __future__ import annotations

from typing import Optional

from .readers import per_iteration_ms


def _table(name: str):
    try:
        import repro.obs as obs
    except ImportError:
        return None
    return getattr(obs, name, None)


def scope_ms_per_iter(r: dict, scope: str) -> Optional[float]:
    """Device milliseconds per window iteration in the ops whose innermost
    scope is `scope`; None when no op of the window has it."""
    op_scopes = _table("op_scopes")
    if op_scopes is None or not r.get("op_seconds"):
        return None
    scopes = op_scopes()
    seconds = [s for name, s in r["op_seconds"].items()
               if scopes.get(name.split(" = ", 1)[0]) == scope]
    return per_iteration_ms(r, sum(seconds)) if seconds else None


def build_span_s(r: dict, name: str) -> Optional[float]:
    """Host seconds of the build step `name` (a `build.*` span) in a run
    that built the program; None when the program keeps no such span."""
    span_totals = _table("span_totals")
    if span_totals is None or "build_s" not in r:
        return None
    seconds = span_totals().get(name)
    return seconds[0] if seconds else None
