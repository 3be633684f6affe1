"""The readers of the program's own annotations (`bench/lib/annotations.py`)
on synthetic readings, and the trace reduction with the program's spans in
the trace."""
from __future__ import annotations

import pytest

import repro.obs as obs
from bench.lib import annotations, trace
from repro.obs import Telemetry, note_op_scopes

HLO = """HloModule jit_run
  %fusion.7 = f32[64]{0} fusion(f32[8]{0} %p), kind=kCustom, metadata={op_name="jit(solve_chunk)/while/body/update/sweep/sweep.lambda_gather/gather"}
  %fusion.8 = f32[64]{0} fusion(f32[64]{0} %fusion.7), kind=kLoop, metadata={op_name="jit(solve_chunk)/while/body/update/sweep/sweep.project/jit(project_boxcut)/while/body/add"}
  %fusion.9 = f32[8]{0} fusion(f32[64]{0} %fusion.8), kind=kCustom, metadata={op_name="jit(solve_chunk)/while/body/update/sweep/shard_map/sweep.ax/gather"}
  ROOT %add.3 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b), metadata={op_name="jit(solve_chunk)/while/body/update/add"}
  %while.1 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(solve_chunk)/while"}
"""


class _Compiled:
    def as_text(self):
        return HLO


def _readings():
    return {"window_iterations": 4, "op_seconds": {
        "%fusion.7 = f32[64]{0:T(1024)} fusion(f32[8]{0} %p), kind=kCus": 0.2,
        "%fusion.8 = f32[64]{0:T(1024)} fusion(f32[64]{0} %fusion.7)": 0.04,
        "%fusion.9 = f32[8]{0:T(1024)} fusion(f32[64]{0} %fusion.8)": 0.4,
        "%add.3 = f32[8]{0} add(f32[8]{0} %a, f32[8]{0} %b)": 0.008,
        "%while.1 = (s32[]) while(%t)": 0.1}}


@pytest.mark.parametrize("scope,ms", [("sweep.lambda_gather", 50.0),
                                      ("sweep.project", 10.0),
                                      ("sweep.ax", 100.0), ("update", 2.0)])
def test_scope_time_per_iteration(scope, ms):
    note_op_scopes(_Compiled())
    assert obs.op_scopes()["%fusion.9"] == "sweep.ax"
    assert "%while.1" not in obs.op_scopes()
    assert annotations.scope_ms_per_iter(_readings(), scope) == \
        pytest.approx(ms)


def test_scope_reads_nothing_without_the_program_table(monkeypatch):
    note_op_scopes(_Compiled())
    assert annotations.scope_ms_per_iter({}, "sweep.ax") is None
    r = _readings()
    assert annotations.scope_ms_per_iter(r, "sweep.collective") is None
    monkeypatch.delattr(obs, "op_scopes")
    assert annotations.scope_ms_per_iter(r, "sweep.ax") is None


def test_build_span_seconds(monkeypatch):
    name = "build.test_annotations"
    assert annotations.build_span_s({"build_s": 1.0}, name) is None
    with Telemetry.disabled().span(name):
        pass
    with Telemetry.disabled().span(name):
        pass
    seconds, count = obs.span_totals()[name]
    assert count == 2
    assert annotations.build_span_s({"build_s": 1.0}, name) == seconds
    assert annotations.build_span_s({}, name) is None
    monkeypatch.delattr(obs, "span_totals")
    assert annotations.build_span_s({"build_s": 1.0}, name) is None


def _ev(name, s, e):
    return (name, s, e)


def test_program_spans_leave_the_device_readings_unchanged():
    ops = [_ev("a", 0.0, 1.0), _ev("b", 3.5, 5.5), _ev("loop", 8.0, 9.0),
           _ev("c", 8.2, 8.4)]
    host = [_ev("np.asarray", 1.2, 3.4), _ev("PjitFunction", 5.6, 7.9)]
    spans = [_ev("repro.solve", 0.0, 10.0), _ev("repro.execute", 2.0, 3.0),
             _ev("repro.host", 3.0, 6.0), _ev("repro.control", 6.0, 7.0)]
    plain = trace.reduce([ops], host, (0.0, 11.0))
    annotated = trace.reduce([ops], host + spans, (0.0, 11.0))
    assert annotated.busy_s == plain.busy_s
    assert annotated.op_seconds == plain.op_seconds
    assert annotated.readings() == plain.readings()
    assert annotated.breakdown()["device_ops"] == \
        plain.breakdown()["device_ops"]
    # a gap is still named after the host event overlapping it most, now
    # an engine span: here the whole solve's, which covers every gap
    assert sum(annotated.idle_by_host.values()) == \
        pytest.approx(sum(plain.idle_by_host.values()))
    assert annotated.idle_by_host == {"repro.solve": pytest.approx(7.0)}
