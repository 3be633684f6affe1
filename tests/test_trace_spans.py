"""Engine spans and sweep scopes on the profiler's clock (DESIGN.md §11).

  * every telemetry span, the disabled recorder's included, is a
    `repro.<name>` TraceMe in a `jax.profiler` trace: the chunked solve's
    `solve` span holds one `execute`, `host` and `control` per chunk, each
    properly nested;
  * the chunk runner's program carries the sweep's op-name scopes;
  * an active profiler trace leaves the trajectory bitwise unchanged;
  * `span_totals()` counts the instance build's `build.*` steps, and
    `op_scopes()` names the compiled runner's instructions by scope.
"""
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (InstanceSpec, MatchingObjective, Maximizer,
                        SolveConfig, StoppingCriteria, generate, precondition)
from repro.core.distributed import DistributedMatchingObjective, place_lp
from repro.core.instance import build_sharded_ax_plan, pack_slabs
from repro.core.maximizer import (SolveEngine, _make_chunk_runner,
                                  hoist_constants)
from repro.launch.mesh import make_mesh
from repro.obs import Telemetry, span_totals

CFG = SolveConfig(iterations=60, gamma=0.1, max_step=10.0,
                  initial_step=1e-3)
CRIT = StoppingCriteria(tol_grad_norm=0.0, check_every=20)
SCOPES = ("sweep.lambda_gather", "sweep.project", "sweep.ax", "update")


@pytest.fixture(scope="module")
def lp():
    spec = InstanceSpec(num_sources=30, num_destinations=8,
                        avg_nnz_per_row=10, seed=3)
    lp = jax.tree.map(jnp.asarray, generate(spec))
    return precondition(lp, row_norm=True)[0]


def _traced(tmp_path, fn):
    """Run `fn` under a profiler trace; its result and the host events
    named `repro.*`, as (name, start ns, end ns) sorted by start."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
        jax.block_until_ready(out.lam)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = [(e.name, e.start_ns, e.end_ns)
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name.startswith("repro.")]
    return out, sorted(events, key=lambda ev: (ev[1], -ev[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_disabled_spans_reach_the_profiler_nested(lp, tmp_path):
    obj = MatchingObjective(lp)
    eng = SolveEngine(obj.calculate, CFG)
    lam0 = jnp.zeros(obj.dual_shape, jnp.float32)
    eng.solve(lam0, criteria=CRIT)           # compile outside the trace
    res, events = _traced(tmp_path, lambda: eng.solve(lam0, criteria=CRIT))
    chunks = len(res.diagnostics)
    assert chunks == 3
    by = {}
    for ev in events:
        by.setdefault(ev[0], []).append(ev)
    solve, = by["repro.solve"]
    assert len(by["repro.start"]) == len(by["repro.finish"]) == 1
    for name in ("repro.execute", "repro.host", "repro.control"):
        assert len(by[name]) == chunks, name
    for ev in events:
        assert _inside(ev, solve)
    # per chunk: execute, then host, then control, none overlapping
    loop = [ev for ev in events if ev[0] in ("repro.execute", "repro.host",
                                             "repro.control")]
    assert [ev[0] for ev in loop] == ["repro.execute", "repro.host",
                                      "repro.control"] * chunks
    for a, b in zip(loop, loop[1:]):
        assert a[2] <= b[1]
    assert by["repro.start"][0][2] <= loop[0][1]
    assert loop[-1][2] <= by["repro.finish"][0][1]


def test_fixed_length_path_spans(lp, tmp_path):
    obj = MatchingObjective(lp)
    mx = Maximizer(CFG)
    mx.maximize(obj)
    _, events = _traced(tmp_path, lambda: mx.maximize(obj))
    names = [ev[0] for ev in events]
    assert names == ["repro.solve", "repro.start", "repro.execute",
                     "repro.finish"]
    assert all(_inside(ev, events[0]) for ev in events)


def test_trajectory_bitwise_identical_under_an_active_trace(lp, tmp_path):
    obj = MatchingObjective(lp, ax_mode="aligned")
    plain = Maximizer(CFG).maximize(obj, criteria=CRIT)
    traced, _ = _traced(tmp_path, lambda: Maximizer(CFG).maximize(
        obj, criteria=CRIT))
    np.testing.assert_array_equal(np.asarray(plain.lam),
                                  np.asarray(traced.lam))
    for a, b in zip(plain.stats, traced.stats):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert plain.iterations_run == traced.iterations_run


def _scopes_in_runner(calculate, lam0):
    eng = SolveEngine(calculate, CFG)
    state = eng.rule.init_state(lam0, CFG)
    hoisted, consts = hoist_constants(calculate, state.lam, jnp.float32(0.0))
    run = _make_chunk_runner(hoisted, CFG, eng.rule, 5, True)
    text = run.lower(state, jnp.float32(0.1), consts).as_text(
        debug_info=True)
    return set(re.findall(r"(?:^|/|\")(sweep\.\w+|update)(?=/)", text,
                          flags=re.M))


@pytest.mark.parametrize("ax_mode", ["scatter", "sorted", "aligned",
                                     "aligned_gvals"])
def test_runner_program_carries_the_sweep_scopes(lp, ax_mode):
    obj = MatchingObjective(lp, ax_mode=ax_mode)
    found = _scopes_in_runner(obj.calculate,
                              jnp.zeros(obj.dual_shape, jnp.float32))
    assert set(SCOPES) <= found, found
    assert "sweep.collective" in found


def test_distributed_runner_program_carries_the_sweep_scopes(lp):
    mesh = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    axes = tuple(mesh.axis_names)
    obj = DistributedMatchingObjective(lp=place_lp(lp, mesh, axes),
                                       mesh=mesh, source_axes=axes,
                                       ax_mode="aligned")
    found = _scopes_in_runner(obj.calculate,
                              jnp.zeros(obj.dual_shape, jnp.float32))
    assert set(SCOPES) | {"sweep.collective"} <= found, found


def test_span_totals_count_the_build_steps():
    spec = InstanceSpec(num_sources=40, num_destinations=6,
                        avg_nnz_per_row=5, seed=1)
    from repro.core.instance import _coefficients, _edges
    src, dst = _edges(spec)
    value, a = _coefficients(spec, src, dst)
    names = ("build.pack", "build.precondition", "build.place",
             "build.ax_plan")
    before = span_totals()
    lp = pack_slabs(src, dst, value, a, spec)
    lp = precondition(jax.tree.map(jnp.asarray, lp), row_norm=True)[0]
    mesh = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    lp = place_lp(lp, mesh, tuple(mesh.axis_names))
    build_sharded_ax_plan(lp, 1)
    after = span_totals()
    for name in names:
        n0 = before.get(name, (0.0, 0))[1]
        assert after[name][1] == n0 + 1, name
        assert after[name][0] >= before.get(name, (0.0, 0))[0]


def test_span_totals_count_every_recorder():
    name = "test.span_totals"
    n0 = span_totals().get(name, (0.0, 0))[1]
    with Telemetry.disabled().span(name):
        pass
    with Telemetry(stream=open("/dev/null", "w")).span(name):
        pass
    assert span_totals()[name][1] == n0 + 2


def test_op_scopes_name_the_compiled_runner_ops(lp):
    from repro.obs import op_scopes
    obj = MatchingObjective(lp, ax_mode="aligned")
    Maximizer(CFG).maximize(obj, criteria=CRIT)
    found = set(op_scopes().values())
    assert set(SCOPES) | {"sweep", "sweep.collective"} <= found, found
    assert all(name.startswith("%") for name in op_scopes())


def test_dual_tile_kernel_is_noted_under_sweep_ax(lp, monkeypatch):
    """The dual-tile Ax kernel's instructions carry the `sweep.ax` scope
    into the compiled runner, so `ax_ms_per_iter` keeps reading the Ax
    on that path.  The objective takes the dual tile only on a TPU: the
    test steers it there (on the CPU the kernel runs in interpret mode)."""
    from repro.core import objectives
    from repro.obs import note_op_scopes, op_scopes
    monkeypatch.setattr(objectives, "platform_of", lambda tree: "tpu")
    obj = MatchingObjective(lp, ax_mode="aligned")
    assert obj.ax_path == "dual_tile"
    eng = SolveEngine(obj.calculate, CFG)
    state = eng.rule.init_state(jnp.zeros(obj.dual_shape, jnp.float32), CFG)
    hoisted, consts = hoist_constants(obj.calculate, state.lam,
                                      jnp.float32(0.0))
    compiled = _make_chunk_runner(hoisted, CFG, eng.rule, 5, True).lower(
        state, jnp.float32(0.1), consts).compile()
    note_op_scopes(compiled)
    kernel = set(re.findall(
        r'^\s*(?:ROOT\s+)?(%[^\s=]+) = [^\n]*op_name="[^"\n]*'
        r'jit\(ax_dual_tile\)', compiled.as_text(), flags=re.M))
    assert kernel
    scopes = op_scopes()
    assert {scopes.get(name) for name in kernel} == {"sweep.ax"}
