"""Maximizer — dual ascent of g(λ) over λ >= 0 (paper §5, Appendix B).

`AGDMaximizer` follows DuaLip's `AcceleratedGradientDescent.scala` semantics,
translated to JAX (paper Appendix B "Optimization algorithm"):

  * Nesterov acceleration with the classic (k−1)/(k+2) momentum on the
    projected iterate;
  * a running local-Lipschitz estimate  L̂ = ‖∇g(y_k) − ∇g(y_{k−1})‖ /
    ‖y_k − y_{k−1}‖  used to set the step 1/L̂ each iteration;
  * the step is capped at `max_step` (paper default 1e-3) and starts at
    `initial_step` (1e-5) — the cap is the robustness/speed balance the
    paper calls out as critical;
  * γ continuation (§5.1): γ starts at `gamma_init` and is multiplied by
    `gamma_decay_rate` every `gamma_decay_every` iterations until it reaches
    the target γ; the step cap is scaled ∝ γ across transition points.

The solve loop is convergence-controlled (DESIGN.md §4): the hot path is an
inner jitted `lax.scan` of `check_every` steps (one XLA program), wrapped by
a host-side controller that evaluates the composable `StoppingCriteria`
(relative dual change, primal infeasibility, gradient norm, iteration /
wall-clock caps) at chunk boundaries and, with
`SolveConfig.adaptive_continuation`, decays γ on stall instead of on the
fixed schedule.  With no criteria set the engine runs ONE scan of the full
iteration count — bit-identical to the legacy fixed-length behavior.  The
update is *replicated* across shards in the distributed setting
(mathematically identical to the paper's rank-0-update-then-broadcast, see
DESIGN.md §2).

What one iteration *does* is pluggable: the engine resolves `algorithm`
through the UpdateRule registry (core/update_rules.py, DESIGN.md §10) at
construction and drives the rule's init-state / step / rollback-retry /
checkpoint hooks.  The step math itself — `agd_step`, `pga_step` and
friends, plus `gamma_at` / `max_step_at` / `initial_state` — lives in
`update_rules` and is re-exported here for compatibility.
"""
from __future__ import annotations

import math
import time
from collections import deque
from functools import partial
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.obs import Telemetry, note_op_scopes
from .types import (ConvergenceCheck, HealthConfig, HealthRecord, IterStats,
                    SolveConfig, SolveResult, SolveState, StopReason,
                    StoppingCriteria)
from .update_rules import (UpdateRule, agd_step, bb_step, gamma_at, get_rule,
                           initial_state, max_step_at, pdhg_step, pga_step,
                           rule_names, _lipschitz_update)

__all__ = ["SolveEngine", "Maximizer", "maximize", "gamma_at", "max_step_at",
           "agd_step", "pga_step", "pdhg_step", "bb_step", "initial_state",
           "get_rule", "rule_names", "UpdateRule"]


def _copy_state(state: SolveState) -> SolveState:
    """Fresh buffers for every leaf — donation-safe snapshot/restore."""
    return jax.tree.map(jnp.copy, state)


def _classify_chunk(health: HealthConfig, rule: UpdateRule,
                    state: SolveState, g: float,
                    infeas: float, grad_norm: float, gamma_cur: float,
                    snap_g: Optional[float], snap_grad: Optional[float],
                    snap_gamma: Optional[float]) -> Optional[str]:
    """Health verdict for one chunk: None = healthy, else the fault kind
    (DESIGN.md §9).  Scalar checks read the chunk's trailing stats; the
    sweep over the rule's `health_arrays` (λ/y by default) catches a NaN
    introduced by the *last* in-chunk update, which the (pre-update)
    trailing stats cannot see."""
    if not (math.isfinite(g) and math.isfinite(infeas)
            and math.isfinite(grad_norm)):
        return "nonfinite"
    if health.check_lambda:
        arrays = rule.health_arrays(state)
        ok = jnp.asarray(True)
        for a in arrays:
            ok = ok & jnp.isfinite(a).all()
        if not bool(jax.device_get(ok)):
            return "nonfinite"
    if (snap_grad is not None
            and grad_norm > health.grad_explosion * max(snap_grad, 1.0)):
        return "grad_explosion"
    # g legitimately moves when γ moves (continuation), so the regression
    # rule only applies between chunks that ended at the same γ
    if (snap_g is not None and snap_gamma is not None
            and gamma_cur == snap_gamma
            and g < snap_g - health.obj_regression_tol
            * max(1.0, abs(snap_g))):
        return "regression"
    return None


def hoist_constants(fn: Callable, *example_args):
    """Split `fn` into `(hoisted, consts)` with `hoisted(consts, *args)`
    equal to `fn(*args)`, where `consts` are the arrays `fn` closes over.

    `jax.jit` embeds every array a traced function closes over into the
    program as a literal.  An objective's `calculate` closes over the
    whole instance — gigabytes at production size — so the executable
    would carry a copy of it: minutes of compile, gigabytes of host
    memory per program, and no persistent-cache entry (too large).  Jit
    `hoisted` instead and pass `consts` as an argument; the arrays then
    stay where they are, on device, and the program holds only code.
    `example_args` fix the shapes `hoisted` is traced at.
    """
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*example_args)
    out_tree = jax.tree.structure(out_shape)

    def hoisted(consts, *args):
        out = jax.core.eval_jaxpr(closed.jaxpr, consts, *jax.tree.leaves(args))
        return jax.tree.unflatten(out_tree, out)

    return hoisted, list(closed.consts)


def _run_with(compiled, consts, state, gamma):
    return compiled(state, gamma, consts)


def _make_chunk_runner(calculate: Callable, config: SolveConfig,
                       rule: UpdateRule, length: int,
                       gamma_override: bool) -> Callable:
    """Jit one inner chunk: `length` steps as a single lax.scan.

    `calculate(consts, λ, γ)` is the objective with its instance hoisted
    out (`hoist_constants`); the runner is `solve_chunk(state, γ, consts)`.

    `gamma_override=False`: γ follows the scheduled continuation
    `gamma_at(config, it)` inside the scan (the iteration counter is carried
    in the state, so chunking does not perturb the schedule).
    `gamma_override=True`: γ is a traced scalar argument, constant within the
    chunk — the host controller drives it (adaptive stall-decay).

    Op-name scopes (DESIGN.md §11): the rule's step runs under `update`
    and the objective under `sweep`, whose stages carry their own
    `sweep.*` scopes; an op belongs to its innermost scope, so `update`
    holds exactly the step's work outside `calculate`.  Scopes are
    trace-time metadata: numerics and fusion are unchanged.  JAX's
    persistent compilation cache keys a program with that metadata
    stripped, so it may hand back an executable compiled under other
    scopes; the runner's name, part of the key, tells the scoped program
    (`solve_chunk`) apart from the unscoped one that preceded it.

    The incoming SolveState is *donated*: XLA aliases the carry buffers
    (λ, momentum, Lipschitz bookkeeping) into the outgoing state instead of
    double-buffering the dual state across chunk boundaries.  Donation is
    pure memory plumbing — the chunked trajectory stays bit-identical
    (tests/test_stopping.py).  Callers must not reuse a state they passed
    in; `SolveEngine.solve` therefore hands the runner a private copy of
    the initial state (whose leaves also alias each other — λ0 appears as
    lam/y/lam_prev/y_prev — and duplicate donation of one buffer is an
    error).
    """
    def solve_chunk(state, gamma, consts):
        def calc(lam, g):
            with jax.named_scope("sweep"):
                return calculate(consts, lam, g)

        if gamma_override:
            gamma = jnp.asarray(gamma, jnp.float32)
            gamma_fn = lambda st: gamma  # noqa: E731
        else:  # scheduled mode: γ comes from the carried counter
            gamma_fn = lambda st: gamma_at(config, st.it)  # noqa: E731

        def step_fn(state, xs):
            with jax.named_scope("update"):
                return rule.step(calc, config, gamma_fn, state, xs)
        return jax.lax.scan(step_fn, state, None, length=length)
    return jax.jit(solve_chunk, donate_argnums=(0,))


class SolveEngine:
    """The one convergence-controlled solve loop (DESIGN.md §4).

    All entry points — the free `maximize()`, the `Maximizer` facade, and
    `solve_distributed` — route through this engine.  It owns a cache of
    jitted chunk runners keyed by (chunk length, γ mode), so a
    tolerance-driven solve compiles exactly one `check_every`-step XLA
    program (plus at most one shorter final-remainder chunk) and reuses it
    across chunks and across repeat solves.

    Host/device contract per chunk: the SolveState (λ, momentum, step
    bookkeeping) stays on device for the whole solve; what crosses to the
    host at a chunk boundary is the chunk's IterStats — per-iteration
    *scalars* — and, in adaptive-continuation mode, one γ scalar goes the
    other way.  λ is only fetched by the caller after the solve ends.
    """

    def __init__(self, calculate: Callable, config: SolveConfig,
                 algorithm: str = "agd"):
        self.calculate = calculate
        self.config = config
        self.algorithm = algorithm
        # construction-time fail-fast: a typo'd algorithm used to surface
        # as a bare KeyError from inside the jit plumbing on first solve
        self.rule = get_rule(algorithm)
        self._runners = {}
        # Chaos-testing seam (DESIGN.md §9): when set, called after every
        # chunk as `hook(it_start, state, stats) -> (state, stats)` so a
        # fault-injection harness can poison the state exactly as a
        # transient device fault would.  Never set in production.
        self.chunk_fault_hook = None

    def _runner(self, length: int, gamma_override: bool, state: SolveState,
                gamma: jax.Array,
                tel: Telemetry = Telemetry.disabled(),
                sampler=None) -> Callable:
        """Return the ahead-of-time-compiled chunk executable for this
        (length, γ-mode, state-layout) key, building it on first use.

        AOT (`jit(...).lower(args).compile()`) runs the exact pipeline the
        jit call path runs — same lowering, same executable, bit-identical
        outputs (asserted in tests/test_telemetry.py) — but makes the
        trace and XLA-compile phases explicit, so telemetry can attribute
        them as `trace`/`compile` spans instead of folding them invisibly
        into the first chunk's wall time.  The state avals key the cache
        the way jit's own cache would (a resumed state or a differently-
        shaped λ recompiles instead of tripping an AOT aval mismatch).
        """
        key = (length, gamma_override,
               tuple((leaf.shape, str(leaf.dtype))
                     for leaf in jax.tree.leaves(state)))
        run = self._runners.get(key)
        if run is None:
            with tel.span("trace", chunk_len=length):
                calculate, consts = hoist_constants(
                    self.calculate, state.lam, jnp.float32(0.0))
                fn = _make_chunk_runner(calculate, self.config, self.rule,
                                        length, gamma_override)
                lowered = fn.lower(state, gamma, consts)
            with tel.span("compile", chunk_len=length):
                compiled = lowered.compile()
            note_op_scopes(compiled)
            if sampler is not None:
                # per-runner static memory estimate (memory_analysis or the
                # hlo_cost census), folded into the run's compiled peak
                # (DESIGN.md §13)
                from repro.obs.memory import compiled_memory_estimate
                est = compiled_memory_estimate(compiled)
                if est:
                    sampler.note_compiled(est)
            run = partial(_run_with, compiled, consts)
            self._runners[key] = run
        return run

    def solve(self, lam0: Optional[jax.Array],
              criteria: Optional[StoppingCriteria] = None,
              diagnostics_fn: Optional[Callable] = None,
              infeas_scale: float = 1.0,
              health: Optional[HealthConfig] = None,
              checkpoint_fn: Optional[Callable] = None,
              preempt_fn: Optional[Callable] = None,
              initial_state: Optional[SolveState] = None,
              resume_meta: Optional[dict] = None,
              telemetry: Optional[Telemetry] = None,
              profiler=None, sampler=None) -> SolveResult:
        """Run the solve loop (DESIGN.md §4; fault tolerance §9;
        telemetry §11; resource sampling §13).

        Beyond the criteria/diagnostics contract:

          health         HealthConfig enabling the per-chunk health guard
                         (NaN/divergence detection → rollback + backoff →
                         StopReason.DIVERGED on exhausted retries);
          checkpoint_fn  `fn(it, state, meta)` called after every healthy
                         chunk and once more at exit (`meta["final"]=True`)
                         — the hook decides its own cadence and must
                         consume `state` before returning (the buffers are
                         donated into the next chunk).  `meta` carries
                         exactly what `resume_meta` needs;
          preempt_fn     `fn() -> bool` polled at every chunk boundary; True
                         stops the loop with StopReason.PREEMPTED;
          initial_state  a restored SolveState (checkpoint resume): the
                         loop continues the trajectory from state.it —
                         bit-identical at chunk boundaries to a run that
                         was never interrupted;
          resume_meta    the `meta` dict the checkpoint hook was given
                         (keys "gamma_now", "g_prev"), restoring the
                         adaptive-continuation controller variables.

          telemetry      a `repro.obs.Telemetry`; the engine emits
                         solve_start/solve_end brackets, the phase spans
                         below, `check`/`gamma`/`health`/`checkpoint`
                         events at the existing seams, and chunk/
                         iteration counters.  Defaults to the disabled
                         no-op — the untelemetered trajectory is bitwise
                         identical (tests/test_telemetry.py), and its
                         spans still annotate a profiler trace;
          profiler       a `repro.obs.ProfilerHook` tracing a window of
                         chunks via jax.profiler (stopped in a finally
                         block, so an aborted solve still flushes);
          sampler        a `repro.obs.MemorySampler`; the engine samples
                         at every chunk boundary (one schema-validated
                         `memory` event each: host RSS, device allocator
                         bytes where available, watermark highs), folds
                         per-runner compiled-memory estimates into the
                         run peak, and stamps `sampler.watermarks()`
                         into the manifest at solve end.  Defaults to
                         None — zero reads, zero events, the unsampled
                         trajectory is bitwise identical
                         (tests/test_memory_obs.py).

        Phase spans (DESIGN.md §11): `solve` covers the call; inside it
        `start` (state init, the first preempt poll), then per chunk
        `execute` (runner lookup with its `trace`/`compile` spans on a
        build, the γ upload, the dispatch), `host` (the read-back of the
        chunk's four scalars) and `control` (everything after it up to the
        next chunk: health guard, stopping rules, γ controller, hooks, the
        next preempt poll), and `finish` (the stats and the result).  They
        tile the loop: no engine work runs outside them.

        Any of health/checkpoint_fn/preempt_fn/initial_state forces the
        chunked path; with none of them and no criteria the fixed-length
        single-scan fast path is bit-identical to the legacy engine.
        """
        tel = telemetry if telemetry is not None else Telemetry.disabled()
        with tel.span("solve"):
            return self._solve(
                tel, lam0, criteria, diagnostics_fn, infeas_scale, health,
                checkpoint_fn, preempt_fn, initial_state, resume_meta,
                profiler, sampler)

    def _start_state(self, tel, lam0, initial_state, total, chunked,
                     adaptive) -> SolveState:
        """The solve's private initial state; emits `solve_start`, and
        the objective's `ax_path` into the run manifest.

        The chunk runners donate the state argument (buffer reuse across
        chunks — no double-buffered dual state).  The fresh initial state
        aliases lam0 into four leaves, and the caller may hold lam0 (warm
        starts) or a restored checkpoint: copy every leaf so donation
        never invalidates a caller buffer nor donates one buffer twice.
        """
        config = self.config
        if initial_state is not None:
            state = _copy_state(initial_state)
        else:
            state = _copy_state(self.rule.init_state(lam0, config))
        # the objective's Ax path, where its sweep has a choice of two
        ax_path = getattr(getattr(self.calculate, "__self__", None),
                          "ax_path", None)
        if ax_path is not None:
            tel.manifest(ax_path=ax_path)
        tel.event("solve_start", algorithm=self.algorithm,
                  iterations_cap=total, chunked=chunked,
                  start_it=(int(jax.device_get(initial_state.it))
                            if initial_state is not None else 0),
                  gamma=config.gamma, gamma_init=config.gamma_init,
                  adaptive_continuation=adaptive)
        return state

    def _solve(self, tel, lam0, criteria, diagnostics_fn, infeas_scale,
               health, checkpoint_fn, preempt_fn, initial_state,
               resume_meta, profiler, sampler) -> SolveResult:
        config = self.config
        total = config.iterations
        if criteria is not None and criteria.max_iterations is not None:
            total = criteria.max_iterations
        adaptive = (config.adaptive_continuation
                    and config.gamma_init is not None
                    and config.gamma_init > config.gamma)
        guarded = (health is not None or checkpoint_fn is not None
                   or preempt_fn is not None or initial_state is not None)
        chunked = (guarded or
                   (total > 0 and
                    (adaptive
                     or (criteria is not None and criteria.needs_checks))))
        if not chunked:
            # Fixed-length path: ONE scan of the full count — bit-identical
            # to the legacy engine, no host round-trips.
            with tel.span("start"):
                state = self._start_state(tel, lam0, initial_state, total,
                                          chunked, adaptive)
                gamma_dev = jnp.asarray(config.gamma, jnp.float32)
            t0 = time.perf_counter()
            with tel.span("execute", chunk=0, it=0, n=total):
                run = self._runner(total, False, state, gamma_dev, tel,
                                   sampler)
                state, stats = run(state, gamma_dev)
                if tel.enabled:
                    jax.block_until_ready(stats.dual_obj)
            with tel.span("finish"):
                tel.counter("solve.chunks")
                tel.counter("solve.iterations", total)
                if sampler is not None:
                    s = sampler.sample(where="solve", it=total)
                    tel.event("memory", it=total, chunk=0,
                              **sampler.event_fields(s))
                    tel.manifest(**sampler.watermarks())
                tel.event("solve_end",
                          stop_reason=StopReason.MAX_ITERATIONS.value,
                          iterations_run=total, converged=False,
                          wall_s=time.perf_counter() - t0, checks=0,
                          health_incidents=0)
                return SolveResult(lam=state.lam, stats=stats,
                                   iterations_run=total, converged=False,
                                   stop_reason=StopReason.MAX_ITERATIONS,
                                   final_state=state)

        with tel.span("start"):
            state = self._start_state(tel, lam0, initial_state, total,
                                      chunked, adaptive)
            criteria = criteria if criteria is not None else StoppingCriteria()
            check = max(1, int(criteria.check_every))
            gamma_now = float(config.gamma_init) if adaptive else config.gamma
            g_prev = None
            it_done = 0
            if initial_state is not None:
                it_done = int(jax.device_get(initial_state.it))
                meta = resume_meta or {}
                if meta.get("gamma_now") is not None:
                    gamma_now = float(meta["gamma_now"])
                if meta.get("g_prev") is not None:
                    g_prev = float(meta["g_prev"])
            t0 = time.perf_counter()
            stats_chunks = []
            # keep-last diagnostics bound (SolveConfig.max_diagnostics): a
            # million-iteration solve with a small check_every must not
            # grow an unbounded host-side tuple; None (the default) keeps
            # everything
            diags = deque(maxlen=config.max_diagnostics)
            health_recs = []
            chunk_idx = 0
            converged = False
            stop_reason = StopReason.MAX_ITERATIONS
            # Health-guard bookkeeping: the last-good snapshot and its
            # baselines.  The snapshot is a private copy — the live state's
            # buffers are donated chunk over chunk, the snapshot's never
            # are.
            snap = _copy_state(state) if health is not None else None
            snap_it = it_done
            snap_gamma_now = gamma_now
            snap_g_prev = g_prev
            snap_g = None      # trailing dual objective of the last-good chunk
            snap_grad = None   # trailing ‖∇g‖ of the last-good chunk
            snap_gamma = None  # trailing γ of the last-good chunk
            fails = 0

            def _meta(final: bool) -> dict:
                meta = {"gamma_now": gamma_now, "g_prev": g_prev,
                        "it": it_done, "final": final}
                meta.update(self.rule.checkpoint_meta())
                return meta

            def _preempted() -> bool:
                """The poll before a chunk: True stops the loop."""
                return (it_done < total and preempt_fn is not None
                        and bool(preempt_fn()))

            preempted = _preempted()

        try:
            while it_done < total and not preempted:
                n = min(check, total - it_done)
                with tel.span("execute", chunk=chunk_idx, it=it_done, n=n):
                    gamma_arr = jnp.asarray(gamma_now, jnp.float32)
                    run = self._runner(n, adaptive, state, gamma_arr, tel,
                                       sampler)
                    if profiler is not None:
                        profiler.chunk_start(chunk_idx, tel)
                    state, stats = run(state, gamma_arr)
                    if tel.enabled:
                        # the dispatch is async; wait here so the execute
                        # span measures device compute, not queue depth
                        # (numerics untouched — pure synchronization)
                        jax.block_until_ready(stats.dual_obj)
                    if self.chunk_fault_hook is not None:
                        state, stats = self.chunk_fault_hook(it_done, state,
                                                             stats)

                # device→host: the chunk's trailing scalars (this is the
                # sync point that keeps the hot path a single XLA program
                # per chunk)
                with tel.span("host", chunk=chunk_idx, it=it_done):
                    g = float(stats.dual_obj[-1])
                    infeas = float(stats.infeas[-1])
                    grad_norm = float(stats.grad_norm[-1])
                    gamma_cur = float(stats.gamma[-1])

                with tel.span("control", chunk=chunk_idx, it=it_done):
                    elapsed = time.perf_counter() - t0
                    if profiler is not None:
                        profiler.chunk_end(chunk_idx, tel)
                    if sampler is not None:
                        # the chunk boundary is the host sync point — the
                        # one place a resource read can't perturb device
                        # pipelining
                        s = sampler.sample(where="chunk", it=it_done + n)
                        tel.event("memory", it=it_done + n, chunk=chunk_idx,
                                  **sampler.event_fields(s))
                    chunk_idx += 1
                    tel.counter("solve.chunks")

                    if health is not None:
                        status = _classify_chunk(
                            health, self.rule, state, g, infeas, grad_norm,
                            gamma_cur, snap_g, snap_grad, snap_gamma)
                        if status is not None:
                            fails += 1
                            scale = health.step_backoff ** fails
                            if fails > health.max_retries:
                                rec = HealthRecord(
                                    it=it_done + n, status=status,
                                    action="giveup", retries=fails,
                                    dual_obj=g, grad_norm=grad_norm,
                                    gamma=gamma_cur, rolled_back_to=snap_it,
                                    step_scale=scale)
                                health_recs.append(rec)
                                tel.event("health", **rec._asdict())
                                state = _copy_state(snap)
                                gamma_now = snap_gamma_now
                                g_prev = snap_g_prev
                                stop_reason = StopReason.DIVERGED
                                break
                            rec = HealthRecord(
                                it=it_done + n, status=status,
                                action="rollback", retries=fails,
                                dual_obj=g, grad_norm=grad_norm,
                                gamma=gamma_cur, rolled_back_to=snap_it,
                                step_scale=scale)
                            health_recs.append(rec)
                            tel.event("health", **rec._asdict())
                            tel.counter("solve.rollbacks")
                            state = self.rule.apply_backoff(
                                _copy_state(snap), config, snap_gamma_now,
                                scale)
                            if adaptive:
                                # γ backoff: retry under heavier
                                # regularization; the stall decay walks it
                                # back down afterwards
                                boosted = min(
                                    snap_gamma_now
                                    * health.gamma_backoff ** fails,
                                    float(config.gamma_init))
                                if boosted != gamma_now:
                                    tel.event("gamma", it=it_done,
                                              gamma_from=gamma_now,
                                              gamma_to=boosted,
                                              reason="health_backoff")
                                gamma_now = boosted
                            g_prev = snap_g_prev
                            # the bad chunk's stats are discarded; the
                            # iteration counter never advanced, so γ
                            # schedules rewind too
                            preempted = _preempted()
                            continue
                        fails = 0

                    it_done += n
                    tel.counter("solve.iterations", n)
                    stats_chunks.append(stats)
                    if g_prev is None:
                        rel_dual = (abs(g - float(stats.dual_obj[0]))
                                    / max(1.0, abs(g)) if n > 1
                                    else float("inf"))
                    else:
                        rel_dual = abs(g - g_prev) / max(1.0, abs(g))
                    g_prev = g

                    at_target = gamma_cur <= config.gamma * (1.0 + 1e-6)
                    stalled = rel_dual < config.gamma_stall_tol
                    if adaptive and not at_target and stalled:
                        decayed = max(gamma_now * config.gamma_decay_rate,
                                      config.gamma)
                        if decayed != gamma_now:
                            tel.event("gamma", it=it_done,
                                      gamma_from=gamma_now,
                                      gamma_to=decayed, reason="stall_decay")
                        gamma_now = decayed
                    rec = ConvergenceCheck(it=it_done, dual_obj=g,
                                           rel_dual=rel_dual,
                                           infeas=infeas, grad_norm=grad_norm,
                                           gamma=gamma_cur, elapsed=elapsed,
                                           stalled=stalled)
                    diags.append(rec)
                    tel.event("check", **rec._asdict())
                    if diagnostics_fn is not None:
                        diagnostics_fn(rec)
                    if health is not None:
                        snap = _copy_state(state)
                        snap_it = it_done
                        snap_gamma_now = gamma_now
                        snap_g_prev = g_prev
                        snap_g, snap_grad, snap_gamma = g, grad_norm, gamma_cur
                    if checkpoint_fn is not None:
                        with tel.span("checkpoint", it=it_done):
                            checkpoint_fn(it_done, state, _meta(final=False))
                        tel.event("checkpoint", it=it_done, final=False)

                    # tolerance checks only count once γ has reached its
                    # target: g and x*(λ) move with γ, so earlier
                    # "convergence" is spurious
                    if at_target and criteria.satisfied(
                            rel_dual, infeas, grad_norm, infeas_scale):
                        converged = True
                        stop_reason = StopReason.CONVERGED
                        break
                    if (criteria.max_seconds is not None
                            and elapsed >= criteria.max_seconds):
                        stop_reason = StopReason.MAX_SECONDS
                        break
                    preempted = _preempted()
        finally:
            if profiler is not None:
                # a solve that raises / diverges / preempts mid-window must
                # still flush a valid trace
                profiler.stop(tel)

        with tel.span("finish"):
            if preempted:
                stop_reason = StopReason.PREEMPTED
            if checkpoint_fn is not None:
                with tel.span("checkpoint", it=it_done):
                    checkpoint_fn(it_done, state, _meta(final=True))
                tel.event("checkpoint", it=it_done, final=True)
            if not stats_chunks:
                stats = IterStats(*(jnp.zeros((0,), jnp.float32)
                                    for _ in IterStats._fields))
            elif len(stats_chunks) == 1:
                stats = stats_chunks[0]
            else:
                stats = jax.tree.map(lambda *xs: jnp.concatenate(xs),
                                     *stats_chunks)
            if sampler is not None:
                # run-level peaks stamped into the manifest (the LAST
                # manifest record in a log carries the complete merged view)
                tel.manifest(**sampler.watermarks())
            tel.event("solve_end", stop_reason=stop_reason.value,
                      iterations_run=it_done, converged=converged,
                      wall_s=time.perf_counter() - t0, checks=len(diags),
                      health_incidents=len(health_recs))
            return SolveResult(lam=state.lam, stats=stats,
                               iterations_run=it_done, converged=converged,
                               stop_reason=stop_reason,
                               diagnostics=tuple(diags),
                               health=tuple(health_recs), final_state=state)


def _infeas_scale(obj, criteria: Optional[StoppingCriteria]) -> float:
    """1 + ‖b‖₂ for the relative infeasibility rule, when obj exposes an LP."""
    if criteria is None or criteria.tol_infeas_rel is None:
        return 1.0
    lp = getattr(obj, "lp", None)
    if lp is None:
        return 1.0
    return 1.0 + float(jnp.linalg.norm(lp.b))


def maximize(calculate: Callable, lam0: jax.Array, config: SolveConfig,
             algorithm: str = "agd",
             criteria: Optional[StoppingCriteria] = None,
             diagnostics_fn: Optional[Callable] = None,
             infeas_scale: float = 1.0,
             health: Optional[HealthConfig] = None,
             checkpoint_fn: Optional[Callable] = None,
             preempt_fn: Optional[Callable] = None,
             initial_state: Optional[SolveState] = None,
             resume_meta: Optional[dict] = None,
             telemetry: Optional[Telemetry] = None,
             profiler=None, sampler=None) -> SolveResult:
    """Thin wrapper over SolveEngine.  With no `criteria` this runs
    `config.iterations` steps as one jitted scan (the legacy fixed-length
    behavior, bit-identical); with criteria it is tolerance-terminated.
    The fault-tolerance hooks (health guard, checkpoint/preempt/resume —
    DESIGN.md §9) and the telemetry/profiler/sampler hooks (§11, §13)
    pass straight through to `SolveEngine.solve`."""
    return SolveEngine(calculate, config, algorithm).solve(
        lam0, criteria=criteria, diagnostics_fn=diagnostics_fn,
        infeas_scale=infeas_scale, health=health,
        checkpoint_fn=checkpoint_fn, preempt_fn=preempt_fn,
        initial_state=initial_state, resume_meta=resume_meta,
        telemetry=telemetry, profiler=profiler, sampler=sampler)


class Maximizer:
    """Paper §4 facade: constructed from algorithm settings, exposes the
    single method `maximize(obj, initial_value) -> Result`.

    Caches the SolveEngine (and with it every jitted chunk runner) for the
    most recent objective: building a fresh closure every call re-traces and
    re-compiles even for an identical objective — repeat solves (warm
    restarts, benchmark repeats) were paying full XLA compile each time.
    The cache is invalidated when the objective's attributes are
    reassigned: the snapshot holds the attribute values themselves and
    compares by identity, so a recycled id can never alias a stale entry.
    It holds a single slot so a sequence of fresh objectives doesn't
    accumulate compiled executables (the snapshot pins nothing beyond what
    the cached objective itself already references).
    """

    def __init__(self, config: SolveConfig, algorithm: str = "agd",
                 criteria: Optional[StoppingCriteria] = None):
        self.config = config
        self.algorithm = algorithm
        get_rule(algorithm)  # fail fast, before any objective is compiled
        self.criteria = criteria
        self._cache = None   # (obj, attr snapshot, SolveEngine)

    def _engine(self, obj) -> SolveEngine:
        snap = tuple(sorted(getattr(obj, "__dict__", {}).items(),
                            key=lambda kv: kv[0]))
        if (self._cache is not None and self._cache[0] is obj
                and len(self._cache[1]) == len(snap)
                and all(k0 == k1 and v0 is v1 for (k0, v0), (k1, v1)
                        in zip(self._cache[1], snap))):
            return self._cache[2]
        engine = SolveEngine(obj.calculate, self.config, self.algorithm)
        self._cache = (obj, snap, engine)
        return engine

    def maximize(self, obj, initial_value: Optional[jax.Array] = None,
                 criteria: Optional[StoppingCriteria] = None,
                 diagnostics_fn: Optional[Callable] = None,
                 health: Optional[HealthConfig] = None,
                 checkpoint_fn: Optional[Callable] = None,
                 preempt_fn: Optional[Callable] = None,
                 initial_state: Optional[SolveState] = None,
                 resume_meta: Optional[dict] = None,
                 telemetry: Optional[Telemetry] = None,
                 profiler=None, sampler=None) -> SolveResult:
        if initial_value is None and initial_state is None:
            initial_value = jnp.zeros(obj.dual_shape, jnp.float32)
        criteria = self.criteria if criteria is None else criteria
        return self._engine(obj).solve(
            initial_value, criteria=criteria, diagnostics_fn=diagnostics_fn,
            infeas_scale=_infeas_scale(obj, criteria), health=health,
            checkpoint_fn=checkpoint_fn, preempt_fn=preempt_fn,
            initial_state=initial_state, resume_meta=resume_meta,
            telemetry=telemetry, profiler=profiler, sampler=sampler)
