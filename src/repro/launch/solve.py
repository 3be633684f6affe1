"""LP solve launcher: `python -m repro.launch.solve [--sources N ...]`.

The production entry point for the paper's workload: generate (or load) a
matching LP, apply the §5.1 enhancements, and run dual ascent.
`--formulation` selects any registered formulation (DESIGN.md §5):
`matching` (default) runs the distributed path on the local mesh;
other formulations compile through `repro.formulations` onto the same
SolveEngine.  `--lambda-sharded` enables the beyond-paper λ-sharding for
very large destination counts.  `--save-duals`/`--warm-start` dump/load λ
as .npz for the repeated-solve workflow (re-solve after an rhs/budget
nudge starts from the previous optimum and stops in far fewer iterations).

Observability (DESIGN.md §11): all launcher output goes through a leveled
`Telemetry` logger.  `--log-jsonl PATH` additionally records the full
structured run log (manifest, per-chunk compile/execute/host spans, check
events, γ moves, health events) for `python -m repro.launch.report`;
`--json` prints one machine-readable result object to stdout (logs move
to stderr); `--profile-dir` captures a jax.profiler trace over a chunk
window.
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import signal
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import (HealthConfig, InstanceSpec, LPValidationError,
                        Maximizer, SolveConfig, StoppingCriteria, generate,
                        get_rule, precondition, rule_names, validate_lp)
from repro.core.types import StopReason
from repro.core.distributed import solve_distributed
from repro.checkpoint.manager import CheckpointManager
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.obs import JsonlSink, LEVELS, ProfilerHook, Telemetry
from repro.obs.memory import MemorySampler
from repro.obs.metrics import REGISTRY, MetricsExporter, MetricsRegistry
from repro import formulations


def instance_fingerprint(lp) -> str:
    """Deterministic digest of an LP instance (shapes + rhs + objective).

    Stored alongside saved duals so a warm re-solve can verify it is
    resuming the SAME instance before trusting the dump's achieved-γ
    metadata.  Hashes the slab geometry, b, and every slab's c_vals —
    cheap (one pass over O(E) bytes) and collision-proof for the purpose
    (distinguishing re-generated instances, not adversaries).
    """
    h = hashlib.sha256()
    h.update(repr((int(lp.m), int(lp.num_destinations),
                   tuple((int(s.n), int(s.width))
                         for s in lp.slabs))).encode())
    h.update(np.ascontiguousarray(np.asarray(lp.b)).tobytes())
    for s in lp.slabs:
        h.update(np.ascontiguousarray(np.asarray(s.c_vals)).tobytes())
    return h.hexdigest()


def save_duals(path: str, lam: jax.Array, gamma: float = None,
               fingerprint: str = None) -> None:
    """Dump a dual solution to .npz (key 'lam'), with optional metadata:
    the γ the solve achieved and the instance fingerprint — what a warm
    re-solve needs to decide, by itself, that continuation can be skipped.
    """
    extra = {}
    if gamma is not None:
        extra["achieved_gamma"] = np.float64(gamma)
    if fingerprint is not None:
        extra["fingerprint"] = np.asarray(fingerprint)
    np.savez(path, lam=np.asarray(lam), **extra)


def load_duals(path: str, expected_shape=None, with_meta: bool = False):
    """Load a dual vector saved by `save_duals`, checking the shape.

    `with_meta=True` additionally returns the metadata dict (possibly
    empty for dumps written before metadata existed): keys
    `achieved_gamma` (float) and `fingerprint` (str) when present.

    A corrupt or truncated dump raises ValueError naming the path —
    a half-written file from a killed process must not surface as a
    bare zipfile traceback deep inside the warm-start path.
    """
    try:
        with np.load(path) as z:
            if "lam" not in z.files:
                raise ValueError(
                    f"duals file {path} has no 'lam' array (keys: "
                    f"{sorted(z.files)}); not a --save-duals dump")
            lam = z["lam"]
            meta = {}
            if "achieved_gamma" in z:
                meta["achieved_gamma"] = float(z["achieved_gamma"])
            if "fingerprint" in z:
                meta["fingerprint"] = str(z["fingerprint"])
    except FileNotFoundError:
        raise
    except ValueError:
        raise
    except Exception as e:
        raise ValueError(
            f"duals file {path} is unreadable ({e}); the dump is corrupt "
            f"or truncated — re-run the producing solve with --save-duals"
        ) from e
    if expected_shape is not None and tuple(lam.shape) != tuple(expected_shape):
        raise ValueError(
            f"warm-start duals at {path} have shape {lam.shape}, but this "
            f"solve needs {tuple(expected_shape)} (different instance or "
            f"formulation?)")
    lam = jnp.asarray(lam)
    return (lam, meta) if with_meta else lam


def apply_warm_start_policy(cfg: SolveConfig, meta: dict,
                            fingerprint: str):
    """Decide whether a warm start may skip γ-continuation (and do it).

    The dump's metadata is the authority: when it shows the duals were
    achieved at (or below) this solve's target γ on the SAME instance,
    re-running continuation from gamma_init would only march the loaded λ
    away from its optimum — so it is stripped automatically instead of
    relying on the caller to remember the rule.  Returns
    (possibly-modified cfg, skipped: bool, reason: str); without matching
    metadata the cfg passes through untouched and `reason` says why.
    """
    continuation = (cfg.gamma_init is not None
                    and cfg.gamma_init > cfg.gamma)
    if not continuation:
        return cfg, False, "no continuation configured"
    g = meta.get("achieved_gamma")
    if g is None:
        return cfg, False, "dump has no achieved-gamma metadata"
    fp = meta.get("fingerprint")
    if fp is not None and fp != fingerprint:
        return cfg, False, "instance fingerprint mismatch"
    if g > cfg.gamma * (1.0 + 1e-6):
        return (cfg, False,
                f"dump stopped at gamma={g:.4g} > target {cfg.gamma:.4g}")
    cfg = dataclasses.replace(cfg, gamma_init=None,
                              adaptive_continuation=False)
    return cfg, True, (f"duals already at gamma={g:.4g} on this instance; "
                       f"continuation skipped")


def attach_byte_census(tel: Telemetry, obj, lam, gamma: float) -> None:
    """Attach an hlo_cost census of one dual value+grad evaluation to the
    run manifest: flops / bytes / collective bytes per iteration at the
    served problem size (DESIGN.md §11).  A lowering the analyzer cannot
    parse fails the run: a census that is silently missing reads as one
    that was never asked for.
    """
    from repro.core.maximizer import hoist_constants
    from repro.launch import hlo_cost
    lam, gamma = jnp.asarray(lam), jnp.float32(gamma)
    calculate, consts = hoist_constants(obj.calculate, lam, gamma)
    txt = (jax.jit(calculate).lower(consts, lam, gamma)
           .compile().as_text())
    cost = hlo_cost.analyze(txt)
    tel.manifest(hlo_cost={
        "flops_per_iteration": cost["flops_per_device"],
        "bytes_per_iteration": cost["bytes_per_device"],
        "collective_bytes_per_iteration":
            cost["collective_bytes_per_device"]})


def main(argv=None) -> dict:
    """Run the launcher on `argv` (default: the command line) and return
    the result object that `--json` prints."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--sources", type=int, default=100_000)
    ap.add_argument("--destinations", type=int, default=1_000)
    ap.add_argument("--nnz-per-row", type=float, default=None)
    ap.add_argument("--formulation", default="matching",
                    choices=formulations.names(),
                    help="registered LP formulation (DESIGN.md §5); "
                         "'matching' uses the distributed path, others "
                         "compile onto the local SolveEngine")
    ap.add_argument("--ax-mode", default=None,
                    choices=["scatter", "sorted", "aligned",
                             "aligned_gvals"],
                    help="Ax reduction layout (default: aligned — the "
                         "value-carrying x-only path; aligned_gvals is "
                         "the legacy gvals-based aligned lowering; the "
                         "distributed matching path maps sorted→scatter)")
    ap.add_argument("--algorithm", default="agd", choices=rule_names(),
                    help="dual update rule (core/update_rules.py, DESIGN.md "
                         "§10): agd is the paper's accelerated ascent, pdhg "
                         "the restarted primal-dual method, bb the spectral "
                         "step, pga plain ascent")
    ap.add_argument("--iterations", type=int, default=200,
                    help="iteration cap (exact count when no tolerance is set)")
    ap.add_argument("--gamma", type=float, default=0.01)
    ap.add_argument("--continuation", action="store_true")
    ap.add_argument("--adaptive-continuation", action="store_true",
                    help="decay gamma on stall instead of on the fixed "
                         "schedule (implies --continuation)")
    ap.add_argument("--no-precondition", action="store_true")
    ap.add_argument("--lambda-sharded", action="store_true")
    ap.add_argument("--use-pallas", action="store_true")
    ap.add_argument("--seed", type=int, default=42)
    # repeated-solve workflow: dump/load the dual vector
    ap.add_argument("--save-duals", default=None, metavar="PATH",
                    help="write the final λ to PATH (.npz) after the solve")
    ap.add_argument("--warm-start", default=None, metavar="PATH",
                    help="initialize λ from a previous --save-duals dump; "
                         "when the dump's metadata shows the duals already "
                         "reached the target γ on this instance, "
                         "γ-continuation is skipped automatically")
    # primal serving & certification (DESIGN.md §8)
    ap.add_argument("--export-primal", default=None, metavar="DIR",
                    help="stream-extract x*(λ) after the solve and write "
                         ".npz decision shards to DIR")
    ap.add_argument("--certify", action="store_true",
                    help="after the solve, extract+repair a feasible primal "
                         "witness and print the duality-gap certificate")
    ap.add_argument("--chunk-rows", type=int, default=4096,
                    help="source rows per extraction chunk for "
                         "--export-primal/--certify")
    # convergence-controlled termination (DESIGN.md §4); any of these flags
    # switches the solve from fixed-length to tolerance-terminated
    ap.add_argument("--tol-infeas", type=float, default=None,
                    help="stop when ||(Ax-b)+|| <= TOL (absolute)")
    ap.add_argument("--tol-rel-dual", type=float, default=None,
                    help="stop when |dg|/max(1,|g|) <= TOL between checks")
    ap.add_argument("--max-seconds", type=float, default=None,
                    help="wall-clock cap, checked every --check-every iters")
    ap.add_argument("--check-every", type=int, default=25,
                    help="iterations per jitted chunk between host-side "
                         "convergence checks")
    ap.add_argument("--verbose-checks", action="store_true",
                    help="print the diagnostics stream (one line per check)")
    # fault tolerance (DESIGN.md §9)
    ap.add_argument("--health-guard", action="store_true",
                    help="check λ/grad/objective health every --check-every "
                         "iterations; roll back to the last-good state and "
                         "retry with smaller steps on NaN/Inf or divergence")
    ap.add_argument("--max-retries", type=int, default=3,
                    help="health-guard retries per bad chunk before giving "
                         "up with stop reason 'diverged'")
    ap.add_argument("--checkpoint-dir", default=None, metavar="DIR",
                    help="persist the solver state to DIR at chunk "
                         "boundaries; SIGTERM/SIGINT flushes a final "
                         "checkpoint before exiting")
    ap.add_argument("--checkpoint-every", type=int, default=100,
                    help="minimum iterations between checkpoints (saves "
                         "land on the next chunk boundary)")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the latest checkpoint in "
                         "--checkpoint-dir (exact trajectory: the resumed "
                         "solve is bitwise-identical to an uninterrupted "
                         "one at matched chunk boundaries)")
    # observability (DESIGN.md §11)
    ap.add_argument("--log-jsonl", default=None, metavar="PATH",
                    help="append the structured run log (manifest, spans, "
                         "check/γ/health events) to PATH as JSON lines; "
                         "render it with `python -m repro.launch.report`")
    ap.add_argument("--log-level", default="info", choices=sorted(LEVELS),
                    help="console verbosity; the JSONL log always carries "
                         "the full stream")
    ap.add_argument("--json", action="store_true",
                    help="print one machine-readable result object to "
                         "stdout (all logs move to stderr)")
    ap.add_argument("--profile-dir", default=None, metavar="DIR",
                    help="capture a jax.profiler trace of the chunk window "
                         "[--profile-start-chunk, +--profile-num-chunks) "
                         "to DIR (opt-in; needs a chunked solve)")
    ap.add_argument("--profile-start-chunk", type=int, default=0,
                    help="first chunk index inside the profiler trace")
    ap.add_argument("--profile-num-chunks", type=int, default=1,
                    help="number of chunks the profiler trace spans")
    # resource observability (DESIGN.md §13)
    ap.add_argument("--metrics-port", type=int, default=None,
                    metavar="PORT",
                    help="serve live Prometheus /metrics on PORT for the "
                         "duration of the solve (counters, histograms, "
                         "memory gauges; 0 binds an ephemeral port)")
    ap.add_argument("--max-host-rss-mb", type=float, default=None,
                    metavar="MB",
                    help="soft host-memory guard: warn (and emit a flagged "
                         "`memory` event) when this process's RSS crosses "
                         "MB MiB — the measurement hook for the "
                         "larger-than-RSS out-of-core gate")
    args = ap.parse_args(argv)
    args.argv = list(sys.argv[1:] if argv is None else argv)
    if args.resume and not args.checkpoint_dir:
        ap.error("--resume requires --checkpoint-dir")
    if args.lambda_sharded and args.formulation != "matching":
        ap.error("--lambda-sharded is only supported with "
                 "--formulation matching (composed formulations solve on "
                 "a single replicated λ)")

    # --json owns stdout: exactly one JSON object; every log line (and the
    # full structured record stream, with --log-jsonl) goes elsewhere
    tel = Telemetry(
        sink=JsonlSink(args.log_jsonl) if args.log_jsonl else None,
        level=args.log_level,
        stream=sys.stderr if args.json else sys.stdout)
    profiler = (ProfilerHook(args.profile_dir,
                             start_chunk=args.profile_start_chunk,
                             num_chunks=args.profile_num_chunks)
                if args.profile_dir else None)
    # the resource sampler rides along whenever anything will consume it:
    # a JSONL run log (per-chunk `memory` events + manifest watermarks), a
    # live /metrics plane, or the RSS soft guard.  Otherwise it stays None
    # and the solve path does zero resource reads (bitwise identical).
    sampler = None
    exporter = None
    registry = None
    if (args.log_jsonl or args.metrics_port is not None
            or args.max_host_rss_mb is not None):
        registry = REGISTRY
        sampler = MemorySampler(
            registry=registry, telemetry=tel,
            max_host_rss_bytes=(int(args.max_host_rss_mb * 2**20)
                                if args.max_host_rss_mb is not None
                                else None))
    if args.metrics_port is not None:
        exporter = MetricsExporter(registry, args.metrics_port)
        tel.info(f"serving /metrics on {exporter.url}")
    tel.info(f"compilation cache: {enable_compile_cache()}")
    try:
        result = _run(args, tel, profiler, sampler=sampler,
                      registry=registry)
        if args.json:
            print(json.dumps(result, sort_keys=True))
    finally:
        if exporter is not None:
            exporter.close()
        tel.close()
    return result


def _run(args, tel: Telemetry, profiler, sampler=None,
         registry: "MetricsRegistry | None" = None) -> dict:
    ap_error = SystemExit  # arg combinations below here are solve errors
    spec = InstanceSpec(
        num_sources=args.sources, num_destinations=args.destinations,
        avg_nnz_per_row=args.nnz_per_row or max(args.sources * 0.001, 8),
        seed=args.seed)
    t0 = time.perf_counter()
    with tel.span("generate", sources=args.sources,
                  destinations=args.destinations):
        lp = jax.tree.map(jnp.asarray, generate(spec))
    try:
        validate_lp(lp, name="instance")
    except LPValidationError as e:
        raise ap_error(f"generated instance failed validation:\n{e}")
    tel.info(f"generated {args.sources}x{args.destinations} in "
             f"{time.perf_counter() - t0:.1f}s")
    continuation = args.continuation or args.adaptive_continuation
    cfg = SolveConfig(
        iterations=args.iterations, gamma=args.gamma,
        gamma_init=(16 * args.gamma if continuation else None),
        adaptive_continuation=args.adaptive_continuation,
        max_step=1e-1 if not args.no_precondition else 1e-3,
        initial_step=1e-5, use_pallas=args.use_pallas)
    criteria = None
    if (args.tol_infeas is not None or args.tol_rel_dual is not None
            or args.max_seconds is not None or args.adaptive_continuation
            or args.health_guard or args.checkpoint_dir
            or profiler is not None):
        # adaptive continuation / health guarding / checkpointing /
        # profiling run chunked even with no tolerances set — build the
        # criteria so --check-every governs the chunk cadence
        criteria = StoppingCriteria(
            tol_infeas=args.tol_infeas, tol_rel_dual=args.tol_rel_dual,
            max_seconds=args.max_seconds, check_every=args.check_every)

    def on_check(rec):
        if args.verbose_checks:
            tel.info(f"  it {rec.it:6d}  dual {rec.dual_obj:.6f}  "
                     f"rel_dual {rec.rel_dual:.2e}  infeas {rec.infeas:.2e}  "
                     f"gamma {rec.gamma:.4f}  {rec.elapsed:.1f}s")

    fingerprint = instance_fingerprint(lp)
    rule = get_rule(args.algorithm)
    tel.manifest(
        fingerprint=fingerprint, formulation=args.formulation,
        algorithm=args.algorithm, sources=args.sources,
        destinations=args.destinations, seed=args.seed,
        gamma=cfg.gamma, gamma_init=cfg.gamma_init,
        adaptive_continuation=cfg.adaptive_continuation,
        iterations_cap=args.iterations,
        check_every=(criteria.check_every if criteria else None),
        config=dataclasses.asdict(cfg),
        argv=args.argv)

    # -- fault tolerance (DESIGN.md §9) ---------------------------------
    health = (HealthConfig(max_retries=args.max_retries)
              if args.health_guard else None)
    checkpoint_fn = None
    preempt_fn = None
    resume_state = None
    resume_meta = None
    if args.checkpoint_dir:
        mgr = CheckpointManager(args.checkpoint_dir, keep_last=3)
        if args.resume:
            step = mgr.latest_step()
            if step is None:
                tel.warning(f"--resume: no checkpoint in "
                            f"{args.checkpoint_dir}; starting fresh")
            else:
                flat, extra = mgr.restore_flat(step)
                ck_fp = extra.get("fingerprint")
                if ck_fp is not None and ck_fp != fingerprint:
                    raise SystemExit(
                        f"--resume refused: checkpoint step {step} in "
                        f"{args.checkpoint_dir} was written for a different "
                        f"instance (fingerprint {ck_fp[:12]}.. != this "
                        f"run's {fingerprint[:12]}..).  Re-run with the "
                        f"original generation flags (--sources/"
                        f"--destinations/--nnz-per-row/--seed) or point "
                        f"--checkpoint-dir at an empty directory.")
                ck_alg = extra.get("algorithm")
                if ck_alg is not None and ck_alg != args.algorithm:
                    raise SystemExit(
                        f"--resume refused: checkpoint step {step} in "
                        f"{args.checkpoint_dir} was written by update rule "
                        f"{ck_alg!r}, but this run uses "
                        f"{args.algorithm!r} (the solver state layouts "
                        f"differ).  Re-run with --algorithm {ck_alg} or "
                        f"point --checkpoint-dir at an empty directory.")
                # The rule rebuilds its SolveState from the flatten keys
                # ('.lam', '.y', ..., '.extra/...' for rule extensions)
                resume_state = rule.state_from_flat(flat)
                resume_meta = {"gamma_now": extra.get("gamma_now"),
                               "g_prev": extra.get("g_prev")}
                tel.info(f"resumed from checkpoint step {step} in "
                         f"{args.checkpoint_dir} "
                         f"(gamma_now={extra.get('gamma_now')})")

        last_saved = {"it": None}

        def checkpoint_fn(it, state, meta):
            # the engine calls this at every healthy chunk boundary plus a
            # forced `final` flush at exit; the hook decides the cadence.
            # `state` must be consumed before returning — its buffers are
            # donated to the next chunk (mgr.save copies them to host).
            if it == last_saved["it"]:
                return
            if (not meta.get("final") and last_saved["it"] is not None
                    and it - last_saved["it"] < args.checkpoint_every):
                return
            mgr.save(it, state,
                     extra={"it": int(it),
                            "gamma_now": float(meta["gamma_now"]),
                            "g_prev": (None if meta["g_prev"] is None
                                       else float(meta["g_prev"])),
                            "algorithm": meta.get("algorithm",
                                                  args.algorithm),
                            "fingerprint": fingerprint})
            last_saved["it"] = it
            tel.info(f"checkpoint saved: step {it} -> "
                     f"{args.checkpoint_dir}")

        # SIGTERM/SIGINT (preemption, ctrl-C) => stop at the next chunk
        # boundary; the engine's final checkpoint_fn call flushes the state
        # reached, so `--resume` afterwards loses at most one chunk of work
        got_signal = {"num": None}

        def _on_signal(signum, frame):
            got_signal["num"] = signum
            tel.warning(f"received signal {signum}; checkpointing at next "
                        f"chunk boundary")

        signal.signal(signal.SIGTERM, _on_signal)
        signal.signal(signal.SIGINT, _on_signal)

        def preempt_fn():
            return got_signal["num"] is not None

    def load_warm(path, expected_shape):
        """Load warm-start duals and apply the continuation-skip policy."""
        nonlocal cfg, continuation
        lam0, meta = load_duals(path, expected_shape, with_meta=True)
        cfg, skipped, reason = apply_warm_start_policy(cfg, meta,
                                                       fingerprint)
        if skipped:
            continuation = False
            tel.info(f"warm start: {reason}")
            tel.event("resolve", outcome="accept", reason=reason)
        elif continuation:
            tel.warning(f"WARNING: --warm-start with --continuation re-runs "
                        f"the γ schedule from gamma_init and will march the "
                        f"loaded λ away from its optimum ({reason})")
            tel.event("resolve", outcome="reject", reason=reason)
        return lam0

    obj = None
    t0 = time.perf_counter()
    if args.formulation == "matching":
        if not args.no_precondition:
            lp, _ = precondition(lp, row_norm=True)
        lam0 = None
        if args.warm_start and resume_state is None:
            lam0 = load_warm(args.warm_start,
                             (lp.m, lp.num_destinations))
        n = jax.device_count()
        mesh = make_mesh((n, 1), ("data", "model"))
        # the distributed objective has no "sorted" mode (the perm would
        # cross shard boundaries); fall back to the scatter baseline there
        ax_mode = args.ax_mode or "aligned"
        res = solve_distributed(lp, cfg, mesh,
                                lambda_axis="model" if args.lambda_sharded
                                else None, lam0=lam0,
                                ax_mode=("scatter" if ax_mode == "sorted"
                                         else ax_mode),
                                algorithm=args.algorithm,
                                criteria=criteria, diagnostics_fn=on_check,
                                health=health, checkpoint_fn=checkpoint_fn,
                                preempt_fn=preempt_fn,
                                initial_state=resume_state,
                                resume_meta=resume_meta,
                                telemetry=tel, profiler=profiler,
                                sampler=sampler)
    else:
        obj = formulations.make_objective(
            args.formulation, lp,
            ax_mode=args.ax_mode or "aligned",
            use_pallas=args.use_pallas,
            row_norm=not args.no_precondition)
        tel.info(f"formulation '{args.formulation}': "
                 f"{obj.dual_shape[0]} dual rows "
                 f"({ {k: f'{v.start}:{v.stop}' for k, v in obj.row_slices().items()} })")
        lam0 = (load_warm(args.warm_start, obj.dual_shape)
                if args.warm_start and resume_state is None else None)
        res = Maximizer(cfg, algorithm=args.algorithm).maximize(
                                      obj, initial_value=lam0,
                                      criteria=criteria,
                                      diagnostics_fn=on_check,
                                      health=health,
                                      checkpoint_fn=checkpoint_fn,
                                      preempt_fn=preempt_fn,
                                      initial_state=resume_state,
                                      resume_meta=resume_meta,
                                      telemetry=tel, profiler=profiler,
                                      sampler=sampler)
    jax.block_until_ready(res.lam)
    dt = time.perf_counter() - t0
    d = np.asarray(res.stats.dual_obj)
    reason = res.stop_reason.value if res.stop_reason else "?"
    tel.info(f"{res.iterations_run} iterations ({args.algorithm}) in "
             f"{dt:.2f}s "
             f"({dt / max(res.iterations_run, 1) * 1e3:.1f} ms/iter, "
             f"compile included); stop reason: {reason}")
    for rec in res.health:
        tel.warning(f"  health: it {rec.it} {rec.status} -> {rec.action} "
                    f"(retry {rec.retries}, step_scale {rec.step_scale:.3g}, "
                    f"gamma {rec.gamma:.4g})")
    if res.stop_reason == StopReason.DIVERGED:
        tel.error("solve DIVERGED: health-guard retries exhausted; the "
                  "duals are the last state that passed the health checks")
    if d.size:
        tel.info(f"dual {d[0]:.3f} -> {d[-1]:.3f}; "
                 f"infeas {float(res.stats.infeas[-1]):.3e}; "
                 f"gamma {float(res.stats.gamma[-1]):.4f}")
    if res.stop_reason == StopReason.PREEMPTED:
        tel.warning(f"preempted at iteration {res.iterations_run}; resume "
                    f"with --resume --checkpoint-dir {args.checkpoint_dir}")
    gamma_last = (float(res.stats.gamma[-1]) if d.size else cfg.gamma)

    result = {
        "run_id": tel.run_id,
        "formulation": args.formulation,
        "algorithm": args.algorithm,
        "iterations_run": int(res.iterations_run),
        "stop_reason": reason,
        "wall_s": dt,
        "ms_per_iteration": dt / max(res.iterations_run, 1) * 1e3,
        "fingerprint": fingerprint,
        "gamma_final": gamma_last,
        "health_events": len(res.health),
    }
    if d.size:
        result.update(
            dual_obj_first=float(d[0]), dual_obj_final=float(d[-1]),
            infeas_final=float(res.stats.infeas[-1]))

    if args.save_duals:
        save_duals(args.save_duals, res.lam, gamma=gamma_last,
                   fingerprint=fingerprint)
        tel.info(f"saved duals -> {args.save_duals} "
                 f"(gamma={gamma_last:.4g}, fingerprinted)")
        result["saved_duals"] = args.save_duals

    serve_obj = None
    if args.export_primal or args.certify or args.log_jsonl:
        # serving/certification/census run single-host over the same
        # (preconditioned) LP the distributed solve consumed; λ is in
        # the same row-normalized space, so x*(λ) matches
        if args.formulation == "matching":
            from repro.core import MatchingObjective
            serve_obj = MatchingObjective(lp, ax_mode=args.ax_mode
                                          or "aligned")
        else:
            serve_obj = obj

    # the byte census costs one extra compile of the dual kernel — only
    # pay it when a run log is actually being recorded
    if args.log_jsonl:
        with tel.span("hlo_census"):
            attach_byte_census(tel, serve_obj, res.lam, gamma_last)

    if ((args.export_primal or args.certify)
            and res.stop_reason == StopReason.PREEMPTED):
        tel.warning("skipping primal export/certification: solve was "
                    "preempted mid-trajectory (resume it to completion "
                    "first)")
    elif args.export_primal or args.certify:
        from repro import primal as primal_sub
        gamma_final = jnp.float32(gamma_last)
        if args.export_primal:
            t0 = time.perf_counter()
            with tel.span("export_primal"):
                paths = primal_sub.write_shards(serve_obj, res.lam,
                                                gamma_final,
                                                args.export_primal,
                                                chunk_rows=args.chunk_rows,
                                                sampler=sampler)
            dt_x = time.perf_counter() - t0
            n_src = sum(s.n for s in serve_obj.lp.slabs)
            tel.info(f"exported {len(paths)} decision shards "
                     f"({n_src} sources) -> {args.export_primal} in "
                     f"{dt_x:.1f}s "
                     f"({n_src / max(dt_x, 1e-9):.0f} sources/s)")
            result["export_shards"] = len(paths)
        if args.certify:
            with tel.span("certify"):
                cert = primal_sub.certify(serve_obj, res.lam, gamma_final,
                                          chunk_rows=args.chunk_rows,
                                          sampler=sampler)
            tel.info(primal_sub.format_certificate(cert))
            result["certificate_valid"] = bool(cert.valid)
            result["certificate_gap"] = cert.gap
            result["certificate_gap_rel"] = cert.gap_rel

    if sampler is not None:
        # fold the export/certify sampling into the run-level watermarks
        # (the engine already stamped its own peaks mid-solve), surface
        # them in the JSON result, and flush the registry digest so the
        # post-mortem log carries the same series the live plane served
        marks = sampler.watermarks()
        tel.manifest(**marks)
        result["peak_rss_bytes"] = marks["peak_rss_bytes"]
        result["peak_hbm_bytes"] = marks["peak_hbm_bytes"]
        if marks["peak_rss_bytes"]:
            tel.info(f"peak host RSS {marks['peak_rss_bytes'] / 2**20:.0f} "
                     f"MiB over {marks['memory_samples']} samples")
    if registry is not None:
        tel.event("metrics", series=registry.summary())
    return result


if __name__ == "__main__":
    main()
