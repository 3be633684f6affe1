#!/usr/bin/env python3
"""Bring-up smoke: the matching solve and the decision server on one TPU.

    python chip_smoke.py [--seed N]          # one chip
    python chip_smoke.py --four-chips        # the 4-chip dual-only path

Run from the root of a checkout; it puts `src/` on the import path itself.
With no arguments it drives the system's main path once, on one chip:

  1. correctness, at I = 20k: one `calculate` at a fixed λ against the
     independent numpy solver (`core/baseline_numpy.py`) and the
     `kernels/ref.py` oracles, then the same evaluation through the
     Pallas kernels against the XLA path — and the compiled Pallas
     program must hold Mosaic kernels (`tpu_custom_call`), proving it did
     not run in interpret mode;
  2. solve, at I = 5M sources x J = 10k destinations x 10 edges per
     source (about 5e7 edges): the `python -m repro.launch.solve` launcher
     in-process — row-normalized matching LP, `solve_distributed` over the
     local mesh, x-carry aligned Ax, AGD with adaptive γ-continuation,
     stopping at the verify recipe's tolerances or its caps — then the
     launcher's duality-gap certificate;
  3. serve: an `AllocationServer` on the solved duals behind a
     `ServerFrontend`; a few dozen microbatch requests must come back OK
     and bitwise equal to batch extraction.

`--four-chips` runs only the phase that exists across chips: the same
instance solved by `solve_distributed` on a (4, 1) mesh and on a one-device
mesh in the same process, for the same iterations; the dual trajectories
must agree within the paper's Fig. 2 criterion (max rel < 1e-2, final
< 1e-4), and the slab data must be split over all four devices.

Every phase prints its numbers.  Any failure exits non-zero; the last line
of standard output is one JSON object naming the device, printed only when
every phase passed.  Without a TPU (or without the repository next to this
file) the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(REPO, "chiprun_out", "chip_smoke")

# the main-path instance: Appendix-B matching LP at one chip's scale
SOURCES, DESTINATIONS, NNZ_PER_ROW = 5_000_000, 10_000, 10
# the correctness instance (the numpy reference projects source by source)
CHECK_SOURCES, CHECK_DESTINATIONS = 20_000, 1_000
# the solve: the verify recipe's tolerances, adaptive continuation, caps
SOLVE_ARGS = ["--tol-rel-dual", "1e-6", "--tol-infeas", "1.0",
              "--check-every", "25", "--adaptive-continuation",
              "--iterations", "3000", "--max-seconds", "90"]
QUERIES, QUERY_SOURCES = 48, 8
FOUR_CHIP_ITERATIONS = 40


class Failure(Exception):
    """A phase's check failed."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise Failure(what)


def say(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums the backend compile durations JAX reports through
    `jax.monitoring` (persistent-cache hits included, as the short
    durations they are) — no compile is forced or repeated to measure."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1

    def lap(self):
        """(seconds, compiles) since the previous lap."""
        out = (self.seconds, self.count)
        self.seconds, self.count = 0.0, 0
        return out


def peak_bytes() -> int:
    import jax
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def instance_spec(seed: int, sources: int, destinations: int):
    from repro.core import InstanceSpec
    return InstanceSpec(num_sources=sources, num_destinations=destinations,
                        avg_nnz_per_row=NNZ_PER_ROW, seed=seed)


def build_lp(spec):
    """The launcher's matching instance: generated on the host, moved to
    the device, row-normalized (`precondition(row_norm=True)`)."""
    import jax
    import jax.numpy as jnp
    from repro.core import generate, precondition
    lp = jax.tree.map(jnp.asarray, generate(spec))
    return precondition(lp, row_norm=True)[0]


def phase_correctness(seed: int, clock: CompileClock,
                      sources: int = CHECK_SOURCES,
                      destinations: int = CHECK_DESTINATIONS) -> None:
    """XLA path vs the numpy solver and the kernel oracles, then the Pallas
    path vs the XLA path, at one fixed λ."""
    import numpy as np
    import jax
    import jax.numpy as jnp
    from repro.core import MatchingObjective, build_ax_plan
    from repro.core import baseline_numpy as bn
    from repro.core.types import edge_space
    from repro.kernels import ops, ref

    t0 = time.perf_counter()
    lp = build_lp(instance_spec(seed, sources, destinations))
    lp_np = jax.tree.map(np.asarray, lp)
    lam = jnp.asarray(np.random.default_rng(seed).uniform(
        0.0, 2.0, (lp.m, lp.num_destinations)).astype(np.float32))
    gamma = jnp.float32(0.05)
    xla = MatchingObjective(lp, ax_mode="aligned")
    g, grad, aux = jax.jit(xla.calculate)(lam, gamma)
    g, grad = float(g), np.asarray(grad, np.float64)
    scale = max(1.0, float(np.abs(grad).max()))

    # independent numpy solver: CSC layout, exact sort-based projection, f64
    g_np, grad_np, _ = bn.dual_value_and_grad(
        bn.from_slabs(lp_np), np.asarray(lam, np.float64), 0.05)
    err_np_g = abs(g - g_np) / max(1.0, abs(g_np))
    err_np_grad = float(np.abs(grad - grad_np).max()) / scale
    say(f"[check] I={sources} J={destinations} edges="
        f"{sum(int(s.mask.sum()) for s in lp_np.slabs)}: XLA vs numpy "
        f"solver: g rel err {err_np_g:.3e}, grad max err {err_np_grad:.3e}")
    check(err_np_g < 1e-4 and err_np_grad < 1e-3,
          "XLA calculate disagrees with the numpy solver")

    # kernel oracles: per-slab fused step + the plan reduction
    xs, c_x, x_sq = [], 0.0, 0.0
    for s in lp.slabs:
        x, _, cx, sq = ref.dual_xstar_ref(s.a_vals, s.c_vals, s.dest_idx,
                                          s.mask, s.ub, s.s, lam, gamma)
        xs.append(edge_space(x))
        c_x, x_sq = c_x + float(cx), x_sq + float(sq)
    # the oracle reduces over an AxPlan, whichever Ax path the objective
    # took (the dual tile on a TPU)
    plan = jax.tree.map(jnp.asarray, build_ax_plan(lp))
    ax = np.asarray(ref.ax_plan_x_ref(plan, jnp.concatenate(xs)),
                    np.float64)
    grad_ref = ax - np.asarray(lp.b, np.float64)
    g_ref = c_x + 0.025 * x_sq + float(np.vdot(np.asarray(lam), grad_ref))
    err_ref_g = abs(g - g_ref) / max(1.0, abs(g_ref))
    err_ref_grad = float(np.abs(grad - grad_ref).max()) / scale
    say(f"[check] XLA vs kernels/ref oracles: g rel err {err_ref_g:.3e}, "
        f"grad max err {err_ref_grad:.3e}")
    check(err_ref_g < 1e-5 and err_ref_grad < 1e-4,
          "XLA calculate disagrees with the kernel oracles")

    # Pallas kernels vs XLA: per slab at tests/test_kernels.py's tolerances
    # (x atol 1e-5, cᵀx and ‖x‖² rel 1e-3), then the whole evaluation
    from repro.core.objectives import slab_xcarry
    for s in lp.slabs:
        xk, cxk, sqk = ops.dual_x_full(s, lam, gamma)
        xr, cxr, sqr = slab_xcarry(s, lam, gamma, "boxcut")
        check(float(jnp.abs(xk - xr).max()) <= 1e-5,
              f"Pallas x differs from XLA on slab width {s.width}")
        check(abs(float(cxk - cxr)) < 1e-3 * max(1.0, abs(float(cxr)))
              and abs(float(sqk - sqr)) < 1e-3 * max(1.0, abs(float(sqr))),
              f"Pallas scalars differ from XLA on slab width {s.width}")
    pallas = MatchingObjective(lp, ax_mode="aligned", use_pallas=True,
                               ax_plan=plan)
    compiled = jax.jit(pallas.calculate).lower(lam, gamma).compile()
    check("tpu_custom_call" in compiled.as_text(),
          "compiled Pallas evaluation holds no Mosaic kernel")
    g_p, grad_p, _ = compiled(lam, gamma)
    err_p_g = abs(float(g_p) - g) / max(1.0, abs(g))
    err_p_grad = float(np.abs(np.asarray(grad_p) - grad).max()) / scale
    sec, n = clock.lap()
    say(f"[check] Pallas (Mosaic, tpu_custom_call present) vs XLA: g rel "
        f"err {err_p_g:.3e}, grad max err {err_p_grad:.3e}; {n} compiles "
        f"{sec:.1f}s; phase {time.perf_counter() - t0:.1f}s")
    check(err_p_g < 1e-4 and err_p_grad < 1e-3,
          "Pallas calculate disagrees with the XLA path")


def phase_solve(seed: int, clock: CompileClock, sources: int = SOURCES,
                destinations: int = DESTINATIONS,
                solve_args=SOLVE_ARGS) -> str:
    """The launcher, in-process, on the main-path instance; returns the
    path of the saved duals."""
    import numpy as np
    from repro.launch import solve as launcher

    os.makedirs(OUT_DIR, exist_ok=True)
    duals = os.path.join(OUT_DIR, "duals.npz")
    t0 = time.perf_counter()
    res = launcher.main([
        "--sources", str(sources), "--destinations", str(destinations),
        "--nnz-per-row", str(NNZ_PER_ROW), "--seed", str(seed),
        *solve_args, "--verbose-checks", "--certify",
        "--chunk-rows", "65536", "--save-duals", duals])
    sec, n = clock.lap()
    say(f"[solve] {res['iterations_run']} iterations, stop reason "
        f"{res['stop_reason']}, solve {res['wall_s']:.1f}s "
        f"({res['ms_per_iteration']:.2f} ms/iteration, compile included); "
        f"dual {res['dual_obj_first']:.6g} -> {res['dual_obj_final']:.9g}, "
        f"infeas {res['infeas_final']:.4g}; certificate gap "
        f"{res['certificate_gap']:.6g} (rel {res['certificate_gap_rel']:.3e})"
        f", valid {res['certificate_valid']}; {n} compiles {sec:.1f}s; "
        f"phase {time.perf_counter() - t0:.1f}s")
    check(res["stop_reason"] != "diverged", "solve diverged")
    check(bool(np.isfinite([res["dual_obj_final"],
                            res["infeas_final"]]).all()),
          "solve ended on a non-finite dual objective or infeasibility")
    if res["stop_reason"] != "converged":
        check(res["dual_obj_final"] > res["dual_obj_first"],
              f"solve hit its cap ({res['stop_reason']}) without ascent")
    check(res["certificate_valid"], "duality-gap certificate is invalid")
    return duals


def phase_serve(seed: int, clock: CompileClock, duals: str,
                sources: int = SOURCES,
                destinations: int = DESTINATIONS) -> int:
    """Decision server on the solved duals; returns the number of requests
    served."""
    import numpy as np
    import jax
    from repro.core import MatchingObjective
    from repro import primal
    from repro.launch.solve import load_duals

    t0 = time.perf_counter()
    lp = build_lp(instance_spec(seed, sources, destinations))
    obj = MatchingObjective(lp, ax_mode="aligned")
    lam, meta = load_duals(duals, (lp.m, lp.num_destinations),
                           with_meta=True)
    gamma = np.float32(meta["achieved_gamma"])
    say("[serve] slabs (n, w): "
        + " ".join(f"{s.n}x{s.width}" for s in lp.slabs)
        + f"; Ax path {obj.ax_path}"
        + ("" if obj._plan is None else "; plan buckets (r, w): " + " ".join(
            f"{b.rows}x{b.width}" for b in obj._plan.buckets)))
    static = sum(leaf.nbytes for leaf in jax.tree.leaves((lp, obj._plan)))
    say(f"[serve] edges {lp.num_edges} padded, "
        f"{sum(int(s.mask.sum()) for s in lp.slabs)} real; instance + plan "
        f"on device {static / 2**30:.3f} GiB")
    batch = primal.extract_primal(obj, lam, gamma, chunk_rows=65536)
    srv = primal.AllocationServer(obj, lam, gamma, max_batch=64)
    srv.warmup()
    fe = primal.ServerFrontend(srv, primal.FrontendConfig(
        max_batch=64, default_deadline_s=60.0, max_queue=QUERIES))
    try:
        rng = np.random.default_rng(seed)
        ids = srv.source_ids()
        tickets = [fe.submit(rng.choice(ids, QUERY_SOURCES,
                                        replace=False).tolist())
                   for _ in range(QUERIES)]
        responses = [t.result(timeout=300.0) for t in tickets]
    finally:
        snap = fe.drain(timeout=60.0)
    bad = [r for r in responses if r.status is not primal.RequestStatus.OK]
    check(not bad, f"{len(bad)} requests not OK (first: "
                   f"{bad[0].status.value if bad else ''} "
                   f"{bad[0].reason if bad else ''})")
    rows = 0
    for r in responses:
        for d in r.decisions.values():
            check(np.array_equal(d.x, batch[d.slab_index][d.row]),
                  f"served decision of source {d.source_id} differs from "
                  f"batch extraction")
            rows += 1
    lat = np.asarray([r.latency_s for r in responses]) * 1e3
    sec, n = clock.lap()
    say(f"[serve] {len(responses)} requests ({rows} sources) OK, bitwise "
        f"equal to batch extraction; latency p50 {np.median(lat):.2f} ms, "
        f"max {lat.max():.2f} ms; batches {snap['batches_total']:.0f}; "
        f"{n} compiles {sec:.1f}s; phase {time.perf_counter() - t0:.1f}s")
    return len(responses)


def phase_four_chips(seed: int, clock: CompileClock,
                     sources: int = SOURCES,
                     destinations: int = DESTINATIONS,
                     iterations: int = FOUR_CHIP_ITERATIONS) -> None:
    """4-way `solve_distributed` vs a one-device mesh, same iterations."""
    import numpy as np
    import jax
    from repro.core import SolveConfig
    from repro.core.distributed import place_lp, solve_distributed
    from repro.launch.mesh import make_mesh

    check(len(jax.devices()) >= 4,
          f"--four-chips needs 4 devices, JAX sees {len(jax.devices())}")
    t0 = time.perf_counter()
    lp = build_lp(instance_spec(seed, sources, destinations))
    say(f"[4chip] instance built in {time.perf_counter() - t0:.1f}s")
    mesh4 = make_mesh((4, 1), ("data", "model"), jax.devices()[:4])
    mesh1 = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])

    # the placement solve_distributed makes: slab rows over every mesh axis
    placed = place_lp(lp, mesh4, tuple(mesh4.axis_names))
    for si, s in enumerate(placed.slabs):
        for name, arr in zip(s._fields, s):
            shards = arr.addressable_shards
            devs = {sh.device for sh in shards}
            sizes = [sh.data.nbytes for sh in shards]
            check(len(devs) == 4 and len(shards) == 4,
                  f"slab {si} {name} is not split over 4 devices: {devs}")
            check(max(sizes) == min(sizes) == arr.nbytes // 4,
                  f"slab {si} {name} shards are not a quarter each: {sizes}")
    say("[4chip] every slab leaf is split over 4 devices, a quarter of its "
        "bytes each: " + ", ".join(
            f"{s.n}x{s.width} -> {s.c_vals.addressable_shards[0].data.shape}"
            for s in placed.slabs))
    del placed

    cfg = SolveConfig(iterations=iterations, gamma=0.01, max_step=1e-1,
                      initial_step=1e-5)
    out = {}
    for name, mesh in (("4-way", mesh4), ("1-device", mesh1)):
        t = time.perf_counter()
        res = solve_distributed(lp, cfg, mesh, ax_mode="aligned")
        jax.block_until_ready(res.lam)
        out[name] = np.asarray(res.stats.dual_obj, np.float64)
        sec, n = clock.lap()
        say(f"[4chip] {name}: {iterations} iterations in "
            f"{time.perf_counter() - t:.1f}s ({n} compiles {sec:.1f}s); "
            f"dual {out[name][0]:.6g} -> {out[name][-1]:.9g}")
    a, b = out["1-device"], out["4-way"]
    rel = np.abs(a - b) / np.maximum(np.abs(a), 1e-12)
    say(f"[4chip] dual trajectory 4-way vs 1-device: max rel "
        f"{rel.max():.3e} (< 1e-2), final rel {rel[-1]:.3e} (< 1e-4)")
    check(bool(np.isfinite(b).all()), "4-way trajectory is not finite")
    check(rel.max() < 1e-2 and rel[-1] < 1e-4,
          "4-way dual trajectory outside the Fig. 2 criterion")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every generated instance and λ")
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-chip solve_distributed phase")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(REPO, "src"))
    try:
        import repro  # noqa: F401
    except ImportError as e:
        print(f"chip_smoke: the repository's src/ is not next to this "
              f"file ({e})", file=sys.stderr)
        return 2
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform {dev.platform!r}); "
              f"this smoke runs on the chip only", file=sys.stderr)
        return 3
    from repro.launch.compile_cache import enable_compile_cache
    say(f"[device] {dev.platform} {dev.device_kind} x{len(jax.devices())}; "
        f"compilation cache {enable_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    try:
        if args.four_chips:
            phase_four_chips(args.seed, clock)
        else:
            phase_correctness(args.seed, clock)
            duals = phase_solve(args.seed, clock)
            phase_serve(args.seed, clock, duals)
    except Failure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    say(f"[done] peak_bytes_in_use {peak_bytes()} "
        f"({peak_bytes() / 2**30:.3f} GiB); total "
        f"{time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
