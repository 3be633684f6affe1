"""Core data types for the DuaLip solver.

The matching-LP data layout is the TPU adaptation of the paper's CSC format
(DESIGN.md §2): edges are grouped by *source* and sources are bucketed by
⌈log2 degree⌉ into dense padded slabs.  Every hot operation (x*(λ) compute,
projection, per-edge gradient) is then a dense masked row-op on a slab —
MXU/VPU friendly — while the `Ax` reduction is a segment-sum keyed by the
destination index.

All array containers are NamedTuples so they are automatically pytrees; any
static metadata (projection kind, bucket widths) lives on plain Python
objects outside the jitted functions.
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Any, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp


class Slab(NamedTuple):
    """One degree bucket of sources, padded to a common width.

    Shapes (n = #sources in bucket, w = padded width = bucket power of two,
    m = #constraint families):
      a_vals:   (n, w, m)  constraint coefficients a^k_ij (0 on padding)
      c_vals:   (n, w)     objective coefficients  c_ij   (0 on padding)
      dest_idx: (n, w)     destination id j of each edge  (0 on padding)
      mask:     (n, w)     True for real edges
      ub:       (n, w)     per-edge upper bound for box / box-cut (inf => none)
      s:        (n,)       per-source budget for simplex / box-cut (inf => none)
      source_ids: (n,)     original source index (bookkeeping / debugging)
    """

    a_vals: jax.Array
    c_vals: jax.Array
    dest_idx: jax.Array
    mask: jax.Array
    ub: jax.Array
    s: jax.Array
    source_ids: jax.Array

    @property
    def n(self) -> int:
        return self.c_vals.shape[0]

    @property
    def width(self) -> int:
        return self.c_vals.shape[1]

    @property
    def m(self) -> int:
        return self.a_vals.shape[2]


def edge_space(a):
    """Flatten one slab-shaped (n, w[, m]) array into its part of the
    concatenated slab-edge space the AxPlan indexes (DESIGN.md §3).

    The order is column-major within the slab: edge (r, q) sits at
    q·n + r.  The TPU compiler lays an (n, w) array with a small w out
    with n as the minor dimension, so this flatten is a plain copy there;
    the row-major flatten is a relayout that compiles into code linear in
    n (about a minute for n = 150k at w = 4) and stages a lane-padded
    temporary.  Works on numpy and JAX arrays alike.
    """
    return a.swapaxes(0, 1).reshape(-1, *a.shape[2:])


def dest_gather(table, dest_idx):
    """`table[:, dest_idx]`: an (m, J) dual-shaped table read at every edge
    of a slab, as (m, n, w).

    Gathers through the transposed (w, n) index.  The TPU compiler emits
    code linear in n for a gather whose (n, w) index has a small w (over a
    minute at n = 1.5M, w = 8); through the transpose the same gather
    compiles in about a second.  Same values either way.
    """
    return jnp.swapaxes(table[:, dest_idx.T], 1, 2)


class AxBucket(NamedTuple):
    """One in-degree bucket of the constraint-aligned companion layout.

    Destination-major mirror of `Slab`: each row is one dual row
    (destination), holding the positions of its incident edges in the
    concatenated slab-edge space, padded to a common power-of-two width.

    Shapes (r = #destinations in bucket, w = padded width = bucket power
    of two, m = #constraint families):
      edge_idx: (r, w)     int32  flat edge positions (0 on padding)
      mask:     (r, w)     bool   True for real incident edges
      dest_ids: (r,)       int32  destination id j of each row
      a_dm:     (r, w, m)  destination-major copy of the constraint
                           weights, `a_dm[r, q] = a_flat[edge_idx[r, q]]`
                           (0 on padding) — the *value-carrying* layout
                           (DESIGN.md §3).  The weights are static, so
                           packing them alongside the indices lets the
                           aligned reduction consume the (E,) x vector
                           directly instead of a materialized (E, m)
                           gvals tensor.  None on plans packed with
                           `carry_values=False` (index-only legacy plans).

    A leading shard axis may be prepended to every field (see
    `instance.build_sharded_ax_plan`); the per-row semantics are unchanged.
    """

    edge_idx: jax.Array
    mask: jax.Array
    dest_ids: jax.Array
    a_dm: Optional[jax.Array] = None

    @property
    def rows(self) -> int:
        return self.edge_idx.shape[-2]

    @property
    def width(self) -> int:
        return self.edge_idx.shape[-1]


class AxPlan(NamedTuple):
    """Destination-major companion of the source-major slab layout
    (DESIGN.md §3) — packed once at construction, consumed every iteration.

    The slabs answer "which edges does source i own?"; the plan answers
    "which edges land on dual row j?".  With it, `Ax` is a *gather*:
    flatten the per-edge gradient values gvals (edge order = slab
    concatenation order, each slab flattened by `edge_space`), gather
    each destination's incident values, and
    masked-row-sum — no scatter, no atomics, fixed shapes.

    With `carry_values=True` (the default) each bucket additionally packs
    the destination-major weight copy `a_dm`, and the reduction becomes
    x-only: `ax[r, k] = Σ_q mask · a_dm[r, q, k] · x[edge_idx[r, q]]` —
    the per-edge gradient tensor is never materialized at all
    (`ops.ax_aligned_x`, DESIGN.md §3).

    buckets:  one AxBucket per ⌈log2 in-degree⌉ class; together the rows
              cover every destination exactly once (zero in-degree
              destinations get a fully masked min-width row).
    inv_perm: (J,) int32 — position of destination j in the
              bucket-concatenated row space, so assembling the dense
              (m, J) result is itself a pure gather.
    """

    buckets: Tuple[AxBucket, ...]
    inv_perm: jax.Array

    @property
    def num_rows(self) -> int:
        return sum(b.rows for b in self.buckets)

    @property
    def num_destinations(self) -> int:
        return self.inv_perm.shape[-1]


class LPData(NamedTuple):
    """A matching LP in bucketed-slab layout.

    slabs: tuple of Slab, one per degree bucket (widths are static shapes).
    b:     (m, J) right-hand side of the complex constraints, one row per
           constraint family.  λ has the same (m, J) shape.
    """

    slabs: Tuple[Slab, ...]
    b: jax.Array

    @property
    def m(self) -> int:
        return self.b.shape[0]

    @property
    def num_destinations(self) -> int:
        return self.b.shape[1]

    @property
    def num_sources(self) -> int:
        return sum(s.n for s in self.slabs)

    @property
    def num_edges(self) -> int:
        # Static (mask-independent) upper bound; true nnz needs a device read.
        return sum(s.n * s.width for s in self.slabs)


@dataclasses.dataclass(frozen=True)
class SolveConfig:
    """Paper-faithful defaults (Appendix B): max-step 1e-3, init-step 1e-5,
    γ = 0.01; continuation per §5.1 / Fig. 5 (0.16 → 0.01, halved every 25)."""

    iterations: int = 200
    gamma: float = 0.01
    initial_step: float = 1e-5
    max_step: float = 1e-3
    # γ continuation (disabled unless gamma_init > gamma)
    gamma_init: Optional[float] = None
    gamma_decay_every: int = 25
    gamma_decay_rate: float = 0.5
    # scale the step cap proportionally with γ during continuation (§5.1)
    scale_step_with_gamma: bool = True
    # adaptive continuation (DESIGN.md §4): instead of decaying γ every
    # `gamma_decay_every` iterations, the chunked solve loop decays it when
    # the dual objective stalls (relative change per convergence check below
    # `gamma_stall_tol`).  Takes effect only in the chunked engine (i.e. when
    # a StoppingCriteria is active or this flag forces chunking).
    adaptive_continuation: bool = False
    gamma_stall_tol: float = 1e-4
    # Jacobi row normalization (§5.1) — applied by `precondition()` before solve
    row_normalize: bool = False
    # primal (per-block) scaling (§5.1)
    primal_scale: bool = False
    projection: str = "boxcut"  # "box" | "simplex" | "boxcut" | "simplex_eq"
    dtype: jnp.dtype = jnp.float32
    log_every: int = 1
    use_pallas: bool = False  # route x*(λ) through the Pallas kernels
    # --- update-rule knobs (core/update_rules.py, DESIGN.md §10) ---
    # Restarted PDHG: jump to the running average when its KKT score both
    # decays by `pdhg_restart_beta` and beats the current iterate's
    # (adaptive, better-of-two); the averaging window is re-based anyway
    # after `pdhg_restart_every` iterations (fixed-frequency cap); no jump
    # before `pdhg_min_window` iterations.  Per-row diagonal steps are
    # ω/L̂_i with L̂_i a running-max coordinatewise secant (decay
    # `pdhg_l_decay`), capped at `pdhg_step_max_scale`·cap·ω; the global
    # multiplier ω starts at `pdhg_omega_init` and is only moved by the
    # health guard's backoff (floor `pdhg_omega_min`).
    pdhg_restart_every: int = 512
    pdhg_restart_beta: float = 0.2
    pdhg_min_window: int = 8
    pdhg_omega_init: float = 1.0
    pdhg_omega_min: float = 0.015625  # 1/64
    pdhg_l_decay: float = 0.97
    pdhg_step_max_scale: float = 8.0
    # Spectral (BB) rule: accepted BB steps are trust-capped at
    # `bb_step_max_scale` × the engine step cap.
    bb_step_max_scale: float = 8.0
    # Bound on the host-side SolveResult.diagnostics stream: keep only the
    # last N ConvergenceCheck records (None = unbounded, the compatible
    # default).  A million-iteration solve with a small check_every would
    # otherwise accumulate host tuples without limit; the telemetry sink
    # (DESIGN.md §11) still receives EVERY check event regardless of this
    # cap — the JSONL log is the unbounded record, the in-memory stream
    # the bounded convenience view.
    max_diagnostics: Optional[int] = None


class StopReason(enum.Enum):
    """Why the solve loop exited (DESIGN.md §4, §9).

    CONVERGED means every tolerance set on the StoppingCriteria held
    simultaneously at a convergence check (with γ at its target) — the
    "matched stopping criteria" of the paper's speedup claims.  The caps
    (iteration / wall-clock) terminate without convergence.  DIVERGED
    means the health guard exhausted its rollback/backoff retries — the
    returned λ is the last *healthy* iterate, never the poisoned one.
    PREEMPTED means the caller's preempt hook requested an orderly stop
    at a chunk boundary (the checkpoint/resume path, DESIGN.md §9).
    """

    CONVERGED = "converged"
    MAX_ITERATIONS = "max_iterations"
    MAX_SECONDS = "max_seconds"
    DIVERGED = "diverged"
    PREEMPTED = "preempted"


@dataclasses.dataclass(frozen=True)
class HealthConfig:
    """Health-guard policy for the chunked solve loop (DESIGN.md §9).

    After every chunk the host controller inspects the chunk's trailing
    scalars plus λ-finiteness.  A chunk is *bad* when any of:

      * non-finite — NaN/Inf in the dual objective, gradient norm,
        infeasibility, or anywhere in λ itself (`check_lambda`);
      * objective regression — g fell more than `obj_regression_tol ·
        max(1, |g_good|)` below the last healthy chunk's value while γ
        was unchanged (g legitimately moves when γ moves);
      * gradient explosion — ‖∇g‖ grew beyond `grad_explosion ·
        max(‖∇g_good‖, 1)`.

    A bad chunk is rolled back to the last-good SolveState snapshot and
    retried with momentum reset and the trusted step shrunk by
    `step_backoff` per consecutive failure (implemented through the
    Lipschitz estimate that *is* the step rule, so no recompilation);
    in adaptive-continuation mode γ is additionally boosted by
    `gamma_backoff` (more regularization = smoother dual).  After
    `max_retries` consecutive failures the solve surfaces
    StopReason.DIVERGED with the last-good λ.
    """

    max_retries: int = 3
    obj_regression_tol: float = 0.5
    grad_explosion: float = 100.0
    step_backoff: float = 0.25
    gamma_backoff: float = 4.0
    check_lambda: bool = True


class HealthRecord(NamedTuple):
    """One incident record of the health-guard diagnostics stream
    (DESIGN.md §9).  Only *bad* chunks produce records — a healthy solve
    has an empty stream.  All fields are host-side Python scalars."""

    it: int               # iteration count the bad chunk ended at
    status: str           # "nonfinite" | "regression" | "grad_explosion"
    action: str           # "rollback" (retrying) | "giveup" (DIVERGED)
    retries: int          # consecutive failures so far, this one included
    dual_obj: float       # g at the bad chunk's end (may be NaN)
    grad_norm: float      # ‖∇g‖ at the bad chunk's end (may be NaN)
    gamma: float          # γ of the bad chunk
    rolled_back_to: int   # iteration of the snapshot restored
    step_scale: float     # step-cap multiplier applied to the retry


@dataclasses.dataclass(frozen=True)
class StoppingCriteria:
    """Composable stopping rules, evaluated host-side every `check_every`
    iterations at a chunk boundary of the solve loop (DESIGN.md §4).

    Tolerances compose conjunctively: the solve is CONVERGED when every
    tolerance that is set holds at the same check.  Unset fields impose
    nothing.  The rules are:

      tol_rel_dual    |g_k − g_prev| <= tol · max(1, |g_k|) between
                      consecutive checks (g = dual objective)
      tol_infeas /    ‖(Ax−b)₊‖₂ <= tol_infeas + tol_infeas_rel · scale,
      tol_infeas_rel  where scale = 1 + ‖b‖₂ (supplied by the caller;
                      defaults to 1 when b is unavailable)
      tol_grad_norm   ‖∇g(λ)‖₂ <= tol_grad_norm

      max_iterations  overrides SolveConfig.iterations as the total cap
      max_seconds     wall-clock cap, checked at chunk boundaries (includes
                      the first chunk's XLA compile)
    """

    tol_rel_dual: Optional[float] = None
    tol_infeas: Optional[float] = None
    tol_infeas_rel: Optional[float] = None
    tol_grad_norm: Optional[float] = None
    max_iterations: Optional[int] = None
    max_seconds: Optional[float] = None
    check_every: int = 25

    @property
    def has_tolerances(self) -> bool:
        return any(t is not None for t in (
            self.tol_rel_dual, self.tol_infeas, self.tol_infeas_rel,
            self.tol_grad_norm))

    @property
    def needs_checks(self) -> bool:
        """True when the loop must pause at chunk boundaries at all."""
        return self.has_tolerances or self.max_seconds is not None

    def satisfied(self, rel_dual: float, infeas: float, grad_norm: float,
                  infeas_scale: float = 1.0) -> bool:
        """All set tolerances hold (NaNs never satisfy a tolerance)."""
        if not self.has_tolerances:
            return False
        if self.tol_rel_dual is not None and not rel_dual <= self.tol_rel_dual:
            return False
        if self.tol_infeas is not None or self.tol_infeas_rel is not None:
            thr = ((self.tol_infeas or 0.0)
                   + (self.tol_infeas_rel or 0.0) * infeas_scale)
            if not infeas <= thr:
                return False
        if (self.tol_grad_norm is not None
                and not grad_norm <= self.tol_grad_norm):
            return False
        return True


class ConvergenceCheck(NamedTuple):
    """One record of the diagnostics stream: the host-side scalars read back
    at a chunk boundary (DESIGN.md §4).  All fields are plain Python values —
    this is exactly what crosses the device→host boundary per check."""

    it: int             # iterations executed so far
    dual_obj: float     # g(λ) at the last iteration of the chunk
    rel_dual: float     # |Δg| / max(1, |g|) since the previous check
    infeas: float       # ‖(Ax−b)₊‖₂
    grad_norm: float    # ‖∇g‖₂
    gamma: float        # γ used for the last iteration of the chunk
    elapsed: float      # seconds since the solve started (compile included)
    stalled: bool       # rel_dual < SolveConfig.gamma_stall_tol


class SolveState(NamedTuple):
    """Maximizer state (λ == paper's λ1, y == paper's λ2/momentum).

    The shared fields are what the engine itself touches (chunking, health
    guard, checkpoint keys); `extra` is the active UpdateRule's state
    extension — a rule-specific NamedTuple pytree (core/update_rules.py),
    or the default `()` for rules that fit in the shared fields.  An empty
    tuple contributes no pytree leaves, so rules without extras (agd, pga,
    bb) keep the exact pre-rule-engine state layout: scan carries,
    donation, and checkpoint key sets are unchanged."""

    lam: jax.Array          # (m, J) current dual iterate, λ >= 0
    y: jax.Array            # (m, J) extrapolated iterate
    lam_prev: jax.Array     # (m, J)
    grad_prev: jax.Array    # (m, J) ∇g at previous y
    y_prev: jax.Array       # (m, J)
    step: jax.Array         # scalar, current step size
    l_est: jax.Array        # scalar, running local-Lipschitz estimate
    k_mom: jax.Array        # scalar int32, momentum age (reset on restart)
    it: jax.Array           # scalar int32
    extra: Any = ()         # rule-specific state extension (pytree)


class IterStats(NamedTuple):
    dual_obj: jax.Array       # g(λ)
    primal_obj: jax.Array     # cᵀx*(λ)
    infeas: jax.Array         # ||(Ax*-b)+||₂
    grad_norm: jax.Array
    step: jax.Array
    gamma: jax.Array


class SolveResult(NamedTuple):
    """Solve output.  `stats` is stacked over the iterations actually
    executed (`iterations_run` entries — a tolerance-terminated solve returns
    a shorter trajectory than the iteration cap; on a resumed solve only the
    post-resume iterations, while `iterations_run` counts globally).
    `diagnostics` is the per-check stream of host-side scalars (empty for
    fixed-length solves).  `health` is the health-guard incident stream
    (DESIGN.md §9; empty unless a HealthConfig was active and tripped).
    `final_state` is the full device-resident SolveState at exit — what a
    preemption-safe checkpoint persists so a resume continues the exact
    trajectory; populated on every chunked solve, None on the fixed-length
    fast path."""

    lam: jax.Array
    stats: IterStats          # stacked over executed iterations
    iterations_run: int = 0
    converged: bool = False
    stop_reason: Optional[StopReason] = None
    diagnostics: Tuple[ConvergenceCheck, ...] = ()
    health: Tuple[HealthRecord, ...] = ()
    final_state: Optional["SolveState"] = None
