"""ObjectiveFunction — dual value and gradient for matching LPs (paper §3-§4).

The dual of the ridge-perturbed LP is
    g(λ) = min_{x∈C} cᵀx + (γ/2)‖x‖² + λᵀ(Ax − b),
maximized over λ >= 0, with
    x*_γ(λ) = Π_C( −(Aᵀλ + c)/γ ),          ∇g(λ) = A x*_γ(λ) − b.

On the bucketed-slab layout every step is a dense masked row-op:
  1. gather λ at each edge's destination:     lam_e = λ[:, dest_idx]   (m,n,w)
  2. pre-projection point: u = −(Σ_k a_k·λ_k + c)/γ                    (n,w)
  3. blockwise projection x = Π_C(u) per source row                    (n,w)
  4. per-edge grad vals g_e = a_k · x, reduced by destination into Ax
  5. local scalars: cᵀx, ‖x‖², λᵀAx accumulate into g(λ).

Step 4 is the only non-local stage, and `ax_mode` selects how it runs
(DESIGN.md §3):
  "scatter"        per-slab `segment_sum` keyed by destination (random
                   scatter-add — the paper-faithful baseline);
  "sorted"         edges pre-sorted by destination at construction so the
                   segmented sum takes the `indices_are_sorted` fast path;
  "aligned"        the x-carry sweep: the per-edge gradient tensor
                   (gvals) is never materialized, and Ax is reduced from
                   x by one of two paths, chosen by `select_ax_path` from
                   what the objective observes.  On a TPU, with λ
                   unsharded and a small dual table, one-hot matmuls on
                   the MXU accumulate each slab straight into an (m, J)
                   dual tile (`ops.ax_dual_tile`).  Otherwise the
                   value-carrying destination-major companion layout
                   (`AxPlan` with `a_dm`) packs a static copy of the
                   constraint weights per dual row, and
                   `ax[r,k] = Σ_q mask · a_dm[r,q,k] · x[edge_idx[r,q]]`
                   is gathered from the (E,) x vector.
  "aligned_gvals"  the index-only aligned layout: gvals are materialized
                   per slab, concatenated to (E, m), and gather-row-summed
                   (the pre-value-carrying lowering, kept as the measured
                   baseline for the x-carry traffic claim).

The legacy gvals-producing sweep survives untouched for
scatter/sorted/aligned_gvals; "aligned" routes through the gvals-free
`slab_xcarry` + `ops.ax_dual_tile` or `ops.ax_aligned_x`.
"""
from __future__ import annotations

import warnings
from functools import partial
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.obs import note_ax_path

from . import projections
from .types import AxPlan, LPData, Slab, dest_gather, edge_space

AX_MODES = ("scatter", "sorted", "aligned", "aligned_gvals")

# Op-name scopes of the sweep's stages (DESIGN.md §11).  Trace-time
# metadata only: they name each device op's stage in a profile and change
# neither numerics nor fusion.
LAMBDA_GATHER = "sweep.lambda_gather"   # λ read at each edge, Aᵀλ
PROJECT = "sweep.project"               # u, x = Π_C(u), cᵀx and ‖x‖²
AX = "sweep.ax"                         # the Ax reduction by destination
COLLECTIVE = "sweep.collective"         # the cross-shard sums, g, ∇g, infeas


def select_ax_path(platform: str, lambda_axis: Optional[str], m: int,
                   num_destinations: int) -> str:
    """How the x-carry sweep reduces Ax (DESIGN.md §3): `dual_tile` on a
    TPU when λ is not sharded and the dual tile's 3·m·⌈J/128⌉ rows are at
    most `MAX_TILE_ROWS`, where the one-hot matmuls beat the AxPlan's
    gathers; `ax_plan` otherwise."""
    from repro.kernels.ax_dual_tile import MAX_TILE_ROWS, tile_rows
    if (platform == "tpu" and lambda_axis is None
            and tile_rows(m, num_destinations) <= MAX_TILE_ROWS):
        return "dual_tile"
    return "ax_plan"


def platform_of(tree) -> str:
    """The platform of the first device or placed array in `tree`; the
    default backend when there is none (numpy arrays, tracers)."""
    for leaf in jax.tree.leaves(tree):
        if isinstance(leaf, jax.Device):
            return leaf.platform
        if isinstance(leaf, jax.Array) and not isinstance(leaf,
                                                          jax.core.Tracer):
            return next(iter(leaf.devices())).platform
    return jax.default_backend()


class ObjectiveAux(NamedTuple):
    primal_obj: jax.Array   # cᵀx*(λ)
    x_sq: jax.Array         # ‖x‖²
    ax: jax.Array           # (m, J)  A x*(λ)
    infeas: jax.Array       # ‖(Ax−b)₊‖₂


def slab_xstar(slab: Slab, lam: jax.Array, gamma: jax.Array,
               proj_kind: str, proj_iters: int = 40,
               use_pallas: bool = False) -> jax.Array:
    """x*(λ) for one slab: gather λ, form u, project.  Returns (n, w)."""
    if use_pallas:
        from repro.kernels import ops as kops
        with jax.named_scope(PROJECT):
            return kops.dual_xstar(slab, lam, gamma, proj_kind, proj_iters)
    with jax.named_scope(LAMBDA_GATHER):
        lam_e = dest_gather(lam, slab.dest_idx)             # (m, n, w)
        atl = jnp.einsum("nwm,mnw->nw", slab.a_vals, lam_e)  # Aᵀλ at edges
    with jax.named_scope(PROJECT):
        u = -(atl + slab.c_vals) / gamma
        return projections.project(proj_kind, u, slab.ub, slab.s, slab.mask,
                                   iters=proj_iters)


def slab_xgvals(slab: Slab, lam: jax.Array, gamma: jax.Array,
                proj_kind: str, proj_iters: int = 40,
                use_pallas: bool = False, shift=None):
    """Fused per-slab forward pass: (x*, gvals, cᵀx, ‖x‖²).

    `shift` is the contribution of coupling (non-destination-keyed) dual
    rows to u, folded into c so the jnp and Pallas paths share one
    implementation.  A scalar shift is the uniform all-ones row of
    GlobalCountObjective; an (n, w) array shift carries per-edge-weighted
    global rows (formulations subsystem, DESIGN.md §5) — zero on padding by
    construction.  With `use_pallas` the fused dual_grad kernel's
    gvals/c_x/x_sq outputs are consumed directly instead of being discarded
    and recomputed outside.
    """
    if use_pallas:
        from repro.kernels import ops as kops
        kslab = (slab if shift is None
                 else slab._replace(c_vals=slab.c_vals + shift))
        with jax.named_scope(PROJECT):
            x, gvals, c_x, x_sq = kops.dual_grad_full(
                kslab, lam, gamma, proj_kind, proj_iters)
            if shift is not None:
                # kernel saw c+μ, so its cᵀx includes the shift term (x is
                # 0 on padding); subtract it back out
                if jnp.ndim(shift):
                    c_x = c_x - jnp.vdot(shift, x)
                else:
                    c_x = c_x - shift * jnp.sum(x)
            return x, gvals, c_x, x_sq
    with jax.named_scope(LAMBDA_GATHER):
        lam_e = dest_gather(lam, slab.dest_idx)
        atl = jnp.einsum("nwm,mnw->nw", slab.a_vals, lam_e)
        if shift is not None:
            atl = atl + shift
    with jax.named_scope(PROJECT):
        u = -(atl + slab.c_vals) / gamma
        x = projections.project(proj_kind, u, slab.ub, slab.s, slab.mask,
                                iters=proj_iters)
        c_x, x_sq = jnp.vdot(slab.c_vals, x), jnp.vdot(x, x)
    with jax.named_scope(AX):
        gvals = slab.a_vals * x[..., None]                  # (n, w, m)
    return x, gvals, c_x, x_sq


def slab_xcarry(slab: Slab, lam: jax.Array, gamma: jax.Array,
                proj_kind: str, proj_iters: int = 40,
                use_pallas: bool = False, shift=None):
    """Gvals-free per-slab forward pass: (x*, cᵀx, ‖x‖²).

    The x-carry twin of `slab_xgvals` for the value-carrying aligned
    layout (DESIGN.md §3): the per-edge gradient tensor is never formed —
    the Ax reduction multiplies by the plan's static `a_dm` copy instead.
    Identical math for x/cᵀx/‖x‖² (same `shift` hook, same Pallas c-fold);
    keep the two in lockstep when editing either.  On the Pallas path this
    consumes the gvals-free `dual_x` kernel, dropping the fused kernel's
    largest output — the (n, w, m) HBM write and its VMEM tile.
    """
    if use_pallas:
        from repro.kernels import ops as kops
        kslab = (slab if shift is None
                 else slab._replace(c_vals=slab.c_vals + shift))
        with jax.named_scope(PROJECT):
            x, c_x, x_sq = kops.dual_x_full(kslab, lam, gamma, proj_kind,
                                            proj_iters)
            if shift is not None:
                # kernel saw c+μ: subtract the shift term back out of cᵀx
                if jnp.ndim(shift):
                    c_x = c_x - jnp.vdot(shift, x)
                else:
                    c_x = c_x - shift * jnp.sum(x)
            return x, c_x, x_sq
    with jax.named_scope(LAMBDA_GATHER):
        lam_e = dest_gather(lam, slab.dest_idx)
        atl = jnp.einsum("nwm,mnw->nw", slab.a_vals, lam_e)
        if shift is not None:
            atl = atl + shift
    with jax.named_scope(PROJECT):
        u = -(atl + slab.c_vals) / gamma
        x = projections.project(proj_kind, u, slab.ub, slab.s, slab.mask,
                                iters=proj_iters)
        return x, jnp.vdot(slab.c_vals, x), jnp.vdot(x, x)


def _segment_ax(gvals_flat: jax.Array, flat_dest: jax.Array,
                num_destinations: int, indices_are_sorted: bool = False):
    """(m, J) destination-keyed segmented sum of flattened gvals (E, m)."""
    return jax.vmap(
        lambda g: jax.ops.segment_sum(g, flat_dest,
                                      num_segments=num_destinations,
                                      indices_are_sorted=indices_are_sorted),
        in_axes=-1, out_axes=0,
    )(gvals_flat)


def slab_contribution(slab: Slab, lam: jax.Array, gamma: jax.Array,
                      num_destinations: int, proj_kind: str,
                      proj_iters: int = 40, use_pallas: bool = False):
    """One slab's (Ax partial, cᵀx, ‖x‖²) via the destination scatter."""
    x, gvals, c_x, x_sq = slab_xgvals(slab, lam, gamma, proj_kind,
                                      proj_iters, use_pallas)
    with jax.named_scope(AX):
        ax = _segment_ax(edge_space(gvals), edge_space(slab.dest_idx),
                         num_destinations)
    return ax, c_x, x_sq


def dual_value_and_grad(
    lp: LPData,
    lam: jax.Array,
    gamma: jax.Array,
    proj_kind: str = "boxcut",
    proj_iters: int = 40,
    use_pallas: bool = False,
    ax_reducer=None,
) -> Tuple[jax.Array, jax.Array, ObjectiveAux]:
    """g(λ), ∇g(λ), and diagnostics (functional scatter-mode entry point).

    `ax_reducer` is the distribution hook: it reduces the locally-computed
    (Ax, cᵀx, ‖x‖²) across shards (e.g. `jax.lax.psum` inside shard_map).
    `None` means single-shard.
    """
    J = lp.num_destinations
    ax = jnp.zeros((lp.m, J), lam.dtype)
    c_x = jnp.zeros((), lam.dtype)
    x_sq = jnp.zeros((), lam.dtype)
    for slab in lp.slabs:
        ax_s, c_s, sq_s = slab_contribution(
            slab, lam, gamma, J, proj_kind, proj_iters, use_pallas)
        ax, c_x, x_sq = ax + ax_s, c_x + c_s, x_sq + sq_s
    if ax_reducer is not None:
        ax, c_x, x_sq = ax_reducer((ax, c_x, x_sq))
    grad = ax - lp.b
    g = c_x + 0.5 * gamma * x_sq + jnp.vdot(lam, grad)
    infeas = jnp.linalg.norm(jnp.maximum(grad, 0.0))
    return g, grad, ObjectiveAux(primal_obj=c_x, x_sq=x_sq, ax=ax, infeas=infeas)


class MatchingObjective:
    """Paper §4 `ObjectiveFunction` facade.

    Encapsulates LP tensors + a ProjectionMap; exposes the single method
    `calculate(λ, γ) -> (g, ∇g, aux)`.  The Maximizer only ever sees this
    interface, so new formulations (different layout, extra constraint
    families, a global count constraint, ...) are purely local changes.

    `ax_mode` selects the Ax reduction (module docstring): "scatter"
    (paper-faithful segment-sum), "sorted" (§Perf it3: edges pre-sorted by
    destination at construction so the segmented sum takes the
    `indices_are_sorted` fast path), "aligned" (§Perf it6/it7: the
    value-carrying destination-major `AxPlan` — x-only hot path, no gvals
    materialization), or "aligned_gvals" (§Perf it4/it5: the index-only
    aligned gather-reduce over a materialized (E, m) gvals tensor).  The
    deprecated `sorted_scatter=True` flag is an alias for
    `ax_mode="sorted"`.

    Re-registered as the declarative formulation "matching"
    (repro.formulations, DESIGN.md §5): the compiled ComposedObjective is
    operation-for-operation this class, and new formulations compose this
    sweep rather than subclassing it.
    """

    def __init__(self, lp: LPData, projection_map=None, proj_kind: str = "boxcut",
                 proj_iters: int = 40, use_pallas: bool = False,
                 ax_reducer=None, ax_mode: Optional[str] = None,
                 sorted_scatter: bool = False,
                 ax_plan: Optional[AxPlan] = None):
        self.lp = lp
        # A ProjectionMap carries a default kind, a per-bucket override table,
        # and its own iteration count — honor all three (block id == slab
        # index), not just `.kind`.
        if projection_map is not None:
            self.proj_kind = projection_map.kind
            self.proj_iters = projection_map.iters
            self._slab_proj = tuple(
                (projection_map.kind_for(i), projection_map.iters_for(i))
                for i in range(len(lp.slabs)))
        else:
            self.proj_kind = proj_kind
            self.proj_iters = proj_iters
            self._slab_proj = tuple(
                (proj_kind, proj_iters) for _ in range(len(lp.slabs)))
        self.use_pallas = use_pallas
        self.ax_reducer = ax_reducer
        if sorted_scatter:
            warnings.warn(
                "MatchingObjective(sorted_scatter=True) is deprecated; use "
                "ax_mode='sorted' instead", DeprecationWarning, stacklevel=2)
        if ax_mode is None:
            ax_mode = "sorted" if sorted_scatter else "scatter"
        if ax_mode not in AX_MODES:
            raise ValueError(f"ax_mode must be one of {AX_MODES}, got {ax_mode!r}")
        self.ax_mode = ax_mode
        self.sorted_scatter = ax_mode == "sorted"   # kept for introspection
        if ax_mode == "sorted":
            import numpy as np
            dests = np.concatenate([edge_space(np.asarray(s.dest_idx))
                                    for s in lp.slabs])
            self._perm = jnp.asarray(np.argsort(dests, kind="stable"))
            self._sorted_dest = jnp.asarray(np.sort(dests, kind="stable"))
        self.ax_path = None
        self._plan = None
        if ax_mode == "aligned":
            self.ax_path = select_ax_path(platform_of(lp), None, lp.m,
                                          lp.num_destinations)
            note_ax_path(self.ax_path)
        if ax_mode == "aligned_gvals" or self.ax_path == "ax_plan":
            if ax_plan is None:
                from .instance import build_ax_plan
                ax_plan = build_ax_plan(lp,
                                        carry_values=(ax_mode == "aligned"))
            if ax_mode == "aligned" and any(b.a_dm is None
                                            for b in ax_plan.buckets):
                raise ValueError(
                    "ax_mode='aligned' (x-carry) needs a value-carrying "
                    "plan; rebuild with build_ax_plan(lp, "
                    "carry_values=True) or use ax_mode='aligned_gvals'")
            self._plan = jax.tree.map(jnp.asarray, ax_plan)

    @property
    def dual_shape(self) -> Tuple[int, int]:
        return (self.lp.m, self.lp.num_destinations)

    @property
    def _carry_x(self) -> bool:
        """True when the sweep is x-only (value-carrying aligned mode):
        slabs emit their (n, w) x instead of (E, m) gvals."""
        return self.ax_mode == "aligned"

    def _reduce_ax(self, parts, dtype):
        """(m, J) Ax from per-slab parts, per the selected mode.

        For the x-carry "aligned" mode `parts` are each slab's (n, w) x
        (the only dynamic per-edge array): the dual tile reduces them slab
        by slab, the AxPlan path from their (E,) concatenation.  For every
        gvals mode they are (n·w, m) per-edge gradient values.
        """
        with jax.named_scope(AX):
            lp = self.lp
            J = lp.num_destinations
            if self.ax_mode == "aligned":
                from repro.kernels import ops as kops
                if self.ax_path == "dual_tile":
                    return kops.ax_dual_tile(lp.slabs, parts, J).astype(dtype)
                return kops.ax_aligned_x(
                    self._plan,
                    jnp.concatenate([edge_space(x) for x in parts]),
                    use_pallas=self.use_pallas, out_dtype=dtype)
            if self.ax_mode == "aligned_gvals":
                from repro.kernels import ops as kops
                return kops.ax_aligned(self._plan,
                                       jnp.concatenate(parts, axis=0),
                                       use_pallas=self.use_pallas,
                                       out_dtype=dtype)
            if self.ax_mode == "sorted":
                gvals = jnp.concatenate(parts, axis=0)[self._perm]
                return _segment_ax(gvals, self._sorted_dest, J,
                                   indices_are_sorted=True)
            ax = jnp.zeros((lp.m, J), dtype)
            for slab, part in zip(lp.slabs, parts):
                ax = ax + _segment_ax(part, edge_space(slab.dest_idx), J)
            return ax

    def _forward(self, lam: jax.Array, gamma: jax.Array, shift=None,
                 with_xsum: bool = False):
        """Shared slab sweep: (Ax, cᵀx, ‖x‖², Σx) for any ax_mode.

        The x-carry aligned mode runs the gvals-free `slab_xcarry` sweep;
        every other mode keeps the legacy gvals-producing `slab_xgvals`
        sweep untouched (the paper-faithful baselines).
        """
        parts = []
        c_x = jnp.zeros((), lam.dtype)
        x_sq = jnp.zeros((), lam.dtype)
        x_sum = jnp.zeros((), lam.dtype)
        carry = self._carry_x
        for slab, (kind, iters) in zip(self.lp.slabs, self._slab_proj):
            if carry:
                x, c_s, sq_s = slab_xcarry(
                    slab, lam, gamma, kind, iters, self.use_pallas, shift)
                parts.append(x)
            else:
                x, gvals, c_s, sq_s = slab_xgvals(
                    slab, lam, gamma, kind, iters, self.use_pallas, shift)
                parts.append(edge_space(gvals))
            c_x = c_x + c_s
            x_sq = x_sq + sq_s
            if with_xsum:
                x_sum = x_sum + jnp.sum(x)
        return self._reduce_ax(parts, lam.dtype), c_x, x_sq, x_sum

    def calculate(self, lam: jax.Array, gamma: jax.Array):
        ax, c_x, x_sq, _ = self._forward(lam, gamma)
        with jax.named_scope(COLLECTIVE):
            if self.ax_reducer is not None:
                ax, c_x, x_sq = self.ax_reducer((ax, c_x, x_sq))
            grad = ax - self.lp.b
            g = c_x + 0.5 * gamma * x_sq + jnp.vdot(lam, grad)
            infeas = jnp.linalg.norm(jnp.maximum(grad, 0.0))
        return g, grad, ObjectiveAux(primal_obj=c_x, x_sq=x_sq, ax=ax,
                                     infeas=infeas)

    def primal(self, lam: jax.Array, gamma: jax.Array):
        """Recover the (padded) primal solution x*(λ) slab by slab."""
        return [
            slab_xstar(s, lam, gamma, kind, iters, self.use_pallas)
            for s, (kind, iters) in zip(self.lp.slabs, self._slab_proj)
        ]

    def _dual_parts(self, lam: jax.Array):
        """Decompose a dual vector into (dest-block λ, per-slab shift fn).

        The uniform hook behind every primal-recovery surface: subclasses
        with extra dual rows (GlobalCountObjective's μ, ComposedObjective's
        coupling rows) override it so `primal_rows` — and with it the whole
        serving/extraction subsystem (DESIGN.md §8) — works unchanged on
        any formulation.  The shift fn maps a slab index to the coupling
        contribution consumed by `slab_xcarry`'s shift hook (None, scalar,
        or a per-slab (n, w) array)."""
        return lam, lambda si: None

    def primal_rows(self, lam: jax.Array, gamma: jax.Array,
                    slab_index: int, rows: jax.Array) -> jax.Array:
        """x*(λ) for a subset of one slab's source rows — the serving path.

        Gathers the requested rows of slab `slab_index` (and, for array
        shifts, the matching shift rows) and runs the same per-row sweep as
        the batch `primal()`: every operation is row-local (einsum over the
        family axis, per-row projection), so the result is BITWISE equal to
        the corresponding rows of the full-slab recovery — asserted in
        tests/test_primal_serving.py.  `rows` is a 1-D int array of row
        indices into the slab; duplicates are allowed (the extraction tail
        chunk clamps its window).
        """
        lam_block, shift_fn = self._dual_parts(lam)
        slab = self.lp.slabs[slab_index]
        kind, iters = self._slab_proj[slab_index]
        sub = Slab(*(leaf[rows] for leaf in slab))
        shift = shift_fn(slab_index)
        if shift is not None and jnp.ndim(shift):
            shift = shift[rows]
        return slab_xcarry(sub, lam_block, gamma, kind, iters,
                           self.use_pallas, shift)[0]


class GlobalCountObjective(MatchingObjective):
    """The paper's §4 motivating extension: add a global count constraint
    Σ_ij x_ij <= count as ONE extra dual row, composed locally.

    A_extra is all-ones on real edges; implemented by treating the extra row
    as an (m+1)-th family whose λ enters u uniformly (the `shift` hook of
    `slab_xgvals`) and whose Ax entry is Σ x.  Demonstrates that 'appending
    a constraint' is a ~20-line subclass here versus 'extensive changes
    across the code base' in Scala DuaLip — and, because it rides the shared
    `_forward` sweep, it inherits every `ax_mode` and the Pallas path for
    free.

    Re-registered as the declarative formulation "global_count"
    (repro.formulations, DESIGN.md §5), which generalizes the single
    all-ones row to any number of weighted global budget rows.
    """

    def __init__(self, lp: LPData, count: float, **kw):
        super().__init__(lp, **kw)
        self.count = count

    @property
    def dual_shape(self) -> Tuple[int, int]:
        m, J = super().dual_shape
        return (m * J + 1,)  # flattened + 1 global row

    def calculate(self, lam_flat: jax.Array, gamma: jax.Array):
        m, J = self.lp.m, self.lp.num_destinations
        lam = lam_flat[:-1].reshape(m, J)
        mu = lam_flat[-1]
        ax, c_x, x_sq, x_sum = self._forward(lam, gamma, shift=mu,
                                             with_xsum=True)
        if self.ax_reducer is not None:
            ax, c_x, x_sq, x_sum = self.ax_reducer((ax, c_x, x_sq, x_sum))
        grad_main = ax - self.lp.b
        grad_cnt = x_sum - self.count
        g = (c_x + 0.5 * gamma * x_sq + jnp.vdot(lam, grad_main)
             + mu * grad_cnt)
        grad = jnp.concatenate([grad_main.reshape(-1), grad_cnt[None]])
        infeas = jnp.linalg.norm(jnp.maximum(grad, 0.0))
        aux = ObjectiveAux(primal_obj=c_x, x_sq=x_sq, ax=ax, infeas=infeas)
        return g, grad, aux

    def primal(self, lam_flat: jax.Array, gamma: jax.Array):
        """Recover x*(λ) slab by slab from the flat (m·J+1,) dual vector.

        The inherited `MatchingObjective.primal` would index λ_flat as if
        it were the (m, J) block — reading garbage destinations — and drop
        the global row's μ shift from u entirely.  Reshape the dest block
        and thread μ through the shift hook, exactly as `calculate` does.
        """
        m, J = self.lp.m, self.lp.num_destinations
        lam = lam_flat[:-1].reshape(m, J)
        mu = lam_flat[-1]
        return [
            slab_xcarry(s, lam, gamma, kind, iters, self.use_pallas,
                        shift=mu)[0]
            for s, (kind, iters) in zip(self.lp.slabs, self._slab_proj)
        ]

    def _dual_parts(self, lam_flat: jax.Array):
        """Dest block + the uniform μ shift of the global count row, so the
        row-subset serving path recovers the same x* as `primal`."""
        m, J = self.lp.m, self.lp.num_destinations
        mu = lam_flat[-1]
        return lam_flat[:-1].reshape(m, J), lambda si: mu
