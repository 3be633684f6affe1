"""Formulation compiler: lower a declarative spec onto the engine (DESIGN.md §5).

`compile_formulation(form, lp)` turns a `Formulation` into a
`ComposedObjective` — an ObjectiveFunction the unchanged SolveEngine /
Maximizer / stopping criteria consume directly.  Lowering steps:

  1. **Row-block selection**: slice the LPData to the DestCapacityFamily's
     lp_families and apply its rhs_scale (compile-time, host-side).
  2. **Weight materialization**: each GlobalBudgetFamily's per-edge weights
     w become per-slab (n, w) tensors (or None for the all-ones "count"
     row, which keeps the scalar uniform-shift fast path).  Weights are
     read from the *original* coefficients, before preconditioning.
  3. **Preconditioning hook**: `row_norm=True` applies the §5.1 Jacobi row
     normalization to the dest-capacity rows (global rows are their own
     dual rows and stay unscaled); the scaling is kept on the compiled
     objective for λ unscaling.
  4. **Projection lowering**: the BlockConstraint becomes a ProjectionMap
     (kind + per-bucket overrides + iters) consumed by the slab sweep.
  5. **Ax lowering**: the dest block inherits MatchingObjective's full
     ax_mode machinery — scatter / sorted perm / aligned AxPlan (built
     here if not supplied) — and the Pallas paths.  Global rows lower to
     scalar masked reductions (Σ w·x); they need no plan.

The emitted dual vector is 1-D: `[dest block flattened (m·J, family-major)
| one entry per global row, declaration order]`.  With no global rows the
computation is operation-for-operation identical to `MatchingObjective`;
with exactly one "count" row it is identical to `GlobalCountObjective`
(asserted bitwise in tests/test_formulations.py).
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.instance import validate_lp
from repro.core.objectives import (AX_MODES, MatchingObjective, ObjectiveAux,
                                   slab_xcarry, slab_xgvals)
from repro.core.preconditioning import row_normalize
from repro.core.projections import ProjectionMap
from repro.core.types import AxPlan, LPData, edge_space

from .spec import Formulation, GlobalBudgetFamily


def _slice_lp(lp: LPData, dest) -> LPData:
    """Apply the DestCapacityFamily's compile-time LP transform: family
    selection, optional rhs override, rhs scaling."""
    if dest.lp_families is not None:
        idx = jnp.asarray(tuple(int(k) for k in dest.lp_families))
        slabs = tuple(s._replace(a_vals=s.a_vals[..., idx])
                      for s in lp.slabs)
        lp = LPData(slabs=slabs, b=lp.b[idx])
    if dest.rhs is not None:
        b = jnp.asarray(dest.rhs, dtype=lp.b.dtype)
        if b.shape != lp.b.shape:
            raise ValueError(
                f"rhs override shape {b.shape} != expected {lp.b.shape}")
        lp = LPData(slabs=lp.slabs, b=b)
    if dest.rhs_scale != 1.0:
        lp = LPData(slabs=lp.slabs, b=lp.b * dest.rhs_scale)
    return lp


def _materialize_weights(lp: LPData, row: GlobalBudgetFamily):
    """Per-slab (n, w) weight tensors for one global row; None = all-ones.

    Weights are zero on padded entries by construction (c_vals and a_vals
    are zero there), so masked edges never contribute to shifts or sums.
    """
    if row.weight == "count":
        return None
    if row.weight == "value":
        # minimization convention: c = −value, so the edge's value is −c
        return tuple(-s.c_vals for s in lp.slabs)
    kind, k = row.weight                      # ("lp_family", k), validated
    return tuple(s.a_vals[..., int(k)] for s in lp.slabs)


class ComposedObjective(MatchingObjective):
    """The compiled form of a Formulation: dual value/gradient as the sum
    over constraint families, λ concatenated across row blocks.

    Subclasses MatchingObjective so the dest-capacity block reuses the
    shared `_forward` sweep verbatim — slab projection table, every
    ax_mode, the Pallas kernels, the ax_reducer distribution hook.  Global
    rows enter through the shift hook of `slab_xgvals` and add one scalar
    gradient entry each.  Construct via `compile_formulation`, not
    directly.

    `global_scales` is the Jacobi factor σ_r = 1/‖w_r‖₂ applied to each
    coupling row when the preconditioning hook is on (w' = σw, limit' =
    σ·limit, dual row λ'_r = λ_r/σ): without it, an unnormalized coupling
    row's gradient runs ~‖w‖ hotter than the normalized dest rows and the
    shared step size crawls.  σ_r = 1 reproduces the legacy un-normalized
    semantics bit-for-bit.  Weighted rows arrive with σ already folded
    into their weight tensors; all-ones "count" rows keep weights=None and
    apply σ symbolically (a uniform row stays uniform under scaling, so
    the scalar-shift fast path survives).
    """

    def __init__(self, lp: LPData, formulation: Formulation,
                 global_weights: Tuple, global_scales: Tuple = None,
                 row_scaling=None, **kw):
        super().__init__(lp, **kw)
        self.formulation = formulation
        self._global_rows = formulation.global_rows
        self._global_weights = tuple(global_weights)
        self._scales = (tuple(global_scales) if global_scales is not None
                        else (1.0,) * len(self._global_rows))
        self._limits_raw = tuple(float(r.limit) for r in self._global_rows)
        self._limits = tuple(lim * s for lim, s
                             in zip(self._limits_raw, self._scales))
        self.row_scaling = row_scaling       # preconditioning undo info
        assert len(self._global_weights) == len(self._global_rows)
        assert len(self._scales) == len(self._global_rows)

    @property
    def dual_shape(self) -> Tuple[int]:
        m, J = self.lp.m, self.lp.num_destinations
        return (m * J + len(self._global_rows),)

    def row_slices(self):
        """{family label: slice into the composed λ vector}."""
        m, J = self.lp.m, self.lp.num_destinations
        out = {self.formulation.dest.label: slice(0, m * J)}
        for i, row in enumerate(self._global_rows):
            out[row.label] = slice(m * J + i, m * J + i + 1)
        return out

    def _shift_for(self, slab_index: int, mus):
        """Σ_r μ_r·w_r for one slab: scalar when every row is all-ones.

        Weighted rows carry σ inside their tensors; count rows apply it
        here (σ == 1.0 keeps the exact legacy expression)."""
        shift = None
        for mu, w, s in zip(mus, self._global_weights, self._scales):
            if w is None:
                term = mu if s == 1.0 else mu * s
            else:
                term = mu * w[slab_index]
            shift = term if shift is None else shift + term
        return shift

    def _forward_rows(self, lam: jax.Array, gamma: jax.Array, mus):
        """Generalized slab sweep: (Ax, cᵀx, ‖x‖², [Σ w_r·x per row]).

        Mirrors MatchingObjective._forward (which must stay untouched for
        the bitwise legacy-parity guarantees) with two generalizations:
        the per-slab shift from the coupling rows, and one weighted-sum
        accumulator per row.  The coupling rows already consume x, so the
        x-carry aligned mode is free here: collect the (E,) x parts
        (gvals-free `slab_xcarry` sweep) and reduce through the
        value-carrying plan.  Keep the sweeps in lockstep when editing
        either."""
        parts = []
        c_x = jnp.zeros((), lam.dtype)
        x_sq = jnp.zeros((), lam.dtype)
        wx = [jnp.zeros((), lam.dtype) for _ in self._global_rows]
        carry = self._carry_x
        for si, (slab, (kind, iters)) in enumerate(
                zip(self.lp.slabs, self._slab_proj)):
            if carry:
                x, c_s, sq_s = slab_xcarry(
                    slab, lam, gamma, kind, iters, self.use_pallas,
                    self._shift_for(si, mus))
                parts.append(edge_space(x))
            else:
                x, gvals, c_s, sq_s = slab_xgvals(
                    slab, lam, gamma, kind, iters, self.use_pallas,
                    self._shift_for(si, mus))
                parts.append(edge_space(gvals))
            c_x = c_x + c_s
            x_sq = x_sq + sq_s
            for r, (w, s) in enumerate(zip(self._global_weights,
                                           self._scales)):
                if w is None:
                    val = jnp.sum(x) if s == 1.0 else s * jnp.sum(x)
                else:
                    val = jnp.vdot(w[si], x)
                wx[r] = wx[r] + val
        return self._reduce_ax(parts, lam.dtype), c_x, x_sq, wx

    def calculate(self, lam_flat: jax.Array, gamma: jax.Array):
        m, J = self.lp.m, self.lp.num_destinations
        k = m * J
        lam = lam_flat[:k].reshape(m, J)
        mus = [lam_flat[k + r] for r in range(len(self._global_rows))]
        if not self._global_rows:
            # pure dest-capacity block: exactly MatchingObjective.calculate
            ax, c_x, x_sq, _ = self._forward(lam, gamma)
            wx = []
        elif (len(self._global_rows) == 1
                and self._global_weights[0] is None
                and self._scales[0] == 1.0):
            # one un-normalized all-ones row: exactly
            # GlobalCountObjective.calculate
            ax, c_x, x_sq, x_sum = self._forward(lam, gamma, shift=mus[0],
                                                 with_xsum=True)
            wx = [x_sum]
        else:
            ax, c_x, x_sq, wx = self._forward_rows(lam, gamma, mus)
        if self.ax_reducer is not None:
            ax, c_x, x_sq, *wx = self.ax_reducer((ax, c_x, x_sq, *wx))
        grad_main = ax - self.lp.b
        g = c_x + 0.5 * gamma * x_sq + jnp.vdot(lam, grad_main)
        pieces = [grad_main.reshape(-1)]
        for mu, limit, w in zip(mus, self._limits, wx):
            grad_r = w - limit
            g = g + mu * grad_r
            pieces.append(grad_r[None])
        grad = jnp.concatenate(pieces) if len(pieces) > 1 else pieces[0]
        infeas = jnp.linalg.norm(jnp.maximum(grad, 0.0))
        return g, grad, ObjectiveAux(primal_obj=c_x, x_sq=x_sq, ax=ax,
                                     infeas=infeas)

    def primal(self, lam_flat: jax.Array, gamma: jax.Array):
        """Recover x*(λ) per slab — global-row shifts included (unlike the
        legacy GlobalCountObjective, whose inherited primal drops μ)."""
        m, J = self.lp.m, self.lp.num_destinations
        k = m * J
        lam = lam_flat[:k].reshape(m, J)
        mus = [lam_flat[k + r] for r in range(len(self._global_rows))]
        xs = []
        for si, (slab, (kind, iters)) in enumerate(
                zip(self.lp.slabs, self._slab_proj)):
            x, _, _ = slab_xcarry(slab, lam, gamma, kind, iters,
                                  self.use_pallas,
                                  self._shift_for(si, mus))
            xs.append(x)
        return xs

    def _dual_parts(self, lam_flat: jax.Array):
        """Dest block + the composed per-slab coupling shift, so the
        row-subset serving path (`primal_rows`, DESIGN.md §8) recovers
        exactly the same x* as the batch `primal` above."""
        m, J = self.lp.m, self.lp.num_destinations
        k = m * J
        lam = lam_flat[:k].reshape(m, J)
        mus = [lam_flat[k + r] for r in range(len(self._global_rows))]
        return lam, lambda si: self._shift_for(si, mus)

    def _row_usage(self, xs, r: int) -> float:
        """Σ w_r·x over all slabs at a candidate primal point, in ORIGINAL
        (un-normalized) row units (count rows keep raw all-ones weights;
        weighted tensors carry σ folded in — undo it)."""
        w = self._global_weights[r]
        return sum(float(jnp.sum(x)) if w is None
                   else float(jnp.vdot(w[si], jnp.asarray(x)))
                   / self._scales[r]
                   for si, x in enumerate(xs))

    def family_report(self, xs):
        """Per-family primal slack report at a candidate point xs — the
        certification hook (DESIGN.md §8).

        `xs` is a list of per-slab (n, w) primal values (padding entries
        ignored via the slab masks).  Each constraint family reports its
        own residual through the spec hooks (`DestCapacityFamily.residual`,
        `GlobalBudgetFamily.residual`): the dest-capacity block in the
        compiled (possibly row-normalized) units, coupling rows in original
        units (matching `global_usage`).  Returns plain dicts so the primal
        subsystem can wrap them without a layering cycle:
        {label: {kind, used, limit, max_violation, norm_violation}}.
        """
        import numpy as np
        # lazy import: primal is the serving layer above formulations, but
        # rounding.primal_ax is its dependency-free numpy accumulation —
        # the certification-critical computation must exist exactly once
        from repro.primal.rounding import primal_ax
        lp = self.lp
        ax = primal_ax(lp, xs)
        dest = self.formulation.dest
        res = np.asarray(dest.residual(ax, np.asarray(lp.b)))
        out = {dest.label: {
            "kind": "dest_capacity",
            "used": float(np.linalg.norm(np.maximum(res, 0.0))),
            "limit": 0.0,
            "max_violation": float(res.max()) if res.size else 0.0,
            "norm_violation": float(np.linalg.norm(np.maximum(res, 0.0))),
            "scale": 1.0 + float(np.abs(np.asarray(lp.b)).max()
                                 if np.asarray(lp.b).size else 0.0),
        }}
        for r, row in enumerate(self._global_rows):
            used = self._row_usage(xs, r)
            viol = float(row.residual(used))
            out[row.label] = {
                "kind": "global",
                "used": used,
                "limit": self._limits_raw[r],
                "max_violation": viol,
                "norm_violation": max(viol, 0.0),
                "scale": 1.0 + abs(self._limits_raw[r]),
            }
        return out

    def global_usage(self, lam_flat: jax.Array, gamma: jax.Array):
        """{row label: (Σ w·x at x*(λ), limit)} in ORIGINAL (un-normalized)
        row units — the constraint audit."""
        xs = self.primal(lam_flat, gamma)
        out = {}
        for r, (row, w) in enumerate(zip(self._global_rows,
                                         self._global_weights)):
            # count rows keep raw (all-ones) weights, so Σx is already in
            # original units; weighted tensors carry σ folded in — undo it
            used = sum(float(jnp.sum(x)) if w is None
                       else float(jnp.vdot(w[si], x)) / self._scales[r]
                       for si, x in enumerate(xs))
            out[row.label] = (used, self._limits_raw[r])
        return out


def compile_formulation(
    form: Formulation,
    lp: LPData,
    *,
    ax_mode: Optional[str] = None,
    use_pallas: bool = False,
    ax_reducer=None,
    ax_plan: Optional[AxPlan] = None,
    row_norm: bool = False,
) -> ComposedObjective:
    """Lower a Formulation onto the shared engine (module docstring)."""
    # reject malformed instances up front (NaN coefficients, negative
    # budgets, ragged slabs, out-of-range dest indices): an LPValidationError
    # here names every problem, where the solver would only surface NaNs
    # hundreds of iterations later
    validate_lp(lp, name=f"lp for formulation {form.name!r}")
    form.validate(lp.m)
    if ax_mode is not None and ax_mode not in AX_MODES:
        raise ValueError(f"ax_mode must be one of {AX_MODES}, got {ax_mode!r}")
    if use_pallas:
        kinds = {form.block.kind} | {
            ov[0] if isinstance(ov, tuple) else ov
            for ov in (form.block.overrides or {}).values()}
        bad = kinds - {"boxcut", "simplex", "box"}
        if bad:
            raise ValueError(
                f"formulation {form.name!r}: the Pallas path supports "
                f"boxcut/simplex/box blocks, not {sorted(bad)!r}")
    # weights read the original coefficients (lp_family indices refer to the
    # un-sliced LP; preconditioning must not rescale global-row semantics)
    weights = list(_materialize_weights(lp, r) for r in form.global_rows)
    scales = [1.0] * len(weights)
    if row_norm and weights:
        # extend the §5.1 Jacobi preconditioning to the coupling rows:
        # σ_r = 1/‖w_r‖₂ over real edges, folded into weighted tensors and
        # kept symbolic for the uniform count rows (see ComposedObjective)
        for r, w in enumerate(weights):
            if w is None:
                nnz = sum(float(jnp.sum(s.mask)) for s in lp.slabs)
                norm = nnz ** 0.5
            else:
                norm = float(sum(jnp.vdot(ws, ws) for ws in w)) ** 0.5
            if norm > 0:
                scales[r] = 1.0 / norm
                if w is not None:
                    weights[r] = tuple(ws * scales[r] for ws in w)
    # slab (n, w) geometry is untouched by family slicing / row-norm, so the
    # materialized weights stay aligned with the transformed slabs below
    lp = _slice_lp(lp, form.dest)
    row_scaling = None
    if row_norm:
        lp, row_scaling = row_normalize(lp)
    pmap = ProjectionMap(kind=form.block.kind,
                         overrides=form.block.overrides,
                         iters=form.block.iters)
    return ComposedObjective(
        lp, form, tuple(weights), global_scales=tuple(scales),
        row_scaling=row_scaling,
        projection_map=pmap, use_pallas=use_pallas,
        ax_reducer=ax_reducer, ax_mode=ax_mode, ax_plan=ax_plan)
