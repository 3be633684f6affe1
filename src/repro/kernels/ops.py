"""Public wrappers for the Pallas kernels.

On a TPU backend the kernels lower through Mosaic, always: nothing here
picks interpret mode there.  On any other backend (this CPU container,
unit tests) they run in interpret mode, which executes the kernel body in
Python with identical semantics.  The choice follows the platform JAX
runs on; callers do not make it.  `repro.core.objectives` routes through
these wrappers when SolveConfig.use_pallas is set, and through
`ax_dual_tile` wherever its Ax path rule picks the dual tile.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from . import ax_dual_tile as _ax_dual_tile
from . import ax_reduce as _ax_reduce
from . import dual_grad as _dual_grad
from . import proj as _proj
from repro.core.types import AxPlan, Slab


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def proj_boxcut(v, ub, s, mask, iters: int = _proj.DEFAULT_ITERS):
    return _proj.proj_boxcut(v, ub, s, mask, iters=iters,
                             interpret=_interpret())


def _kernel_slab(slab: Slab, proj_kind: str) -> Slab:
    """The slab the boxcut kernels see for a projection kind: simplex is
    boxcut with an unreachable per-edge bound."""
    if proj_kind == "simplex":
        return slab._replace(ub=jnp.full_like(slab.ub, 1e30))
    if proj_kind not in ("boxcut", "box"):
        raise NotImplementedError(
            f"pallas path supports boxcut/simplex/box, got {proj_kind}")
    return slab


def dual_grad_slab(slab: Slab, lam, gamma, iters: int = _proj.DEFAULT_ITERS):
    """Fused x*(λ)+gvals+scalars for one slab (kernel: dual_grad.py)."""
    return _dual_grad.dual_grad_slab(
        slab.a_vals, slab.c_vals, slab.dest_idx, slab.mask, slab.ub, slab.s,
        lam, gamma, iters=iters, interpret=_interpret())


def dual_grad_full(slab: Slab, lam, gamma, proj_kind: str = "boxcut",
                   iters: int = _proj.DEFAULT_ITERS):
    """Fused (x*, gvals, cᵀx, ‖x‖²) for one slab with proj-kind dispatch.

    Entry point used by repro.core.objectives.slab_xgvals(use_pallas=True):
    all four kernel outputs are consumed downstream — nothing recomputed.
    """
    return dual_grad_slab(_kernel_slab(slab, proj_kind), lam, gamma,
                          iters=iters)


def dual_xstar(slab: Slab, lam, gamma, proj_kind: str = "boxcut",
               iters: int = _proj.DEFAULT_ITERS):
    """x*(λ) for one slab via the fused kernel (boxcut/simplex kinds)."""
    return dual_x_full(slab, lam, gamma, proj_kind, iters)[0]


def dual_x_full(slab: Slab, lam, gamma, proj_kind: str = "boxcut",
                iters: int = _proj.DEFAULT_ITERS):
    """Gvals-free fused (x*, cᵀx, ‖x‖²) for one slab (kernel: dual_x_slab).

    Entry point for the value-carrying aligned path
    (`core.objectives.slab_xcarry(use_pallas=True)`): the kernel's largest
    output — the (n, w, m) per-edge gradient tile — is dropped entirely;
    the x-carry Ax reduction (`ax_aligned_x`) consumes x directly.
    """
    slab = _kernel_slab(slab, proj_kind)
    return _dual_grad.dual_x_slab(
        slab.a_vals, slab.c_vals, slab.dest_idx, slab.mask, slab.ub, slab.s,
        lam, gamma, iters=iters, interpret=_interpret())


def ax_reduce_bucket(gvals, edge_idx, mask):
    return _ax_reduce.ax_reduce_bucket(gvals, edge_idx, mask,
                                       interpret=_interpret())


def ax_aligned(plan: AxPlan, gvals: jax.Array, use_pallas: bool = False,
               out_dtype=None) -> jax.Array:
    """Scatter-free (m, J) Ax via the destination-major companion layout.

    gvals: (E, m) per-edge gradient values, flattened in slab concatenation
    order (the plan's edge space).  Per bucket the reduction is a masked
    gather row-sum — through the Pallas kernel when `use_pallas`, otherwise
    the XLA take+sum fallback; assembly into destination order is the
    inv_perm gather.  No scatter, no atomics anywhere.
    """
    rows = []
    for b in plan.buckets:
        if use_pallas:
            rows.append(ax_reduce_bucket(gvals, b.edge_idx, b.mask))
        else:  # XLA fallback: identical math, plain take+sum
            # plan indices are valid by construction: skip gather bounds
            # checks (they constant-fold painfully over E-sized index sets)
            g = gvals.at[b.edge_idx].get(
                mode="promise_in_bounds").astype(jnp.float32)
            rows.append(jnp.sum(jnp.where(b.mask[..., None], g, 0.0),
                                axis=1))
    rows = jnp.concatenate(rows, axis=0)               # (R, m) f32
    ax = rows.at[plan.inv_perm].get(                   # (m, J)
        mode="promise_in_bounds").T
    return ax.astype(out_dtype or gvals.dtype)


def ax_reduce_bucket_x(x, a_dm, edge_idx, mask):
    return _ax_reduce.ax_reduce_bucket_x(x, a_dm, edge_idx, mask,
                                         interpret=_interpret())


def ax_aligned_x(plan: AxPlan, x: jax.Array, use_pallas: bool = False,
                 out_dtype=None) -> jax.Array:
    """Value-carrying scatter-free (m, J) Ax: the x-only hot path.

    x: (E,) x*(λ) values, flattened in slab concatenation order (the
    plan's edge space).  The plan must be packed with `carry_values=True`
    so every bucket carries its static destination-major weight copy
    `a_dm`; the per-bucket reduction is then
    `Σ_q mask · a_dm[r, q] · x[edge_idx[r, q]]` — the (E, m) per-edge
    gradient tensor of `ax_aligned` never exists, and the only dynamic
    per-edge array read is x itself.  Products form in the input dtype
    (bit-matching the legacy gvals), accumulation is f32, assembly into
    destination order is the same inv_perm gather.
    """
    rows = []
    for b in plan.buckets:
        if b.a_dm is None:
            raise ValueError(
                "ax_aligned_x needs a value-carrying plan; rebuild with "
                "build_ax_plan(lp, carry_values=True)")
        if use_pallas:
            rows.append(ax_reduce_bucket_x(x, b.a_dm, b.edge_idx, b.mask))
        else:  # XLA fallback: identical math, plain take+multiply+sum
            xe = x.at[b.edge_idx].get(mode="promise_in_bounds")
            prod = (b.a_dm * xe[..., None]).astype(jnp.float32)
            rows.append(jnp.sum(jnp.where(b.mask[..., None], prod, 0.0),
                                axis=1))
    rows = jnp.concatenate(rows, axis=0)               # (R, m) f32
    ax = rows.at[plan.inv_perm].get(                   # (m, J)
        mode="promise_in_bounds").T
    return ax.astype(out_dtype or x.dtype)


def ax_dual_tile(slabs, xs, num_destinations: int) -> jax.Array:
    """(m, J) f32 Ax summed over slabs by one-hot matmuls on the MXU
    (kernel: ax_dual_tile.py).

    xs: each slab's (n, w) x*(λ).  The edge values v = a·x are formed in
    f32, the product `ax_aligned_x` forms, with padding slots set to 0
    whatever x holds there; the fusion that forms them writes the kernel's
    (m, w, n) edges-on-lanes view.
    """
    ax = None
    for slab, x in zip(slabs, xs):
        v = jnp.where(slab.mask[..., None], slab.a_vals * x[..., None], 0.0)
        part = _ax_dual_tile.ax_dual_tile(
            jnp.transpose(v, (2, 1, 0)), slab.dest_idx.T, num_destinations,
            interpret=_interpret())
        ax = part if ax is None else ax + part
    return ax
