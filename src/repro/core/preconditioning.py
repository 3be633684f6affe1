"""Paper §5.1: Jacobi row normalization and primal (per-block) scaling.

Row normalization:  A' = D A, b' = D b with D = diag(‖A_r·‖₂⁻¹) — exact
Jacobi preconditioning of the dual Hessian −(1/γ)AAᵀ.  Zero-norm rows are
left unscaled (D_rr = 1), mirroring the paper.  Feasible set is unchanged.

Primal scaling:  z = D_v x with a *per-source-block constant* scale v_i, so
the simple-constraint polytope stays in-family (box-cut maps to box-cut with
ub' = v_i·ub, s' = v_i·s).  We use v_i = RMS of the block's column norms,
which equalizes the ridge term's effective curvature across blocks.

Both transforms operate on the slab layout and return a new LPData (plus the
inverse data needed to map duals/primals back to the original problem).
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .types import LPData, Slab, dest_gather, edge_space


class RowScaling(NamedTuple):
    d: jax.Array  # (m, J): A' = D A with D = diag(d) per (family, destination) row


def row_norms(lp: LPData) -> jax.Array:
    """‖A_r·‖₂ per dual row, from the slabs: (m, J)."""
    J = lp.num_destinations
    sq = jnp.zeros((lp.m, J), jnp.float32)
    for slab in lp.slabs:
        flat_dest = edge_space(slab.dest_idx)
        contrib = jax.vmap(
            lambda g: jax.ops.segment_sum(g, flat_dest, num_segments=J),
            in_axes=-1, out_axes=0,
        )(edge_space(slab.a_vals ** 2))
        sq = sq + contrib
    return jnp.sqrt(sq)


def row_normalize(lp: LPData) -> Tuple[LPData, RowScaling]:
    """Jacobi preconditioning: returns (scaled LP, scaling to undo duals).

    λ-space relation: the scaled problem's dual λ' relates to the original
    by λ = D λ' (since λᵀ(Ax−b) = λ'ᵀ(DAx−Db) with λ' = D⁻¹λ).
    """
    norms = row_norms(lp)
    d = jnp.where(norms > 0, 1.0 / jnp.maximum(norms, 1e-30), 1.0)
    slabs = []
    for slab in lp.slabs:
        d_e = dest_gather(d, slab.dest_idx)             # (m, n, w)
        a_new = slab.a_vals * jnp.transpose(d_e, (1, 2, 0))
        slabs.append(slab._replace(a_vals=a_new))
    return LPData(slabs=tuple(slabs), b=lp.b * d), RowScaling(d=d)


def undo_row_scaling(lam_scaled: jax.Array, scaling: RowScaling) -> jax.Array:
    """Map a dual solution of the scaled problem back: λ = D λ'."""
    return lam_scaled * scaling.d


class PrimalScaling(NamedTuple):
    v: Tuple[jax.Array, ...]  # per-slab (n,) block scale factors


def block_scales(lp: LPData) -> PrimalScaling:
    """v_i = RMS column norm within block i (column norm over families)."""
    vs = []
    for slab in lp.slabs:
        col_sq = jnp.sum(slab.a_vals ** 2, axis=-1)          # (n, w)
        cnt = jnp.maximum(jnp.sum(slab.mask, axis=-1), 1)
        rms = jnp.sqrt(jnp.sum(jnp.where(slab.mask, col_sq, 0.0), axis=-1) / cnt)
        vs.append(jnp.where(rms > 0, rms, 1.0))
    return PrimalScaling(v=tuple(vs))


def primal_scale(lp: LPData, scaling: PrimalScaling = None) -> Tuple[LPData, PrimalScaling]:
    """Apply z = D_v x blockwise:  c' = c/v, A' = A/v, ub' = v·ub, s' = v·s.

    The solved z maps back as x = z / v (per block).  Duals are unchanged.
    """
    if scaling is None:
        scaling = block_scales(lp)
    slabs = []
    for slab, v in zip(lp.slabs, scaling.v):
        inv = (1.0 / v)[:, None]
        slabs.append(slab._replace(
            a_vals=slab.a_vals * inv[..., None],
            c_vals=slab.c_vals * inv,
            ub=slab.ub * v[:, None],
            s=slab.s * v,
        ))
    return LPData(slabs=tuple(slabs), b=lp.b), scaling


def undo_primal_scaling(xs, scaling: PrimalScaling):
    """Map a per-slab primal solution z of the scaled problem back: x = z/v.

    `xs` is the list returned by `ObjectiveFunction.primal` on the scaled
    problem (one (n, w) array per slab)."""
    return [z / v[:, None] for z, v in zip(xs, scaling.v)]


def precondition(lp: LPData, row_norm: bool = True, primal: bool = False):
    """Convenience: apply the §5.1 transforms; returns (lp', undo_info)."""
    row_scaling = None
    p_scaling = None
    if primal:
        lp, p_scaling = primal_scale(lp)
    if row_norm:
        lp, row_scaling = row_normalize(lp)
    return lp, (row_scaling, p_scaling)


def gram_condition_number(lp: LPData) -> float:
    """κ(AAᵀ) via dense Gram assembly — small instances only (tests and the
    Lemma 5.1 empirical check)."""
    m, J = lp.m, lp.num_destinations
    rows = m * J
    gram = np.zeros((rows, rows))
    for slab in lp.slabs:
        a = np.asarray(slab.a_vals)          # (n, w, m)
        d = np.asarray(slab.dest_idx)        # (n, w)
        n, w, mm = a.shape
        for r in range(n):
            idx = d[r]                        # (w,)
            # rows touched by this source: (family k, dest idx[q]) -> k*J+idx
            for k1 in range(mm):
                r1 = k1 * J + idx
                for k2 in range(mm):
                    r2 = k2 * J + idx
                    np.add.at(gram, (r1, r2), a[r, :, k1] * a[r, :, k2])
    nz = np.diag(gram) > 0
    gram = gram[np.ix_(nz, nz)]
    ev = np.linalg.eigvalsh(gram)
    ev = ev[ev > max(ev.max() * 1e-12, 0)]
    return float(ev.max() / ev.min())
