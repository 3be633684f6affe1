"""Pallas TPU kernel: fused dual-gradient inner step (paper §6, "the hot path").

Per slab (one degree bucket):
    1. λ gather at each edge's destination        lam_e = λ[k, dest_idx]
    2. pre-projection point                       u = −(Σ_k a_k·lam_e + c)/γ
    3. box-cut projection by bisection            x = Π_C(u)
    4. per-edge gradient values                   gvals = a ⊙ x
    5. block-local scalars                        c_x, x_sq (partial sums)

The kernel fuses 2-5 into one VMEM-resident pass per (rows, w) tile: u, x
and gvals never round-trip HBM between stages, which matters because
every stage is memory-bound at production sizes.  The destination
segment-sum of gvals stays outside the kernel (XLA scatter-add / psum),
preserving the paper's "communicate only the duals" structure.

Stage 1 and the Σ_k a_k·lam_e contraction run in XLA in front of the
kernel, as the same expression `ref.dual_xstar_ref` uses (the gather
through `core.types.dest_gather`): Mosaic has no
general in-kernel gather (a lane gather over J > 128 destinations does not
lower), and staging the whole (m, J) λ block per grid step would not fit
VMEM at production J.  XLA fuses the gather with the contraction, so the
kernel reads one (rows, w) tile of Aᵀλ per step and nothing edge-space
sized is staged whole.

Layouts the TPU compiler accepts: γ is one scalar in SMEM; s enters as a
(rows, 1) column; per-family tiles (a, gvals) are family-major (m, rows,
w) so the lane axis is the slab width, never the tiny m; the per-block
c_x / x_sq partials leave as lane-dense (1, 1, 128) blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.types import dest_gather
from .proj import DEFAULT_ITERS, _block_rows, bisect_project, pad_rows

_LANES = 128


def _dual_kernel(gamma_ref, atl_ref, c_ref, mask_ref, ub_ref, s_ref, *refs,
                 iters: int, with_gvals: bool, u_dtype):
    if with_gvals:
        a_ref, x_ref, g_ref, cx_ref, xsq_ref = refs
    else:
        x_ref, cx_ref, xsq_ref = refs
    c = c_ref[...]                                   # (br, w)
    # γ rides in SMEM as f32; dividing in u's dtype keeps the operation
    # the one ref.dual_xstar_ref performs
    u = -(atl_ref[...] + c) / gamma_ref[0].astype(u_dtype)
    x = bisect_project(u, ub_ref[...], s_ref[...], mask_ref[...] != 0,
                       iters)
    x_ref[...] = x.astype(x_ref.dtype)
    if with_gvals:
        for k in range(a_ref.shape[0]):            # m is tiny: unrolled
            g_ref[k] = (a_ref[k] * x).astype(g_ref.dtype)
    # scalar partials always accumulate in f32 (bf16 slabs included)
    cx_ref[...] = jnp.full(cx_ref.shape, jnp.sum((c * x).astype(jnp.float32)))
    xsq_ref[...] = jnp.full(xsq_ref.shape,
                            jnp.sum((x * x).astype(jnp.float32)))


def _dual_call(a_vals, c_vals, dest_idx, mask, ub, s, lam, gamma, *,
               iters: int, interpret: bool, block_rows, with_gvals: bool):
    n, w, m = a_vals.shape
    gamma = jnp.asarray(gamma)
    # Aᵀλ at every edge, exactly as ref.dual_xstar_ref forms it
    atl = jnp.einsum("nwm,mnw->nw", a_vals, dest_gather(lam, dest_idx))
    u_dtype = jnp.result_type(atl, c_vals, gamma)
    # batch-aware tile pick: a serve-path microbatch (DESIGN.md §8) must
    # not be padded up to the full VMEM tile (per-row results don't depend
    # on the grid split)
    br = block_rows or _block_rows(w * (2 * m + 5 if with_gvals else 5),
                                   n=n)
    n_pad = -(-n // br) * br
    nb = n_pad // br
    tile = pl.BlockSpec((br, w), lambda i: (i, 0))
    fam = pl.BlockSpec((m, br, w), lambda i: (0, i, 0))
    part = pl.BlockSpec((1, 1, _LANES), lambda i: (i, 0, 0))
    in_specs = [pl.BlockSpec(memory_space=pltpu.SMEM),     # γ
                tile, tile, tile, tile,                     # atl, c, mask, ub
                pl.BlockSpec((br, 1), lambda i: (i, 0))]    # s
    args = [jnp.reshape(gamma, (1,)).astype(jnp.float32),
            pad_rows(atl, n_pad), pad_rows(c_vals, n_pad),
            pad_rows(mask.astype(jnp.int32), n_pad), pad_rows(ub, n_pad),
            pad_rows(s, n_pad, 1.0).reshape(n_pad, 1)]
    out_specs = [tile]
    out_shape = [jax.ShapeDtypeStruct((n_pad, w), c_vals.dtype)]
    if with_gvals:
        in_specs.append(fam)
        args.append(jnp.moveaxis(pad_rows(a_vals, n_pad), -1, 0))
        out_specs.append(fam)
        out_shape.append(jax.ShapeDtypeStruct((m, n_pad, w), a_vals.dtype))
    out_specs += [part, part]
    out_shape += [jax.ShapeDtypeStruct((nb, 1, _LANES), jnp.float32)] * 2
    outs = pl.pallas_call(
        functools.partial(_dual_kernel, iters=iters, with_gvals=with_gvals,
                          u_dtype=u_dtype),
        grid=(nb,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        interpret=interpret,
    )(*args)
    x, cx, xsq = outs[0], outs[-2], outs[-1]
    res = [x[:n]]
    if with_gvals:
        res.append(jnp.moveaxis(outs[1], 0, -1)[:n])
    return (*res, jnp.sum(cx[:, 0, 0]), jnp.sum(xsq[:, 0, 0]))


@functools.partial(jax.jit,
                   static_argnames=("iters", "interpret", "block_rows"))
def dual_x_slab(a_vals: jax.Array, c_vals: jax.Array, dest_idx: jax.Array,
                mask: jax.Array, ub: jax.Array, s: jax.Array,
                lam: jax.Array, gamma: jax.Array,
                iters: int = DEFAULT_ITERS, interpret: bool = False,
                block_rows: int | None = None):
    """Fused x*(λ) + scalars for one slab, NO per-edge gradient output.

    Returns (x (n,w), c_x scalar, x_sq scalar).  The (n, w, m) gvals HBM
    write (and its VMEM tile) of `dual_grad_slab` is gone — the x-carry
    aligned reduction never needs it.
    """
    return _dual_call(a_vals, c_vals, dest_idx, mask, ub, s, lam, gamma,
                      iters=iters, interpret=interpret,
                      block_rows=block_rows, with_gvals=False)


@functools.partial(jax.jit,
                   static_argnames=("iters", "interpret", "block_rows"))
def dual_grad_slab(a_vals: jax.Array, c_vals: jax.Array, dest_idx: jax.Array,
                   mask: jax.Array, ub: jax.Array, s: jax.Array,
                   lam: jax.Array, gamma: jax.Array,
                   iters: int = DEFAULT_ITERS, interpret: bool = False,
                   block_rows: int | None = None):
    """Fused x*(λ) + per-edge grad for one slab.

    Returns (x (n,w), gvals (n,w,m), c_x scalar, x_sq scalar).
    """
    return _dual_call(a_vals, c_vals, dest_idx, mask, ub, s, lam, gamma,
                      iters=iters, interpret=interpret,
                      block_rows=block_rows, with_gvals=True)
