"""Pallas TPU kernels: constraint-aligned gather-reduce for Ax (paper §6).

The companion layout (`core.types.AxPlan`) turns the dual-gradient's
`Ax` reduction from a destination-keyed scatter-add into a dense masked
row-sum.  Two variants:

`ax_reduce_bucket` (gvals-consuming, legacy): each dual row gathers its
incident per-edge gradient values from a materialized (E, m) tensor,

    ax[row, k] = Σ_q mask[row, q] · gvals[edge_idx[row, q], k].

`ax_reduce_bucket_x` (value-carrying, DESIGN.md §3): the plan packs a
static destination-major weight copy `a_dm`, so the reduction consumes
the (E,) x vector alone,

    ax[row, k] = Σ_q mask[row, q] · a_dm[row, q, k] · x[edge_idx[row, q]],

and the (E, m) per-edge gradient tensor never exists — the only dynamic
per-edge array crossing HBM is x.

That is exactly the gather-based formulation cuPDLP-class GPU solvers use
to retire atomics from the transpose product — every lane does independent
loads, the sum is a fixed-shape VPU reduction, and there is no write
contention at all.

The gather at `edge_idx` runs in XLA in front of the kernel: Mosaic
lowers no gather over an edge-space-sized operand ("Only 2D gather is
supported"), and staging the whole (E,) x or (E, m) gvals into VMEM
stops fitting long before production edge counts.  The kernel then reads
bucket-shaped tiles only and does the FMA and the masked reduce.

Tiling: a 2-D grid over (row-blocks, width-chunks) of one in-degree
bucket.  Buckets of hub destinations are 2^17+ wide, so a row's edges
are summed chunk by chunk into a resident output block (the width axis is
the reduction axis, last in the grid).  Per-family tiles are
family-major (m, rows, chunk) so the lane axis is the bucket width, and
the output is a family-major (m, rows, 1) column block.

Accumulation is always f32 (bf16 inputs included), matching dual_grad.py's
scalar partials; products are formed in the input dtype — bit-matching the
gvals = a ⊙ x the legacy path materializes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .proj import _block_rows

# widest width chunk one grid step reduces (lanes); wider buckets loop
_MAX_CHUNK = 2048


def _ax_kernel(*refs, carry_x: bool):
    if carry_x:
        xe_ref, a_ref, mask_ref, out_ref = refs
        xe = xe_ref[...]                             # (br, bw)
    else:
        a_ref, mask_ref, out_ref = refs
    mask = mask_ref[...] != 0                        # (br, bw)

    @pl.when(pl.program_id(1) == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    # m is tiny (1-4 constraint families): unrolled, one row-sum per family
    for k in range(a_ref.shape[0]):
        prod = a_ref[k] * xe if carry_x else a_ref[k]
        out_ref[k] += jnp.sum(jnp.where(mask, prod.astype(jnp.float32), 0.0),
                              axis=-1, keepdims=True)


def _ax_call(fam_vals, xe, mask, interpret: bool, block_rows):
    """Masked (m, r, w) row-sum [times xe], as (r, m) float32 rows."""
    m, r, w = fam_vals.shape
    bw = min(w, _MAX_CHUNK)
    w_pad = -(-w // bw) * bw
    n_in = m + 2 if xe is not None else m + 1
    br = block_rows or min(_block_rows(n_in * bw), max(r, 8))
    r_pad = -(-r // br) * br

    def pad(a):             # zero rows/width overhang: mask 0 drops it
        cfg = [(0, 0)] * a.ndim
        cfg[-2], cfg[-1] = (0, r_pad - r), (0, w_pad - w)
        return jnp.pad(a, cfg)

    tile = pl.BlockSpec((br, bw), lambda i, j: (i, j))
    specs = [pl.BlockSpec((m, br, bw), lambda i, j: (0, i, j)), tile]
    args = [pad(fam_vals), pad(mask.astype(jnp.int32))]
    if xe is not None:
        specs.insert(0, tile)
        args.insert(0, pad(xe))
    out = pl.pallas_call(
        functools.partial(_ax_kernel, carry_x=xe is not None),
        grid=(r_pad // br, w_pad // bw),
        in_specs=specs,
        out_specs=pl.BlockSpec((m, br, 1), lambda i, j: (0, i, 0)),
        out_shape=jax.ShapeDtypeStruct((m, r_pad, 1), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(*args)
    return out[:, :r, 0].T


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def ax_reduce_bucket(gvals: jax.Array, edge_idx: jax.Array, mask: jax.Array,
                     interpret: bool = False,
                     block_rows: int | None = None) -> jax.Array:
    """Masked gather row-sum of one AxBucket.

    gvals: (E, m) flattened per-edge gradient values; edge_idx/mask: (r, w).
    Returns (r, m) float32 partial Ax rows (bucket row order).
    """
    r, w = edge_idx.shape
    E, m = gvals.shape
    if E == 0 or r == 0:
        return jnp.zeros((r, m), jnp.float32)
    # plan indices are valid by construction: no gather bounds checks
    g = gvals.T.at[:, edge_idx].get(mode="promise_in_bounds")   # (m, r, w)
    return _ax_call(g, None, mask, interpret, block_rows)


@functools.partial(jax.jit, static_argnames=("interpret", "block_rows"))
def ax_reduce_bucket_x(x: jax.Array, a_dm: jax.Array, edge_idx: jax.Array,
                       mask: jax.Array, interpret: bool = False,
                       block_rows: int | None = None) -> jax.Array:
    """Value-carrying masked gather row-sum of one AxBucket (module doc).

    x: (E,) flattened x*(λ); a_dm: (r, w, m) static destination-major
    weights; edge_idx/mask: (r, w).  Returns (r, m) float32 partial Ax
    rows (bucket row order).  Only x is dynamic — the gathered operand is
    m·times smaller than the gvals the legacy kernel reads.
    """
    r, w = edge_idx.shape
    (E,) = x.shape
    m = a_dm.shape[-1]
    if E == 0 or r == 0:
        return jnp.zeros((r, m), jnp.float32)
    xe = x.at[edge_idx].get(mode="promise_in_bounds")           # (r, w)
    return _ax_call(jnp.moveaxis(a_dm, -1, 0), xe, mask, interpret,
                    block_rows)
