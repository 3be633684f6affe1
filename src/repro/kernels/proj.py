"""Pallas TPU kernel: batched box-cut projection via τ-bisection (paper §6).

GPU→TPU adaptation (DESIGN.md §2): the paper batches per-bucket projections
into dense padded slabs to amortize kernel launches.  On TPU we keep the
bucketed slabs but replace the sort-based threshold search with *bisection*:
branch-free, VPU-vectorized over (rows × width) tiles, no data-dependent
control flow, fixed iteration count — exactly what Mosaic compiles well.

Tiling: grid over row-blocks; each kernel instance owns a
(BLOCK_ROWS, width) tile of v/ub/mask and a (BLOCK_ROWS, 1) column of s,
all VMEM-resident.  Every per-row quantity (s, τ, the bracket) stays a
(rows, 1) column: Mosaic lays a rank-1 block out differently from XLA, so
the kernels never hand it one.  The inner fori_loop does `iters` rounds of
f(τ) = Σ clip(v−τ, 0, ub) per row (one VPU reduction per round).
Width is the slab's power-of-two bucket width — already lane-aligned for
buckets >= 128; small buckets underfill lanes but are cheap in absolute terms.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

DEFAULT_ITERS = 40
# target <= ~2 MB per input tile in VMEM (3 f32 tiles + outputs live at once)
_VMEM_TILE_BYTES = 2 * 1024 * 1024


def _block_rows(width: int, dtype_bytes: int = 4,
                n: int | None = None) -> int:
    """Rows per kernel instance: VMEM-capped, and — when the batch row
    count `n` is known — never larger than the batch needs.

    The cap matters on the serving path (DESIGN.md §8): a microbatch query
    projects a handful of gathered rows, and without the `n` cap it would
    be padded up to the full VMEM tile (512 rows at small widths — 10-100×
    wasted work per query).  Batch-aware picks change only the grid/padding
    split, never the per-row results (each row's bisection is independent).
    """
    rows = _VMEM_TILE_BYTES // max(width * dtype_bytes, 1)
    rows = max(8, min(512, rows))
    if n is not None:
        # smallest power of two covering the batch, floored at 8 rows
        rows = min(rows, max(8, 1 << (max(n - 1, 1)).bit_length()))
    # power of two for clean grid math
    return 1 << (rows.bit_length() - 1)


def pad_rows(a: jax.Array, n_pad: int, fill=0) -> jax.Array:
    """Pad the leading (row) axis of `a` to `n_pad` rows with `fill`."""
    extra = n_pad - a.shape[0]
    if extra == 0:
        return a
    return jnp.pad(a, [(0, extra)] + [(0, 0)] * (a.ndim - 1),
                   constant_values=fill)


def bisect_project(v, ub, s, mask, iters: int):
    """Row-wise box-cut projection of a (rows, w) tile; `s` is (rows, 1).

    The kernel-side twin of `ref.boxcut_bisect_ref` (same operations in
    the same order, per-row values kept as (rows, 1) columns), shared by
    every kernel of this package that projects.
    """
    neg = jnp.asarray(-1e30, v.dtype)
    v = jnp.where(mask, v, neg)
    f0 = jnp.sum(jnp.where(mask, jnp.clip(v, 0.0, ub), 0.0), axis=-1,
                 keepdims=True)
    need = f0 > s
    hi = jnp.max(v, axis=-1, keepdims=True)
    lo = jnp.minimum(jnp.zeros_like(hi), hi)

    def body(_, carry):
        lo, hi = carry
        mid = 0.5 * (lo + hi)
        xm = jnp.clip(v - mid, 0.0, ub)
        f = jnp.sum(jnp.where(mask, xm, 0.0), axis=-1, keepdims=True)
        big = f > s
        return jnp.where(big, mid, lo), jnp.where(big, hi, mid)

    lo, hi = jax.lax.fori_loop(0, iters, body, (lo, hi))
    tau = jnp.where(need, 0.5 * (lo + hi), 0.0)
    x = jnp.clip(v - tau, 0.0, ub)
    return jnp.where(mask, x, 0.0)


def _proj_kernel(v_ref, ub_ref, s_ref, mask_ref, x_ref, *, iters: int):
    x = bisect_project(v_ref[...], ub_ref[...], s_ref[...],
                       mask_ref[...] != 0, iters)
    x_ref[...] = x.astype(x_ref.dtype)


@functools.partial(jax.jit, static_argnames=("iters", "interpret", "block_rows"))
def proj_boxcut(v: jax.Array, ub: jax.Array, s: jax.Array, mask: jax.Array,
                iters: int = DEFAULT_ITERS, interpret: bool = False,
                block_rows: int | None = None) -> jax.Array:
    """Batched box-cut projection of an (n, w) slab. Returns x of shape (n, w).

    `interpret=True` executes the kernel body in Python on CPU (used for all
    validation in this container); on TPU the same code lowers via Mosaic.
    """
    n, w = v.shape
    br = block_rows or _block_rows(w, n=n)
    n_pad = -(-n // br) * br
    v, ub = pad_rows(v, n_pad), pad_rows(ub, n_pad)
    s = pad_rows(s, n_pad, 1.0).reshape(n_pad, 1)
    mask = pad_rows(mask.astype(jnp.int32), n_pad)
    tile = pl.BlockSpec((br, w), lambda i: (i, 0))
    out = pl.pallas_call(
        functools.partial(_proj_kernel, iters=iters),
        grid=(n_pad // br,),
        in_specs=[tile, tile, pl.BlockSpec((br, 1), lambda i: (i, 0)), tile],
        out_specs=tile,
        out_shape=jax.ShapeDtypeStruct((n_pad, w), v.dtype),
        interpret=interpret,
    )(v, ub, s, mask)
    return out[:n]
