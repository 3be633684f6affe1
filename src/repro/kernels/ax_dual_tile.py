"""Pallas TPU kernel: the Ax reduction as one-hot matmuls into a dual tile.

The x-carry sweep's Ax is a scatter-add of v_e = a_e·x_e into an (m, J)
table keyed by each edge's destination.  When the table is small, the MXU
does that scatter-add itself: write destination d = 128·h + l, then

    Ax[k, h, l] = Σ_e v_e[k] · [h_e = h] · [l_e = l] = (H·v)ᵀ L,

where H·v is the (edges × ⌈J/128⌉) one-hot of h scaled by v and L the
(edges × 128) one-hot of l.  The kernel streams a slab's edges once, in
source-major order, with no gather and no destination-major copy
(DESIGN.md §3).

Exactness.  The MXU multiplies bf16 operands and accumulates in f32.  The
one-hot factors are exact in bf16, and v is split exactly into three bf16
parts v = v₀ + v₁ + v₂ (three 8-bit significands cover f32's 24), so every
product is exact and only the order of the f32 sums differs from a plain
f32 scatter-add.  The parts are reduced separately and added in f32 at
the end.

Layout.  The operands come as (w, n) views of the (n, w) slab: edges on
lanes, one slot row per sublane.  The TPU lays a small-w (n, w) slab out
n-minor, so that view costs nothing.  A grid step takes T source rows; for
each slot row it builds Hv (3·m·H, T) and Lᵀ (128, T) in VMEM and
accumulates `Hv · L` into a resident f32 (3·m·H, 128) block.  Neither
one-hot matrix reaches HBM.

The cost grows with the tile: about 3·m·⌈J/128⌉ MXU rows and as many VPU
selects per edge.  `MAX_TILE_ROWS` is where it stops beating the AxPlan
gathers on a v5e (PERF.md §6).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
PARTS = 3                  # bf16 parts of an f32 value
_SUBLANES = 16             # bf16 sublane tile: H is padded to it
# largest tile 3·m·⌈J/128⌉ the dual tile takes (v5e sweep, PERF.md §6)
MAX_TILE_ROWS = 3_072
_MAX_LANES = 4_096         # source rows per grid step
# elements of a step's Hv and Lᵀ (f32, then bf16) kept in VMEM
_STEP_ELEMS = 3 * 1024 * 1024


def tile_rows(m: int, num_destinations: int) -> int:
    """3·m·⌈J/128⌉: the dual tile's rows, as the selection rule counts
    them."""
    return PARTS * m * -(-num_destinations // LANES)


def _block_lanes(rows: int, n: int) -> int:
    """Source rows per grid step: a power of two that keeps the step's
    Hv and one-hots within `_STEP_ELEMS`, no wider than the slab needs."""
    lanes = _MAX_LANES
    while lanes > LANES and (rows + 2 * LANES) * lanes > _STEP_ELEMS:
        lanes //= 2
    while lanes > LANES and lanes // 2 >= n:
        lanes //= 2
    return lanes


def _kernel(v_ref, d_ref, out_ref, *, n: int, h_rows: int):
    i = pl.program_id(0)
    m, w, lanes = v_ref.shape

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros(out_ref.shape, out_ref.dtype)

    # the last block may overhang the slab: its lanes hold garbage
    live = (i * lanes
            + jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)) < n
    h_iota = jax.lax.broadcasted_iota(jnp.int32, (h_rows, lanes), 0)
    l_iota = jax.lax.broadcasted_iota(jnp.int32, (LANES, lanes), 0)

    def slot(q, acc):
        d = d_ref[pl.ds(q, 1), :]                          # (1, T)
        h_hot = h_iota == (d // LANES)                     # (H, T)
        l_hot = (l_iota == (d % LANES)).astype(jnp.bfloat16)
        pieces = []
        for k in range(m):
            rest = jnp.where(live, v_ref[k, pl.ds(q, 1), :], 0.0)
            for _ in range(PARTS):
                part = rest.astype(jnp.bfloat16).astype(jnp.float32)
                pieces.append(jnp.where(h_hot, part, 0.0))
                rest = rest - part
        hv = jnp.concatenate(pieces, axis=0).astype(jnp.bfloat16)
        return acc + jax.lax.dot_general(
            hv, l_hot, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)

    out_ref[...] += jax.lax.fori_loop(
        0, w, slot, jnp.zeros(out_ref.shape, jnp.float32))


@functools.partial(jax.jit, static_argnames=("num_destinations",
                                             "interpret", "lanes"))
def ax_dual_tile(v: jax.Array, dest: jax.Array, num_destinations: int,
                 interpret: bool = False,
                 lanes: int | None = None) -> jax.Array:
    """(m, J) f32 Ax of one slab from its (w, n) views.

    v: (m, w, n) f32 edge values a·x, 0 on padding slots; dest: (w, n)
    int32 destination ids in [0, J).  Returns Σ_e v_e at each destination.
    `lanes` overrides the source rows per grid step (a multiple of 128).
    """
    m, w, n = v.shape
    h = -(-num_destinations // LANES)
    h_rows = -(-h // _SUBLANES) * _SUBLANES
    rows = PARTS * m * h_rows
    if n == 0 or w == 0:
        return jnp.zeros((m, num_destinations), jnp.float32)
    lanes = lanes or _block_lanes(rows, n)
    out = pl.pallas_call(
        functools.partial(_kernel, n=n, h_rows=h_rows),
        grid=(pl.cdiv(n, lanes),),
        in_specs=[pl.BlockSpec((m, w, lanes), lambda i: (0, 0, i)),
                  pl.BlockSpec((w, lanes), lambda i: (0, i))],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, LANES), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(v.astype(jnp.float32), dest.astype(jnp.int32))
    ax = out.reshape(m, PARTS, h_rows * LANES).sum(axis=1)
    return ax[:, :num_destinations]
