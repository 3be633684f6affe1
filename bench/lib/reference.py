"""The plain reference the benchmark judges `correct` by.

It evaluates the ridge-regularised dual of the matching LP at a given dual
point, from the benchmark's own edge lists (`gen.Edges`), and imports
nothing of the program:

    precondition   a' = a / ||A_j.||, b' = b / ||A_j.|| per dual row (the
                   paper's Jacobi row normalisation, in float64 on the host)
    u              -(sum_k a'_k lam_k[dst] + c) / gamma, per edge
    x              the exact projection of each source's u onto
                   {0 <= x <= ub, sum x <= s}, found from the breakpoints
                   of the piecewise-linear sum (no bisection)
    Ax, c.x, |x|^2 reduced on the host in float64
    g              c.x + gamma/2 |x|^2 + lam.(Ax - b')

The per-edge part (gather, u, projection) runs in JAX on the device, in
blocks of source rows, in the `dtype` it is given: float32 for the
reference, bfloat16 for the control that must fail.  `a` and `c` are
rounded to that dtype before use, in the per-edge part and the reductions
alike.
"""
from __future__ import annotations

import dataclasses
import functools

import numpy as np

BLOCK_ROWS = 65536
_BIG = 1e30


@dataclasses.dataclass(frozen=True)
class Preconditioned:
    """Row-normalised constraint weights and rhs, float64."""

    a: np.ndarray          # (m, E)
    b: np.ndarray          # (m, J)
    c: np.ndarray          # (E,) minimisation cost, -value


def precondition(e) -> Preconditioned:
    m, J = e.a.shape[0], e.num_destinations
    norms = np.stack([np.sqrt(np.bincount(e.dst, weights=e.a[k] ** 2,
                                          minlength=J)) for k in range(m)])
    d = np.where(norms > 0, 1.0 / np.maximum(norms, 1e-300), 1.0)
    return Preconditioned(a=e.a * d[:, e.dst], b=e.b * d, c=-e.value)


@dataclasses.dataclass
class Evaluation:
    """The reference's dual evaluation at one point, float64."""

    g: float
    ax: np.ndarray         # (m, J)
    b: np.ndarray          # (m, J) preconditioned rhs
    grad: np.ndarray       # (m, J) Ax - b
    scale: np.ndarray      # (m, J) sum_i a_ij ub + b_j, the most Ax_j can be
    cx: float
    x_sq: float
    infeas: float


def _project(v, ub, s, mask):
    """Exact projection of each row of v onto {0 <= x <= ub, sum x <= s}.

    f(t) = sum_j clip(v_j - t, 0, ub_j) is piecewise linear and
    non-increasing, with breakpoints at v_j - ub_j and v_j.  Where f(0) > s
    the threshold t > 0 solves f(t) = s; it lies between the last
    breakpoint with f > s and the next, where f is linear.
    """
    import jax.numpy as jnp

    big = jnp.asarray(_BIG, v.dtype)
    v = jnp.where(mask, v, -big)
    ub = jnp.where(mask, ub, 0)
    zero = jnp.zeros((), v.dtype)

    def f(t):                                  # t: (..., T) -> (..., T)
        return jnp.sum(jnp.clip(v[..., None, :] - t[..., :, None], zero,
                                ub[..., None, :]), axis=-1)

    bp = jnp.sort(jnp.concatenate([v - ub, v], axis=-1), axis=-1)
    fb = f(bp)
    s_ = s[..., None]
    k = jnp.sum(fb > s_, axis=-1, keepdims=True)
    lo = jnp.maximum(k - 1, 0)
    hi = jnp.minimum(k, bp.shape[-1] - 1)
    t0 = jnp.take_along_axis(bp, lo, axis=-1)
    t1 = jnp.take_along_axis(bp, hi, axis=-1)
    f0 = jnp.take_along_axis(fb, lo, axis=-1)
    f1 = jnp.take_along_axis(fb, hi, axis=-1)
    den = jnp.where(f0 > f1, f0 - f1, jnp.ones((), v.dtype))
    tau = t0 + (f0 - s_) * (t1 - t0) / den
    need = f(jnp.zeros(v.shape[:-1] + (1,), v.dtype)) > s_
    tau = jnp.where(need, jnp.maximum(tau, zero), zero)
    return jnp.where(mask, jnp.clip(v - tau, zero, ub), zero)


def _block(a, c, dst, mask, lam, gamma, ub, s):
    """x for one block of source rows: (m, B, W) a, (B, W) the rest."""
    import jax.numpy as jnp

    lam_e = lam[:, dst]                                      # (m, B, W)
    u = -(jnp.sum(a * lam_e, axis=0) + c) / gamma
    return _project(u, ub, s, mask)


@functools.cache
def _block_fn():
    import jax
    return jax.jit(_block)


def _to(dtype, x):
    """Round a float64 array to `dtype` and back (numpy side)."""
    return np.asarray(x, dtype).astype(np.float64)


def evaluate(e, pre: Preconditioned, lam, gamma: float,
             dtype) -> Evaluation:
    """The reference's g, Ax and x at the dual point `lam` (m, J)."""
    import jax
    import jax.numpy as jnp

    m, J, I = pre.a.shape[0], e.num_destinations, e.num_sources
    deg = np.bincount(e.src, minlength=I)
    starts = np.concatenate([[0], np.cumsum(deg)[:-1]])
    W = max(8, -(-int(deg.max(initial=1)) // 8) * 8)
    a_d = _to(dtype, pre.a)
    c_d = _to(dtype, pre.c)
    block = _block_fn()
    lam_dev = jnp.asarray(np.asarray(lam, np.float64), dtype)
    gamma_dev = jnp.asarray(gamma, dtype)
    x_edges = np.zeros(e.num_edges)
    for r0 in range(0, I, BLOCK_ROWS):
        r = np.arange(r0, r0 + BLOCK_ROWS)
        live = r < I
        rr = np.where(live, r, 0)
        msk = (np.arange(W)[None, :] < deg[rr][:, None]) & live[:, None]
        idx = np.where(msk, starts[rr][:, None] + np.arange(W)[None, :], 0)
        xb = block(jnp.asarray(np.where(msk[None], a_d[:, idx], 0), dtype),
                   jnp.asarray(np.where(msk, c_d[idx], 0), dtype),
                   jnp.asarray(np.where(msk, e.dst[idx], 0), jnp.int32),
                   jnp.asarray(msk),
                   lam_dev, gamma_dev,
                   jnp.asarray(np.full(msk.shape, e.box_ub), dtype),
                   jnp.asarray(np.full(BLOCK_ROWS, e.budget_s), dtype))
        xb = np.asarray(jax.device_get(xb), np.float64)
        x_edges[idx[msk]] = xb[msk]
    ax = np.stack([np.bincount(e.dst, weights=a_d[k] * x_edges, minlength=J)
                   for k in range(m)])
    lam64 = np.asarray(lam, np.float64)
    grad = ax - pre.b
    cx = float(np.dot(c_d, x_edges))
    x_sq = float(np.dot(x_edges, x_edges))
    return Evaluation(
        g=cx + 0.5 * gamma * x_sq + float(np.vdot(lam64, grad)), ax=ax,
        b=pre.b, grad=grad, cx=cx, x_sq=x_sq,
        scale=np.stack([np.bincount(e.dst, weights=np.abs(a_d[k]) * e.box_ub,
                                    minlength=J) for k in range(m)]) + pre.b,
        infeas=float(np.linalg.norm(np.maximum(grad, 0.0))))
