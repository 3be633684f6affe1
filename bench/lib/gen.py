"""The benchmark's own Appendix-B matching-LP generator (steps 1-7).

Kept with the benchmark so that the traffic cannot move with the program.
It follows the paper's construction step by step:

  1. lognormal "breadth" per destination j, normalised to p_j;
  2. K_j ~ Poisson(p_j * I * nu), truncated at I;
  3. K_j distinct sources per destination -> edges (i, j);
  4. value c_ij = min(v_j * u_i * eps_ij, c_max), lognormal v_j, u_i and a
     counter-hashed lognormal eps_ij;
  5. constraint weight a_ij = s_j * c_ij, lognormal s_j per family;
  6. rhs b_j = rho_j (l_j + eps): l_j is the greedy load, each source
     sending its budget along its largest-a edge;
  7. the objective is a minimisation, so the program's slabs hold -value.

The instance is drawn from the configuration's fixed `instance_seed`.
Where the traffic asks for it, the run's `--seed` then relabels the
sources by a random permutation: every seed solves the same LP up to the
order of its rows, with the same slab and plan shapes.  A relabelling
reassociates the program's float sums, and that can move the iterations
to tolerance by a chunk or more, so traffic that times solves to
tolerance keeps the generated labels.  Destination
labels stay fixed, so the rhs is the same for every seed.
"""
from __future__ import annotations

import dataclasses
from concurrent.futures import ThreadPoolExecutor

import numpy as np

THREADS = 8     # numpy releases the interpreter lock in these loops


@dataclasses.dataclass(frozen=True)
class Edges:
    """One generated instance as flat edge lists, sorted by (relabelled)
    source and, within a source, by destination."""

    src: np.ndarray        # (E,) int64 source id, after relabelling
    dst: np.ndarray        # (E,) int64 destination id
    value: np.ndarray      # (E,) float64 value c_ij (maximised)
    a: np.ndarray          # (m, E) float64 constraint weights
    b: np.ndarray          # (m, J) float64 right-hand side
    num_sources: int
    num_destinations: int
    budget_s: float        # per-source budget: sum_j x_ij <= s
    box_ub: float          # per-edge bound: 0 <= x_ij <= ub

    @property
    def num_edges(self) -> int:
        return int(self.src.size)

    def degrees(self) -> np.ndarray:
        return np.bincount(self.src, minlength=self.num_sources)


def _splitmix64(x: np.ndarray) -> np.ndarray:
    with np.errstate(over="ignore"):
        x = (x + np.uint64(0x9E3779B97F4A7C15)).astype(np.uint64)
        x = ((x ^ (x >> np.uint64(30)))
             * np.uint64(0xBF58476D1CE4E5B9)).astype(np.uint64)
        x = ((x ^ (x >> np.uint64(27)))
             * np.uint64(0x94D049BB133111EB)).astype(np.uint64)
        return x ^ (x >> np.uint64(31))


def _hash_lognormal(seed: int, src: np.ndarray, dst: np.ndarray,
                    sigma: float) -> np.ndarray:
    """Per-edge lognormal(0, sigma) noise keyed by (seed, i, j)."""
    with np.errstate(over="ignore"):
        key = (src.astype(np.uint64) * np.uint64(0x100000001B3)
               + dst.astype(np.uint64)
               + np.uint64(seed) * np.uint64(0x9E3779B1))
    u1 = (_splitmix64(key).astype(np.float64) + 1.0) / 2.0**64
    u2 = (_splitmix64(key ^ np.uint64(0xDEADBEEF)).astype(np.float64)
          + 1.0) / 2.0**64
    normal = np.sqrt(-2.0 * np.log(u1)) * np.cos(2.0 * np.pi * u2)
    return np.exp(sigma * normal)


def _edges(p: dict):
    """Steps 1-3: (src, dst) in destination-major order."""
    seed, I, J = p["instance_seed"], p["num_sources"], p["num_destinations"]
    rng = np.random.default_rng(seed)
    breadth = rng.lognormal(0.0, p["breadth_sigma"], size=J)
    prob = breadth / breadth.sum()
    K = np.minimum(rng.poisson(prob * I * p["avg_nnz_per_row"]), I)
    src = np.empty(int(K.sum()), np.int64)
    dst = np.repeat(np.arange(J, dtype=np.int64), K)
    off = 0
    for j in np.nonzero(K)[0]:
        k = int(K[j])
        src[off:off + k] = np.random.default_rng((seed, 1, int(j))).choice(
            I, size=k, replace=False)
        off += k
    return src, dst


def _chunked(fn, n: int) -> np.ndarray:
    """fn(slice) over THREADS slices of range(n), concatenated."""
    bounds = np.linspace(0, n, THREADS + 1).astype(np.int64)
    with ThreadPoolExecutor(THREADS) as ex:
        return np.concatenate(list(ex.map(
            fn, [slice(a, b) for a, b in zip(bounds[:-1], bounds[1:])])),
            axis=-1)


def _stable_order(keys: np.ndarray) -> np.ndarray:
    """`np.argsort(keys, kind="stable")` for keys in [0, 2**32), as two
    16-bit radix passes."""
    first = np.argsort((keys & 0xFFFF).astype(np.uint16), kind="stable")
    return first[np.argsort((keys >> 16).astype(np.uint16)[first],
                            kind="stable")]


def _coefficients(p: dict, src: np.ndarray, dst: np.ndarray):
    """Steps 4-5: value (E,) and weights (m, E)."""
    seed, I, J = p["instance_seed"], p["num_sources"], p["num_destinations"]
    rj = np.random.default_rng((seed, 2))
    v = rj.lognormal(0.0, p["value_sigma"], size=J)
    s_scale = rj.lognormal(0.0, p["scale_sigma"],
                           size=(p["num_families"], J))
    u = np.random.default_rng((seed, 3)).lognormal(0.0, p["value_sigma"],
                                                   size=I)
    value = _chunked(lambda sl: np.minimum(
        v[dst[sl]] * u[src[sl]]
        * _hash_lognormal(seed, src[sl], dst[sl], p["noise_sigma"]),
        p["c_max"]), src.size)
    return value, _chunked(lambda sl: s_scale[:, dst[sl]] * value[sl],
                           src.size)


def _rhs(p: dict, src: np.ndarray, dst: np.ndarray,
         a: np.ndarray) -> np.ndarray:
    """Step 6 on edges sorted by source (ties: the last largest edge in
    that order, as a stable sort by (source, a) would pick)."""
    J, m = p["num_destinations"], p["num_families"]
    rho = np.random.default_rng((p["instance_seed"], 6)).uniform(
        p["rho_low"], p["rho_high"], size=(m, J))
    b = np.zeros((m, J))
    if not src.size:
        return rho * p["rhs_eps"]
    starts = np.flatnonzero(np.r_[True, src[1:] != src[:-1]])
    counts = np.diff(np.r_[starts, src.size])
    for k in range(m):
        best = np.repeat(np.maximum.reduceat(a[k], starts), counts)
        cand = np.flatnonzero(a[k] == best)
        last = cand[np.r_[src[cand][1:] != src[cand][:-1], True]]
        load = np.zeros(J)
        np.add.at(load, dst[last], a[k][last] * p["budget_s"])
        b[k] = rho[k] * (load + p["rhs_eps"])
    return b


def relabel(num_sources: int, seed: int) -> np.ndarray:
    """The run's source relabelling: new id of each generated source."""
    return np.random.default_rng(seed % 2**64).permutation(num_sources)


def generate(p: dict, seed: int, relabel_sources: bool = True) -> Edges:
    """The configuration's instance, its sources relabelled by `seed` when
    `relabel_sources`."""
    src, dst = _edges(p)
    value, a = _coefficients(p, src, dst)
    if relabel_sources:
        src = relabel(p["num_sources"], seed)[src]
    order = _stable_order(src)
    with ThreadPoolExecutor(THREADS) as ex:
        src, dst, value, a = ex.map(lambda x: x[..., order],
                                    (src, dst, value, a))
    return Edges(src=src, dst=dst, value=value, a=a, b=_rhs(p, src, dst, a),
                 num_sources=int(p["num_sources"]),
                 num_destinations=int(p["num_destinations"]),
                 budget_s=float(p["budget_s"]), box_ub=float(p["box_ub"]))
