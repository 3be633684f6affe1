"""Time the x-carry sweep's two Ax paths against each other over J.

For each J it builds one synthetic (n, 16) source slab: Poisson(10)
edges per source (1 to 16), destinations drawn with lognormal(σ = 1) breadth
(skewed in-degree, as `core.instance.generate`), and its AxPlan.  It then
times `ops.ax_dual_tile` and `ops.ax_aligned_x` on the same random x and
reports each path's milliseconds and its largest error against a float64
host sum, relative to Σ|a·x| at the destination.  `--lanes` times the dual
tile's kernel alone at the 5M-source slab shapes, J = 10,000, for each
grid-step width.  `objectives.select_ax_path` takes the dual tile up to
`ax_dual_tile.MAX_TILE_ROWS`, set from this sweep (PERF.md §6).

Run on a TPU, in one process; each row is printed as a JSON line, and
the whole result is written to `--out` when given:

    python benchmarks/ax_path_sweep.py --sources 1000000 \\
        --destinations 10000 40000 100000 200000 400000 --lanes 2048 4096
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "src"))

# (rows, width) of the 5M-source instance's slabs at J = 10,000
SLABS_5M = [(145_500, 4), (1_517_000, 8), (3_202_000, 16), (135_000, 32),
            (8, 64)]


def _timed(fn, *args, reps: int):
    import jax
    out = jax.block_until_ready(fn(*args))          # compile + warm-up
    t = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t) / reps * 1e3


def synthetic_slab(n: int, J: int, seed: int):
    """One (n, 16) slab with skewed destinations, as numpy arrays."""
    from repro.core.types import Slab
    rng = np.random.default_rng(seed)
    w = 16
    deg = np.clip(rng.poisson(10, n), 1, w)
    mask = np.arange(w)[None, :] < deg[:, None]
    p = rng.lognormal(0.0, 1.0, J)
    dest = rng.choice(J, size=(n, w), p=p / p.sum()).astype(np.int32)
    dest = np.where(mask, dest, 0)
    a = np.where(mask, rng.uniform(0.5, 1.5, (n, w)), 0.0)
    zeros = np.zeros((n, w), np.float32)
    return Slab(a_vals=a[..., None].astype(np.float32), c_vals=zeros,
                dest_idx=dest, mask=mask, ub=zeros,
                s=np.ones(n, np.float32),
                source_ids=np.arange(n, dtype=np.int32))


def sweep_j(n: int, J: int, reps: int, seed: int) -> dict:
    import jax
    from repro.core.instance import build_ax_plan
    from repro.core.types import LPData, edge_space
    from repro.kernels import ops
    from repro.kernels.ax_dual_tile import tile_rows

    slab_np = synthetic_slab(n, J, seed)
    t = time.perf_counter()
    plan = build_ax_plan(LPData(slabs=(slab_np,),
                                b=np.zeros((1, J), np.float32)))
    plan_s = time.perf_counter() - t
    slab, plan = jax.device_put((slab_np, plan))
    x = jax.random.uniform(jax.random.PRNGKey(seed), slab.mask.shape)

    dual = jax.jit(lambda s, x: ops.ax_dual_tile([s], [x], J))
    gather = jax.jit(lambda p, x: ops.ax_aligned_x(p, edge_space(x)))
    row = {"J": J, "tile_rows": tile_rows(1, J), "slots": int(n * 16),
           "edges": int(slab_np.mask.sum()), "plan_build_s": plan_s}
    v = np.where(slab_np.mask, slab_np.a_vals[..., 0].astype(np.float64)
                 * np.asarray(x, np.float64), 0.0).ravel()
    d = slab_np.dest_idx.ravel()
    exact = np.bincount(d, v, minlength=J)
    scale = np.maximum(np.bincount(d, np.abs(v), minlength=J), 1e-30)
    for name, fn, arg in (("dual_tile", dual, slab), ("ax_plan", gather,
                                                      plan)):
        try:
            ax, ms = _timed(fn, arg, x, reps=reps)
        except Exception as e:  # a tile too large to compile is an answer
            row[name] = {"error": f"{type(e).__name__}: {e}"[:300]}
            continue
        err = np.abs(np.asarray(ax, np.float64)[0] - exact) / scale
        row[name] = {"ms": ms, "max_rel_err": float(err.max())}
    return row


def lanes_5m(lanes: list, reps: int, seed: int) -> list:
    import jax
    import jax.numpy as jnp
    from repro.kernels import ax_dual_tile as k
    J = 10_000
    if not lanes:
        return []
    key = jax.random.PRNGKey(seed)
    views = []
    for i, (n, w) in enumerate(SLABS_5M):
        kv, kd = jax.random.split(jax.random.fold_in(key, i))
        views.append((jax.random.uniform(kv, (1, w, n)),
                      jax.random.randint(kd, (w, n), 0, J, jnp.int32)))
    out = []
    for L in lanes:
        fn = jax.jit(lambda vs, L=L: sum(
            k.ax_dual_tile(v, d, J, lanes=L) for v, d in vs))
        try:
            _, ms = _timed(fn, views, reps=reps)
            out.append({"lanes": L, "ms": ms})
        except Exception as e:
            out.append({"lanes": L, "error": f"{type(e).__name__}: {e}"[:300]})
    return out


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--sources", type=int, default=1_000_000)
    ap.add_argument("--destinations", type=int, nargs="*",
                    default=[10_000, 40_000, 100_000, 200_000])
    ap.add_argument("--lanes", type=int, nargs="*", default=[])
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", help="write the result here as JSON")
    args = ap.parse_args(argv)
    import jax
    dev = jax.devices()[0]
    res = {"device": {"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "count": jax.device_count()},
           "sweep": [], "lanes_5m": lanes_5m(args.lanes, args.reps,
                                             args.seed)}
    print(json.dumps(res["lanes_5m"]), flush=True)
    for J in args.destinations:
        row = sweep_j(args.sources, J, args.reps, args.seed)
        print(json.dumps(row), flush=True)
        res["sweep"].append(row)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    return res


if __name__ == "__main__":
    main()
