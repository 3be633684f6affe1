"""Table 2 analogue: average time per AGD iteration, prior-CPU baseline vs
this solver, across problem sizes; plus multi-shard scaling (subprocess with
8 virtual host devices — wall-clock on 1 physical core measures partitioning
overhead honestly; real scaling is the dry-run's collective analysis).
The shard-scaling children run on the CPU by design (JAX_PLATFORMS=cpu is
set for them, and their rows say so): the parent holds JAX, so a child
could never reach an accelerator the parent already holds.

Paper claim reproduced: >= 10x per-iteration speedup over the prior CPU
solver under matched stopping criterion (same AGD math, same instance).
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

import numpy as np

from .lp_common import (bench_instance, paper_config, time_jax_iteration,
                        time_numpy_iteration)

SIZES = [20_000, 50_000, 100_000]


def run(quick: bool = False):
    rows = []
    sizes = SIZES[:2] if quick else SIZES
    for I in sizes:
        spec, lp_host = bench_instance(I)
        cfg = paper_config(iterations=20 if quick else 50)
        t_np, _ = time_numpy_iteration(lp_host, cfg,
                                       max_iters=3 if quick else 5)
        t_jx, _ = time_jax_iteration(lp_host, cfg)
        rows.append({
            "name": f"table2/iter_time/I={I}",
            "us_per_call": t_jx * 1e6,
            "derived": {
                "numpy_baseline_us": t_np * 1e6,
                "speedup_vs_prior_cpu": t_np / t_jx,
            },
        })
    # paper claim: >=10x under matched criterion
    worst = min(r["derived"]["speedup_vs_prior_cpu"] for r in rows)
    rows.append({"name": "table2/speedup_claim_10x",
                 "us_per_call": 0.0,
                 "derived": {"worst_speedup": worst, "pass": worst >= 10.0}})
    return rows


_SHARD_PROG = textwrap.dedent("""
    import os, sys, time, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, jax.numpy as jnp
    from repro.core import InstanceSpec, SolveConfig, generate
    from repro.core.distributed import solve_distributed
    from repro.launch.mesh import make_mesh
    I = int(sys.argv[1]); shards = int(sys.argv[2])
    spec = InstanceSpec(num_sources=I, num_destinations=1000,
                        avg_nnz_per_row=max(0.001 * I, 4.0), seed=42)
    lp = jax.tree.map(jnp.asarray, generate(spec))
    cfg = SolveConfig(iterations=30, gamma=0.01, max_step=1e-3,
                      initial_step=1e-5)
    mesh = make_mesh((shards, 1), ("data", "model"))
    res = solve_distributed(lp, cfg, mesh)              # compile+run
    jax.block_until_ready(res.lam)
    t0 = time.perf_counter()
    res = solve_distributed(lp, cfg, mesh)
    jax.block_until_ready(res.lam)
    dt = (time.perf_counter() - t0) / cfg.iterations
    print(json.dumps({"per_iter_s": dt,
                      "final_dual": float(res.stats.dual_obj[-1])}))
""")


def run_shard_scaling(quick: bool = False):
    rows = []
    I = 50_000
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    finals = {}
    for shards in ([1, 4] if quick else [1, 2, 4, 8]):
        out = subprocess.run(
            [sys.executable, "-c", _SHARD_PROG, str(I), str(shards)],
            capture_output=True, text=True, env=env, cwd=root, timeout=600)
        data = json.loads(out.stdout.strip().splitlines()[-1])
        finals[shards] = data["final_dual"]
        rows.append({
            "name": f"table2/shard_scaling/I={I}/shards={shards}",
            "us_per_call": data["per_iter_s"] * 1e6,
            "derived": {"final_dual": data["final_dual"],
                        "platform": "cpu"},
        })
    # all shard counts converge to the same optimum (Fig.1-style invariance)
    vals = np.array(list(finals.values()))
    rows.append({
        "name": "table2/shard_invariance",
        "us_per_call": 0.0,
        "derived": {"max_rel_spread": float(np.ptp(vals) / np.abs(vals).max()),
                    "pass": bool(np.ptp(vals) / np.abs(vals).max() < 1e-2)},
    })
    return rows
