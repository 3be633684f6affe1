"""Benchmark harness — one entry per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (derived is compact JSON) and
merges them into benchmarks/results/bench_results.json keyed by row name
(so ``--only``/``--quick`` runs update their own rows without wiping the
rest of the artifact; ``--fresh`` replaces the file wholesale).

  table2   per-iteration time vs prior-CPU baseline + shard scaling (Table 2)
  fig12    implementation parity (<1% in 100 iters)        (Figures 1-2)
  fig4     Jacobi preconditioning ablation                 (Figure 4)
  fig5     γ-continuation ablation                         (Figure 5)
  lemma51  row-normalization conditioning bound            (Lemma 5.1)
  lemmaA1  primal-infeasibility bound                      (Lemma A.1)
  kernels  Pallas dual-grad + ax-reduce kernels vs pure-jnp hot path
  roofline aggregated dry-run roofline terms               (§Roofline)
  perf_lp  solver §Perf hillclimb it0..it7 (it4/it5: constraint-aligned
           scatter-free Ax over materialized gvals; it6/it7: value-carrying
           x-only reduction — all guarded by dual_drift_rel in each row)
  perf_lp_tol  wall-clock-to-tolerance under matched stopping criteria —
           the paper's actual speedup metric (scatter vs aligned vs x-carry
           rows share one StoppingCriteria; each reports
           seconds/iterations/stop_reason; tol_xcarry's drift vs
           tol_aligned is the CI gate), plus the update-rule race
           (tol_agd/tol_pdhg/tol_bb × every registered formulation under
           one shared criteria; tol_rules_summary carries the pdhg >= 2x
           iteration-speedup count the CI smoke gates on)
  perf_lp_bytes  analytic HBM bytes/iteration of the three Ax lowerings
           from compiled HLO (launch/hlo_cost.py): the no-gvals and
           ≥2x dynamic edge-traffic acceptance checks
  perf_lp_serve  primal serving (DESIGN.md §8): streaming-extraction
           throughput (sources/sec) + λ-resident microbatch query
           latency, gated on a valid duality-gap certificate
  perf_lp_load  served traffic (DESIGN.md §12): closed-loop load test
           through the ServerFrontend — 4 concurrent clients vs a
           single-client baseline (coalescing must scale qps >= 2x),
           p50/p99 of admitted queries vs the deadline, shed/timeout
           rates, a warm_resolve landing mid-run; raises instead of
           recording a row if any request goes unclassified

Every invocation also appends one compact summary line per executed suite
to benchmarks/results/bench_history.jsonl (timestamp, suite, quick flag,
row names + us_per_call + resource watermarks) — an append-only trend log
that survives the keyed merges of bench_results.json, so perf drift is
diffable across invocations.  ``--no-history`` opts out; ``--list``
enumerates the registered suites and the rows each one emits without
running anything.

Usage: PYTHONPATH=src python -m benchmarks.run [--quick] [--only NAME]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _kernel_bench(quick: bool = False):
    """Hot-path timing: fused-pallas(interpret) correctness + jnp timing.

    On CPU, interpret-mode pallas is not representative of TPU wall time, so
    the timed row is the jnp hot path (the deployed CPU path); the kernel row
    reports correctness delta vs the oracle instead of time.
    """
    import jax, jax.numpy as jnp, numpy as np
    from repro.core import (InstanceSpec, build_ax_plan, generate,
                            dual_value_and_grad)
    from repro.kernels import ops, ref as kref
    spec = InstanceSpec(num_sources=20_000, num_destinations=1000,
                        avg_nnz_per_row=20, seed=42)
    lp = jax.tree.map(jnp.asarray, generate(spec))
    lam = jnp.zeros((1, 1000))
    gamma = jnp.float32(0.01)
    f = jax.jit(lambda l: dual_value_and_grad(lp, l, gamma, "boxcut"))
    compiled = f.lower(lam).compile()
    g, grad, aux = f(lam)
    jax.block_until_ready(grad)
    t0 = time.perf_counter()
    n = 3 if quick else 10
    for _ in range(n):
        g, grad, aux = f(lam)
    jax.block_until_ready(grad)
    dt = (time.perf_counter() - t0) / n
    # achieved-vs-peak bytes bound: hlo_cost census of the compiled module
    # against the roofline_report peak table (published peaks by
    # device_kind; a device missing there — the CPU included — leaves the
    # row without a bound and says why)
    from repro.launch import hlo_cost
    from . import roofline_report
    try:
        txt = compiled.as_text()
        census = hlo_cost.analyze(txt)
        bound = roofline_report.bytes_bound(census["bytes_per_device"], dt)
        bound["dyn_bytes_per_call"] = hlo_cost.analyze(
            txt, dynamic_only=True)["bytes_per_device"]
    except Exception as e:
        bound = {"error": f"bytes bound unavailable: {e}"}
    # kernel vs oracle on the largest slab
    slab = max(lp.slabs, key=lambda s: s.n * s.width)
    x_k, g_k, cx_k, xsq_k = ops.dual_grad_slab(slab, lam, gamma)
    x_r, g_r, cx_r, xsq_r = kref.dual_xstar_ref(
        slab.a_vals, slab.c_vals, slab.dest_idx, slab.mask, slab.ub, slab.s,
        lam, gamma)
    # aligned gather-reduce kernel vs oracle over the whole plan
    plan = jax.tree.map(jnp.asarray, build_ax_plan(lp))
    E = sum(s.n * s.width for s in lp.slabs)
    gv = jnp.asarray(np.random.default_rng(0)
                     .normal(size=(E, lp.m)).astype(np.float32))
    ax_k = ops.ax_aligned(plan, gv, use_pallas=True)
    ax_r = kref.ax_plan_ref(plan, gv)
    # value-carrying x-only gather-reduce kernel vs oracle
    xv = jnp.asarray(np.random.default_rng(1)
                     .normal(size=(E,)).astype(np.float32))
    axx_k = ops.ax_aligned_x(plan, xv, use_pallas=True)
    axx_r = kref.ax_plan_x_ref(plan, xv)
    return [
        {"name": "kernels/dual_grad_jnp_hotpath", "us_per_call": dt * 1e6,
         "derived": {"edges": int(sum(int(np.asarray(s.mask).sum())
                                      for s in lp.slabs)),
                     **bound}},
        {"name": "kernels/dual_grad_pallas_vs_oracle", "us_per_call": 0.0,
         "derived": {"max_abs_err_x": float(jnp.abs(x_k - x_r).max()),
                     "max_abs_err_gvals": float(jnp.abs(g_k - g_r).max())}},
        {"name": "kernels/ax_reduce_pallas_vs_oracle", "us_per_call": 0.0,
         "derived": {"max_abs_err_ax":
                     float(jnp.abs(ax_k - ax_r.astype(ax_k.dtype)).max()),
                     "plan_rows": int(sum(b.rows for b in plan.buckets))}},
        {"name": "kernels/ax_reduce_x_pallas_vs_oracle", "us_per_call": 0.0,
         "derived": {"max_abs_err_ax":
                     float(jnp.abs(axx_k - axx_r.astype(axx_k.dtype)).max()),
                     "plan_rows": int(sum(b.rows for b in plan.buckets))}},
    ]


SUITES = {}


def _register():
    from . import (table2_scaling, fig12_parity, fig45_ablations,
                   lemma_checks, roofline_report, perf_lp)
    SUITES.update({
        "table2": lambda q: table2_scaling.run(q),
        "table2_shards": lambda q: table2_scaling.run_shard_scaling(q),
        "fig12": lambda q: fig12_parity.run(q),
        "fig4": lambda q: fig45_ablations.run_fig4(q),
        "fig5": lambda q: fig45_ablations.run_fig5(q),
        "lemma51": lambda q: lemma_checks.run_lemma51(q),
        "lemmaA1": lambda q: lemma_checks.run_lemmaA1(q),
        "kernels": lambda q: _kernel_bench(q),
        "roofline": lambda q: roofline_report.run(q),
        "perf_lp": lambda q: perf_lp.run(q),
        "perf_lp_tol": lambda q: perf_lp.run_tolerance(q),
        "perf_lp_bytes": lambda q: perf_lp.run_bytes(q),
        "perf_lp_serve": lambda q: perf_lp.run_serve(q),
        "perf_lp_load": lambda q: perf_lp.run_load(q),
    })


def _merge_results(out_path: str, rows, fresh: bool):
    """Merge new rows into the artifact keyed by row name.

    A partial run (--only, --quick) updates its own rows in place and
    appends genuinely new ones, instead of silently discarding every other
    suite's results (the old wholesale-overwrite trap).  `fresh=True`
    restores the replace behavior.
    """
    if not fresh and os.path.exists(out_path):
        try:
            with open(out_path) as f:
                old = json.load(f)
        except (json.JSONDecodeError, OSError):
            old = []
        new_by_name = {r["name"]: r for r in rows}
        merged = [new_by_name.pop(r.get("name"), r) for r in old]
        merged.extend(r for r in rows if r["name"] in new_by_name)
        rows = merged
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as f:
        json.dump(rows, f, indent=1, default=str)


def _append_history(history_path: str, suite: str, rows, quick: bool,
                    seconds: float) -> None:
    """Append one summary line for an executed suite (module doc).

    The line is self-contained (timestamp, suite, row name -> us_per_call
    + any resource watermarks) so a plain `jq`/grep over the file answers
    "how has perf_lp/it6 moved over the last month" without loading the
    merged artifact.  Append-only by design: history is never rewritten.
    """
    entry = {
        "ts": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        "suite": suite,
        "quick": bool(quick),
        "seconds": round(seconds, 3),
        "rows": {
            r["name"]: {
                "us_per_call": r["us_per_call"],
                **{k: r.get("derived", {}).get(k)
                   for k in ("peak_rss_bytes", "peak_hbm_bytes")
                   if k in r.get("derived", {})},
            }
            for r in rows},
    }
    os.makedirs(os.path.dirname(history_path), exist_ok=True)
    with open(history_path, "a") as f:
        f.write(json.dumps(entry, default=str, sort_keys=True) + "\n")


def _list_suites() -> None:
    """Print the registered suites and what each one measures (--list)."""
    descriptions = {}
    for line in (__doc__ or "").splitlines():
        parts = line.split(None, 1)
        if len(parts) == 2 and parts[0] in SUITES:
            descriptions[parts[0]] = parts[1].strip()
    for name in SUITES:
        print(f"{name:16s} {descriptions.get(name, '')}".rstrip())


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--fresh", action="store_true",
                    help="replace bench_results.json wholesale instead of "
                         "merging this run's rows into it")
    ap.add_argument("--list", action="store_true",
                    help="list registered suites and exit (runs nothing)")
    ap.add_argument("--no-history", action="store_true",
                    help="skip the bench_history.jsonl append for this run")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    _register()
    if args.list:
        _list_suites()
        return
    suites = {args.only: SUITES[args.only]} if args.only else SUITES
    results_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                               "results")
    history = os.path.join(results_dir, "bench_history.jsonl")
    all_rows = []
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        t0 = time.perf_counter()
        try:
            rows = fn(args.quick)
        except Exception as e:  # report, keep going
            rows = [{"name": f"{name}/ERROR", "us_per_call": 0.0,
                     "derived": {"error": str(e)[:200]}}]
        for r in rows:
            print(f"{r['name']},{r['us_per_call']:.2f},"
                  f"\"{json.dumps(r['derived'], default=str)}\"")
            sys.stdout.flush()
        all_rows.extend(rows)
        if not args.no_history:
            _append_history(history, name, rows, args.quick,
                            time.perf_counter() - t0)
    out = os.path.join(results_dir, "bench_results.json")
    _merge_results(out, all_rows, args.fresh)


if __name__ == "__main__":
    main()
