"""Chaos smoke: kill the solve launcher mid-run, resume it, demand the
exact trajectory (DESIGN.md §9).

    PYTHONPATH=src python examples/chaos_smoke.py [--quick]

The preemption drill, end to end through the REAL process surface — not
an in-process simulation:

  1. run `repro.launch.solve` to completion with checkpointing on; save
     the reference duals;
  2. run it again, watch stdout for the first `checkpoint saved:` line,
     then deliver SIGTERM — the launcher's handler flushes a final
     checkpoint at the next chunk boundary and exits cleanly with
     stop reason `preempted`;
  3. relaunch with `--resume`: the fingerprint check accepts, the solve
     continues from the restored SolveState, and the final duals must
     match the uninterrupted run with drift ≤ 1e-7 (they are bitwise
     equal — the bound only guards against platform quirks);
  4. fault-injection sanity on the same instance size: a transient NaN
     chunk under the health guard rolls back and still converges.

Exit code is non-zero on any miss: no checkpoint line, unclean death,
refused resume, dual drift, or an unguarded recovery.  This file doubles
as the CI chaos smoke (--quick).
"""
import argparse
import os
import signal
import subprocess
import sys
import tempfile
import time

import numpy as np


def launch(args, extra, log_path):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    base = [sys.executable, "-m", "repro.launch.solve",
            "--sources", str(args.sources), "--destinations", "50",
            "--iterations", str(args.iterations),
            "--check-every", str(args.check_every),
            "--checkpoint-every", str(args.check_every),
            "--seed", "11"]
    log = open(log_path, "w")
    return subprocess.Popen(base + extra, stdout=log, stderr=subprocess.STDOUT,
                            env=env)


def wait_for_line(log_path, needle, timeout_s):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout_s:
        if os.path.exists(log_path):
            with open(log_path) as f:
                if needle in f.read():
                    return True
        time.sleep(0.2)
    return False


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--sources", type=int, default=None)
    args = ap.parse_args()
    args.sources = args.sources or (1500 if args.quick else 20000)
    args.iterations = 120
    args.check_every = 20

    failures = []
    with tempfile.TemporaryDirectory() as tmp:
        ck = os.path.join(tmp, "ck")
        ref_npz = os.path.join(tmp, "ref.npz")
        res_npz = os.path.join(tmp, "resumed.npz")

        # 1. uninterrupted reference (its own checkpoint dir, kept apart)
        print("== reference run ==", flush=True)
        p = launch(args, ["--checkpoint-dir", os.path.join(tmp, "ck_ref"),
                          "--save-duals", ref_npz],
                   os.path.join(tmp, "ref.log"))
        if p.wait(timeout=600) != 0:
            failures.append("reference run exited non-zero")

        # 2. kill mid-solve after the first checkpoint commits
        print("== interrupted run (SIGTERM) ==", flush=True)
        log1 = os.path.join(tmp, "run1.log")
        p = launch(args, ["--checkpoint-dir", ck], log1)
        if not wait_for_line(log1, "checkpoint saved:", timeout_s=300):
            failures.append("no checkpoint line before timeout")
            p.kill()
        else:
            p.send_signal(signal.SIGTERM)
        rc = p.wait(timeout=300)
        body = open(log1).read()
        if rc != 0:
            failures.append(f"interrupted run exited {rc} (want clean 0)")
        if "stop reason: preempted" not in body:
            failures.append("interrupted run did not report 'preempted'")
        print(body.strip().splitlines()[-1])

        # 3. resume to completion; duals must match the reference
        print("== resumed run ==", flush=True)
        log2 = os.path.join(tmp, "run2.log")
        p = launch(args, ["--checkpoint-dir", ck, "--resume",
                          "--save-duals", res_npz], log2)
        rc = p.wait(timeout=600)
        body = open(log2).read()
        if rc != 0:
            failures.append(f"resumed run exited {rc}")
        if "resumed from checkpoint step" not in body:
            failures.append("resume did not restore a checkpoint")
        if failures:
            print("\n".join(f"FAIL: {f}" for f in failures))
            return 1
        ref = np.load(ref_npz)["lam"]
        got = np.load(res_npz)["lam"]
        drift = float(np.abs(ref - got).max())
        print(f"dual drift vs uninterrupted: {drift:.3e}")
        if not (drift <= 1e-7):
            failures.append(f"dual drift {drift:.3e} > 1e-7")

    # 4. in-process fault injection: transient NaN -> rollback -> recovery
    print("== health-guard recovery ==", flush=True)
    import jax
    import jax.numpy as jnp
    from repro.core import (HealthConfig, InstanceSpec, MatchingObjective,
                            SolveConfig, StopReason, StoppingCriteria,
                            generate, precondition)
    from repro.core.maximizer import SolveEngine
    from repro.testing import ChunkFaultInjector
    spec = InstanceSpec(num_sources=min(args.sources, 2000),
                        num_destinations=50, avg_nnz_per_row=8, seed=11)
    lp, _ = precondition(jax.tree.map(jnp.asarray, generate(spec)),
                         row_norm=True)
    obj = MatchingObjective(lp)
    eng = SolveEngine(obj.calculate,
                      SolveConfig(iterations=args.iterations, gamma=0.01,
                                  max_step=1e-1, initial_step=1e-5))
    eng.chunk_fault_hook = ChunkFaultInjector(at_it=args.check_every,
                                              times=1)
    res = eng.solve(jnp.zeros(obj.dual_shape, jnp.float32),
                    criteria=StoppingCriteria(tol_grad_norm=0.0,
                                              check_every=args.check_every),
                    health=HealthConfig())
    if res.stop_reason != StopReason.MAX_ITERATIONS:
        failures.append(f"guarded solve stopped {res.stop_reason}")
    if not res.health or res.health[0].action != "rollback":
        failures.append("fault was not detected/rolled back")
    if not bool(jnp.isfinite(res.lam).all()):
        failures.append("guarded solve returned non-finite duals")
    print(f"health records: {[(r.status, r.action) for r in res.health]}")

    if failures:
        print("\n".join(f"FAIL: {f}" for f in failures))
        return 1
    print("chaos smoke: all checks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
