"""Constraint-aligned Ax reduction: plan packing, kernel, and solve parity.

Covers the destination-major companion layout (core.types.AxPlan):
  - packing parity: the plan's gather rows cover every real edge exactly
    once, bucketed by in-degree, with every destination present; the
    value-carrying `a_dm` copy equals `a_flat[edge_idx]` entry for entry;
  - numerical parity: aligned x-carry (XLA and Pallas) vs aligned_gvals vs
    scatter vs sorted Ax on random instances and dtypes, f32 accumulation
    for bf16 inputs;
  - end-to-end: identical converged dual through the full solver, the
    GlobalCountObjective subclass, the distributed (shard_map) path, and
    the compiled multi_budget formulation — x-carry vs the legacy
    gvals-aligned lowering included.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core import (GlobalCountObjective, InstanceSpec, MatchingObjective,
                        Maximizer, SolveConfig, build_ax_plan,
                        build_sharded_ax_plan, generate, precondition)
from repro.core.distributed import pad_for_sharding, solve_distributed
from repro.core.types import edge_space
from repro.kernels import ops as kops, ref as kref
from repro.launch.mesh import make_mesh


def _edge_map(slabs):
    """{destination: sorted flat edge positions} ground truth from slabs."""
    out, off = {}, 0
    for s in slabs:
        d = edge_space(np.asarray(s.dest_idx))
        mk = edge_space(np.asarray(s.mask)).astype(bool)
        for pos in np.nonzero(mk)[0]:
            out.setdefault(int(d[pos]), []).append(off + int(pos))
        off += d.size
    return {j: sorted(v) for j, v in out.items()}


@pytest.fixture(scope="module")
def lp():
    spec = InstanceSpec(num_sources=120, num_destinations=19,
                        avg_nnz_per_row=9, seed=11, num_families=2)
    return jax.tree.map(jnp.asarray, generate(spec))


class TestPlanPacking:
    def test_every_edge_exactly_once(self, lp):
        plan = build_ax_plan(lp)
        truth = _edge_map(lp.slabs)
        seen = {}
        for b in plan.buckets:
            for r in range(b.rows):
                j = int(b.dest_ids[r])
                real = np.asarray(b.edge_idx[r])[np.asarray(b.mask[r])]
                seen.setdefault(j, []).extend(int(e) for e in real)
        J = lp.num_destinations
        assert set(seen) == set(range(J))          # every dual row present
        for j in range(J):
            assert sorted(seen[j]) == truth.get(j, []), j

    def test_bucket_widths_pow2_and_cover_indegree(self, lp):
        plan = build_ax_plan(lp)
        for b in plan.buckets:
            w = b.width
            assert w & (w - 1) == 0                # power of two
            indeg = np.asarray(b.mask).sum(axis=1)
            assert indeg.max() <= w
            # bucketing is tight: at least one row needs > w/2 (or min width)
            assert w == 4 or indeg.max() > w // 2

    def test_inv_perm_is_destination_gather(self, lp):
        plan = build_ax_plan(lp)
        dest_concat = np.concatenate(
            [np.asarray(b.dest_ids) for b in plan.buckets])
        inv = np.asarray(plan.inv_perm)
        np.testing.assert_array_equal(dest_concat[inv],
                                      np.arange(lp.num_destinations))

    def test_a_dm_packing_parity(self, lp):
        """a_dm[r, q] == a_flat[edge_idx[r, q]] on real slots, 0 on padding."""
        plan = build_ax_plan(lp)
        a_flat = np.concatenate([edge_space(np.asarray(s.a_vals))
                                 for s in lp.slabs])
        for b in plan.buckets:
            assert b.a_dm.shape == (*b.edge_idx.shape, lp.m)
            want = np.where(np.asarray(b.mask)[..., None],
                            a_flat[np.asarray(b.edge_idx)], 0.0)
            np.testing.assert_array_equal(np.asarray(b.a_dm), want)

    def test_carry_values_false_packs_index_only(self, lp):
        plan = build_ax_plan(lp, carry_values=False)
        assert all(b.a_dm is None for b in plan.buckets)

    def test_sharded_a_dm_packing_parity(self, lp):
        n_shards = 2
        lp_pad = pad_for_sharding(lp, n_shards)
        plan = build_sharded_ax_plan(lp_pad, n_shards)
        for k in range(n_shards):
            locals_ = []
            for s in lp_pad.slabs:
                nl = s.n // n_shards
                locals_.append(edge_space(
                    np.asarray(s.a_vals)[k * nl:(k + 1) * nl]))
            a_flat = np.concatenate(locals_)
            for b in plan.buckets:
                want = np.where(np.asarray(b.mask[k])[..., None],
                                a_flat[np.asarray(b.edge_idx[k])], 0.0)
                np.testing.assert_array_equal(np.asarray(b.a_dm[k]), want)

    def test_sharded_plan_partitions_local_edges(self, lp):
        n_shards = 2
        lp_pad = pad_for_sharding(lp, n_shards)
        plan = build_sharded_ax_plan(lp_pad, n_shards)
        for k in range(n_shards):
            local_slabs = []
            for s in lp_pad.slabs:
                nl = s.n // n_shards
                local_slabs.append(jax.tree.map(
                    lambda a: a[k * nl:(k + 1) * nl], s))
            truth = _edge_map(local_slabs)
            shard_plan = jax.tree.map(lambda a: a[k], plan)
            seen = {}
            for b in shard_plan.buckets:
                for r in range(b.edge_idx.shape[0]):
                    j = int(b.dest_ids[r])
                    real = np.asarray(b.edge_idx[r])[np.asarray(b.mask[r])]
                    seen.setdefault(j, []).extend(int(e) for e in real)
            for j in range(lp.num_destinations):
                assert sorted(seen.get(j, [])) == truth.get(j, []), (k, j)


class TestSortedScatterAlias:
    def test_sorted_scatter_warns_and_maps_to_sorted(self, lp):
        with pytest.warns(DeprecationWarning, match="sorted_scatter"):
            obj = MatchingObjective(lp, sorted_scatter=True)
        assert obj.ax_mode == "sorted"

    def test_ax_mode_does_not_warn(self, lp):
        import warnings
        with warnings.catch_warnings():
            warnings.simplefilter("error", DeprecationWarning)
            obj = MatchingObjective(lp, ax_mode="sorted")
        assert obj.ax_mode == "sorted"


class TestAlignedReduction:
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_matches_segment_sum(self, lp, dtype):
        plan = jax.tree.map(jnp.asarray, build_ax_plan(lp))
        E = sum(s.n * s.width for s in lp.slabs)
        rng = np.random.default_rng(0)
        gv = jnp.asarray(rng.normal(size=(E, lp.m)).astype(np.float32),
                         dtype=dtype)
        # zero padded-edge values, as real gvals are (a_vals=0 on padding)
        mask = jnp.concatenate([edge_space(jnp.asarray(s.mask))
                                for s in lp.slabs])
        gv = jnp.where(mask[:, None], gv, 0)
        dests = jnp.concatenate([edge_space(s.dest_idx) for s in lp.slabs])
        ref = jax.vmap(lambda g: jax.ops.segment_sum(
            g.astype(jnp.float32), dests,
            num_segments=lp.num_destinations),
            in_axes=-1, out_axes=0)(gv)
        got = kops.ax_aligned(plan, gv, out_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=1e-5, atol=1e-4)

    def test_pallas_bucket_matches_oracle(self, lp):
        plan = jax.tree.map(jnp.asarray, build_ax_plan(lp))
        E = sum(s.n * s.width for s in lp.slabs)
        gv = jnp.asarray(np.random.default_rng(1)
                         .normal(size=(E, lp.m)).astype(np.float32))
        for b in plan.buckets:
            want = kref.ax_reduce_ref(gv, b.edge_idx, b.mask)
            got = kops.ax_reduce_bucket(gv, b.edge_idx, b.mask)
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=1e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_x_pallas_bucket_matches_oracle(self, lp, dtype):
        """Value-carrying kernel vs oracle, f32 and bf16 slabs: the
        product forms in the input dtype, accumulation is always f32."""
        plan = jax.tree.map(jnp.asarray, build_ax_plan(lp))
        E = sum(s.n * s.width for s in lp.slabs)
        x = jnp.asarray(np.random.default_rng(2)
                        .normal(size=(E,)).astype(np.float32), dtype=dtype)
        # eager bf16 truncates the a·x product where the jitted kernel's
        # multiply+convert fuses at f32 precision (XLA's bf16 laxity) —
        # same tolerance split as test_kernels.py
        tol = (dict(rtol=1e-6, atol=1e-5) if dtype == jnp.float32
               else dict(rtol=5e-2, atol=5e-2))
        for b in plan.buckets:
            a_dm = b.a_dm.astype(dtype)
            want = kref.ax_reduce_x_ref(x, a_dm, b.edge_idx, b.mask)
            got = kops.ax_reduce_bucket_x(x, a_dm, b.edge_idx, b.mask)
            assert got.dtype == jnp.float32          # f32 accumulation
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       **tol)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("use_pallas", [False, True])
    def test_ax_aligned_x_matches_gvals_reduction(self, lp, dtype,
                                                  use_pallas):
        """x-carry == gvals reduction fed the very same products."""
        plan = jax.tree.map(jnp.asarray, build_ax_plan(lp))
        E = sum(s.n * s.width for s in lp.slabs)
        rng = np.random.default_rng(3)
        x = jnp.asarray(rng.normal(size=(E,)).astype(np.float32),
                        dtype=dtype)
        a_flat = jnp.concatenate([edge_space(s.a_vals)
                                  for s in lp.slabs]).astype(dtype)
        gv = a_flat * x[:, None]
        want = kops.ax_aligned(plan, gv, out_dtype=jnp.float32)
        plan_t = jax.tree.map(
            lambda a: a.astype(dtype) if a.ndim == 3 else a, plan)
        got = kops.ax_aligned_x(plan_t, x, use_pallas=use_pallas,
                                out_dtype=jnp.float32)
        tol = dict(rtol=1e-6, atol=1e-5) if dtype == jnp.float32 \
            else dict(rtol=5e-2, atol=5e-2)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want), **tol)

    def test_ax_aligned_x_rejects_index_only_plan(self, lp):
        plan = jax.tree.map(jnp.asarray, build_ax_plan(lp,
                                                       carry_values=False))
        E = sum(s.n * s.width for s in lp.slabs)
        with pytest.raises(ValueError, match="value-carrying"):
            kops.ax_aligned_x(plan, jnp.zeros((E,), jnp.float32))

    @pytest.mark.parametrize("seed,m", [(0, 1), (5, 2), (9, 3)])
    def test_objective_parity_random_instances(self, seed, m):
        spec = InstanceSpec(num_sources=90, num_destinations=13,
                            avg_nnz_per_row=7, seed=seed, num_families=m)
        lp = jax.tree.map(jnp.asarray, generate(spec))
        rng = np.random.default_rng(seed)
        lam = jnp.asarray(rng.uniform(0, 1, (m, 13)).astype(np.float32))
        gamma = jnp.float32(0.05)
        outs = {}
        for mode in ("scatter", "sorted", "aligned", "aligned_gvals"):
            g, grad, aux = MatchingObjective(lp, ax_mode=mode).calculate(
                lam, gamma)
            outs[mode] = (np.asarray(g), np.asarray(grad))
        for mode in ("sorted", "aligned", "aligned_gvals"):
            np.testing.assert_allclose(outs[mode][0], outs["scatter"][0],
                                       rtol=1e-5)
            np.testing.assert_allclose(outs[mode][1], outs["scatter"][1],
                                       rtol=1e-4, atol=1e-4)
        # x-carry and the gvals-aligned lowering share every product and
        # summation order — identical to the last bit
        np.testing.assert_array_equal(outs["aligned"][1],
                                      outs["aligned_gvals"][1])


class TestEndToEnd:
    # small steps: the whole dual trajectory is then deterministic up to fp
    # reassociation, so parity is tight (large steps make AGD chaotic and a
    # 1-ulp Ax difference forks the λ path; the *converged dual* still
    # agrees there, but only to ~1e-5 — tested at the bench protocol level).
    CFG = dict(iterations=300, gamma=0.1, max_step=0.05, initial_step=1e-4)

    def _solve(self, lp, **kw):
        cfg = SolveConfig(**self.CFG,
                          use_pallas=kw.pop("use_pallas", False))
        obj = MatchingObjective(lp, use_pallas=cfg.use_pallas, **kw)
        return Maximizer(cfg).maximize(obj)

    def test_solve_parity_aligned_vs_scatter(self, lp):
        lp_pc, _ = precondition(lp, row_norm=True)
        ref = self._solve(lp_pc)
        ali = self._solve(lp_pc, ax_mode="aligned")
        pal = self._solve(lp_pc, ax_mode="aligned", use_pallas=True)
        a = np.asarray(ref.stats.dual_obj)
        for res in (ali, pal):
            rel = np.abs((np.asarray(res.stats.dual_obj) - a)
                         / np.maximum(np.abs(a), 1e-8)).max()
            assert rel < 1e-5, rel
            np.testing.assert_allclose(np.asarray(res.lam),
                                       np.asarray(ref.lam), atol=1e-3)

    def test_global_count_inherits_ax_mode(self, lp):
        gamma = jnp.float32(0.1)
        lamf = jnp.asarray(
            np.random.default_rng(2).uniform(
                0, 0.5, lp.m * lp.num_destinations + 1).astype(np.float32))
        g0, grad0, _ = GlobalCountObjective(lp, count=8.0).calculate(
            lamf, gamma)
        g1, grad1, _ = GlobalCountObjective(
            lp, count=8.0, ax_mode="aligned").calculate(lamf, gamma)
        g2, grad2, _ = GlobalCountObjective(
            lp, count=8.0, ax_mode="aligned", use_pallas=True).calculate(
            lamf, gamma)
        np.testing.assert_allclose(float(g1), float(g0), rtol=1e-5)
        np.testing.assert_allclose(np.asarray(grad1), np.asarray(grad0),
                                   rtol=1e-4, atol=1e-4)
        np.testing.assert_allclose(float(g2), float(g0), rtol=1e-4)
        np.testing.assert_allclose(np.asarray(grad2), np.asarray(grad0),
                                   rtol=1e-3, atol=1e-3)

    def test_distributed_aligned_matches_reference(self, lp):
        lp_pc, _ = precondition(lp, row_norm=True)
        cfg = SolveConfig(**self.CFG)
        ref = Maximizer(cfg).maximize(MatchingObjective(lp_pc))
        mesh = make_mesh((1, 1), ("data", "model"))
        dist = solve_distributed(lp_pc, cfg, mesh, ax_mode="aligned")
        a = float(ref.stats.dual_obj[-1])
        assert abs(float(dist.stats.dual_obj[-1]) - a) < 1e-4 * abs(a)

    def test_xcarry_trajectory_matches_gvals_aligned(self, lp):
        """The tentpole's correctness bar: the x-carry path reproduces the
        gvals-aligned dual trajectory (same products, same summation order
        — drift far below the 1e-6 acceptance tolerance)."""
        lp_pc, _ = precondition(lp, row_norm=True)
        gv = self._solve(lp_pc, ax_mode="aligned_gvals")
        xc = self._solve(lp_pc, ax_mode="aligned")
        a = np.asarray(gv.stats.dual_obj)
        rel = np.abs((np.asarray(xc.stats.dual_obj) - a)
                     / np.maximum(np.abs(a), 1e-8)).max()
        assert rel <= 1e-6, rel
        np.testing.assert_allclose(np.asarray(xc.lam), np.asarray(gv.lam),
                                   atol=1e-5)

    def test_xcarry_matched_stopping_criteria_drift(self, lp):
        """Under ONE shared StoppingCriteria, x-carry and gvals-aligned
        stop at the same check with dual_drift_rel <= 1e-6 (the
        acceptance-criterion protocol, small-scale)."""
        from repro.core import StoppingCriteria
        lp_pc, _ = precondition(lp, row_norm=True)
        cfg = SolveConfig(iterations=3000, gamma=0.1, max_step=10.0,
                          initial_step=1e-3)
        crit = StoppingCriteria(tol_rel_dual=1e-7, check_every=50)
        res = {}
        for mode in ("aligned_gvals", "aligned"):
            res[mode] = Maximizer(cfg).maximize(
                MatchingObjective(lp_pc, ax_mode=mode), criteria=crit)
            assert res[mode].converged
        a = float(res["aligned_gvals"].stats.dual_obj[-1])
        b = float(res["aligned"].stats.dual_obj[-1])
        assert abs(a - b) / abs(a) <= 1e-6
        assert (res["aligned"].iterations_run
                == res["aligned_gvals"].iterations_run)

    def test_distributed_xcarry_matches_gvals_aligned(self, lp):
        lp_pc, _ = precondition(lp, row_norm=True)
        cfg = SolveConfig(**self.CFG)
        mesh = make_mesh((1, 1), ("data", "model"))
        gv = solve_distributed(lp_pc, cfg, mesh, ax_mode="aligned_gvals")
        xc = solve_distributed(lp_pc, cfg, mesh, ax_mode="aligned")
        a = float(gv.stats.dual_obj[-1])
        assert abs(float(xc.stats.dual_obj[-1]) - a) <= 1e-6 * abs(a)

    def test_multi_budget_compiled_xcarry_parity(self, lp):
        """The compiled formulation path (coupling rows + shift hook) rides
        the same x-carry sweep: solve parity vs its gvals-aligned twin."""
        from repro import formulations
        cfg = SolveConfig(**self.CFG)
        res = {}
        for mode in ("aligned_gvals", "aligned"):
            obj = formulations.make_objective("multi_budget", lp,
                                              ax_mode=mode, row_norm=True)
            res[mode] = Maximizer(cfg).maximize(obj)
        a = np.asarray(res["aligned_gvals"].stats.dual_obj)
        rel = np.abs((np.asarray(res["aligned"].stats.dual_obj) - a)
                     / np.maximum(np.abs(a), 1e-8)).max()
        assert rel <= 1e-6, rel
