"""Unit tests for the maximizer's schedule helpers and the AGD step.

Covers the pieces the system tests only exercise implicitly: the γ
continuation schedule (`gamma_at`), the γ-proportional step cap
(`max_step_at`), and the O'Donoghue–Candès adaptive restart inside
`agd_step` (momentum age resets when the gradient opposes travel).
"""
import numpy as np
import jax.numpy as jnp
import pytest

from repro.core import SolveConfig, gamma_at, max_step_at
from repro.core.maximizer import agd_step
from repro.core.objectives import ObjectiveAux
from repro.core.types import SolveState


CONT = SolveConfig(gamma=0.01, gamma_init=0.16, gamma_decay_every=25,
                   gamma_decay_rate=0.5, max_step=1e-3)


class TestGammaSchedule:
    def test_decay_points(self):
        # decays exactly at multiples of gamma_decay_every
        for it, want in [(0, 0.16), (24, 0.16), (25, 0.08), (49, 0.08),
                         (50, 0.04), (75, 0.02), (100, 0.01)]:
            assert float(gamma_at(CONT, jnp.asarray(it))) == pytest.approx(
                want, rel=1e-6), it

    def test_floor_at_target_gamma(self):
        # 0.16 / 2^4 == 0.01 exactly; beyond that γ must stay clamped
        for it in [100, 125, 1000, 10**6]:
            assert float(gamma_at(CONT, jnp.asarray(it))) == pytest.approx(
                0.01, rel=1e-6)

    def test_constant_without_continuation(self):
        cfg = SolveConfig(gamma=0.01)                     # gamma_init unset
        assert float(gamma_at(cfg, jnp.asarray(0))) == pytest.approx(0.01)
        assert float(gamma_at(cfg, jnp.asarray(999))) == pytest.approx(0.01)
        # gamma_init <= gamma is "continuation off" too
        cfg = SolveConfig(gamma=0.01, gamma_init=0.01)
        assert float(gamma_at(cfg, jnp.asarray(999))) == pytest.approx(0.01)


class TestStepCap:
    def test_cap_scales_proportionally_with_gamma(self):
        # §5.1: L = ‖A‖²/γ, so the usable step shrinks as γ decays — the cap
        # follows γ/γ_target down to exactly max_step at the target
        for g, want in [(0.16, 16e-3), (0.08, 8e-3), (0.02, 2e-3),
                        (0.01, 1e-3)]:
            got = float(max_step_at(CONT, jnp.asarray(g, jnp.float32)))
            assert got == pytest.approx(want, rel=1e-5), g

    def test_cap_constant_when_scaling_disabled(self):
        cfg = SolveConfig(gamma=0.01, gamma_init=0.16,
                          scale_step_with_gamma=False, max_step=1e-3)
        for g in [0.16, 0.04, 0.01]:
            got = float(max_step_at(cfg, jnp.asarray(g, jnp.float32)))
            assert got == pytest.approx(1e-3, rel=1e-6)

    def test_cap_constant_without_continuation(self):
        cfg = SolveConfig(gamma=0.01, max_step=1e-3)
        got = float(max_step_at(cfg, jnp.asarray(0.01, jnp.float32)))
        assert got == pytest.approx(1e-3, rel=1e-6)


def _state(lam, y, k_mom, it=5):
    lam = jnp.asarray(lam, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    return SolveState(lam=lam, y=y, lam_prev=lam,
                      grad_prev=jnp.zeros_like(lam), y_prev=y - 0.1,
                      step=jnp.asarray(1e-3, jnp.float32),
                      l_est=jnp.asarray(1.0, jnp.float32),
                      k_mom=jnp.asarray(k_mom, jnp.int32),
                      it=jnp.asarray(it, jnp.int32))


def _calc_with_grad(grad):
    grad = jnp.asarray(grad, jnp.float32)

    def calculate(y, gamma):
        aux = ObjectiveAux(primal_obj=jnp.float32(0.0),
                           x_sq=jnp.float32(0.0),
                           ax=jnp.zeros_like(grad),
                           infeas=jnp.float32(0.0))
        return jnp.float32(0.0), grad, aux

    return calculate


class TestAdaptiveRestart:
    CFG = SolveConfig(gamma=0.1, max_step=1.0, initial_step=1e-2)

    def _step(self, state, grad):
        gamma_fn = lambda st: jnp.asarray(self.CFG.gamma, jnp.float32)
        return agd_step(_calc_with_grad(grad), self.CFG, gamma_fn,
                        state, None)

    def test_restart_when_gradient_opposes_travel(self):
        # y < λ with a positive (small) gradient: λ_new lands below λ, so
        # ⟨∇g, λ_new − λ⟩ < 0 — momentum must reset to age 0
        state = _state(lam=[1.0] * 4, y=[0.5] * 4, k_mom=7)
        new_state, _ = self._step(state, [0.1] * 4)
        assert int(new_state.k_mom) == 0
        # with β = 0 the extrapolated iterate collapses onto λ_new
        np.testing.assert_allclose(np.asarray(new_state.y),
                                   np.asarray(new_state.lam))

    def test_momentum_ages_when_aligned(self):
        # y > λ and a positive gradient: travel and gradient agree
        state = _state(lam=[1.0] * 4, y=[1.5] * 4, k_mom=7)
        new_state, _ = self._step(state, [0.1] * 4)
        assert int(new_state.k_mom) == 8
        beta = 8.0 / (8.0 + 3.0)
        lam_new = np.asarray(new_state.lam)
        want_y = lam_new + beta * (lam_new - 1.0)
        np.testing.assert_allclose(np.asarray(new_state.y), want_y,
                                   rtol=1e-6)

    def test_first_iteration_uses_initial_step(self):
        state = _state(lam=[1.0] * 4, y=[1.0] * 4, k_mom=0, it=0)
        new_state, stats = self._step(state, [0.1] * 4)
        assert float(stats.step) == pytest.approx(self.CFG.initial_step,
                                                  rel=1e-6)


class TestInstanceAsArgument:
    """The compiled programs take the instance as an argument: an array a
    jitted function closes over is otherwise compiled into the program as
    a literal, a copy of the whole instance per executable."""

    @pytest.fixture(scope="class")
    def obj(self):
        import jax
        from repro.core import (InstanceSpec, MatchingObjective, generate,
                                precondition)
        lp = generate(InstanceSpec(num_sources=3000, num_destinations=100,
                                   avg_nnz_per_row=8, seed=0))
        lp = precondition(jax.tree.map(jnp.asarray, lp), row_norm=True)[0]
        return MatchingObjective(lp, ax_mode="aligned")

    @staticmethod
    def _value_bytes(slabs):
        """Bytes of the slab arrays every evaluation reads."""
        return sum(s.a_vals.nbytes + s.c_vals.nbytes for s in slabs)

    def test_hoisted_equals_closure(self, obj):
        import jax
        from repro.core.maximizer import hoist_constants
        lam = jnp.full(obj.dual_shape, 0.5, jnp.float32)
        gamma = jnp.float32(0.05)
        calc, consts = hoist_constants(obj.calculate, lam, gamma)
        got = jax.jit(calc)(consts, lam, gamma)
        want = jax.jit(obj.calculate)(lam, gamma)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-6, atol=1e-6)

    def test_chunk_runner_takes_instance_as_argument(self, obj):
        from repro.core.maximizer import SolveEngine
        eng = SolveEngine(obj.calculate, SolveConfig(iterations=5))
        state = eng.rule.init_state(jnp.zeros(obj.dual_shape), eng.config)
        run = eng._runner(5, False, state, jnp.float32(0.01))
        mem = run.args[0].memory_analysis()
        assert mem.argument_size_in_bytes >= self._value_bytes(obj.lp.slabs)

    def test_primal_rows_fn_takes_slab_as_argument(self, obj):
        from repro.primal import primal_rows_fn
        fn = primal_rows_fn(obj, 0)
        lam = jnp.zeros(obj.dual_shape, jnp.float32)
        rows = jnp.arange(4, dtype=jnp.int32)
        fn(lam, 0.05, rows)
        (jitted, consts), = fn._by_shape.values()
        mem = jitted.lower(consts, lam, jnp.float32(0.05),
                           rows).compile().memory_analysis()
        assert (mem.argument_size_in_bytes
                >= self._value_bytes(obj.lp.slabs[:1]))
