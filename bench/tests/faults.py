"""Faults planted under the timed path, for the tests that must see
`correct` come out false.  Each patches the program where the answer is
produced; none touches the benchmark."""
from __future__ import annotations


def frozen_step(monkeypatch):
    """The update step returns its state unchanged."""
    from repro.core.update_rules import AGDRule
    step = AGDRule.step

    def frozen(self, calculate, config, gamma_fn, state, xs):
        return state, step(self, calculate, config, gamma_fn, state, xs)[1]

    monkeypatch.setattr(AGDRule, "step", frozen)


def half_batch(monkeypatch):
    """The sweep leaves out the second half of each batch of rows."""
    import jax.numpy as jnp
    from repro.core import objectives
    sweep = objectives.slab_xcarry

    def half(slab, lam, gamma, *args, **kw):
        x, _, _ = sweep(slab, lam, gamma, *args, **kw)
        x = jnp.where((jnp.arange(x.shape[0]) < x.shape[0] // 2)[:, None],
                      x, 0.0)
        return x, jnp.vdot(slab.c_vals, x), jnp.vdot(x, x)

    monkeypatch.setattr(objectives, "slab_xcarry", half)


def altered_dual(monkeypatch):
    """The dual objective comes back altered by 0.1%."""
    from repro.core.distributed import DistributedMatchingObjective
    calculate = DistributedMatchingObjective.calculate

    def altered(self, lam, gamma):
        g, grad, aux = calculate(self, lam, gamma)
        return g * 1.001, grad, aux

    monkeypatch.setattr(DistributedMatchingObjective, "calculate", altered)


SOLVE = {"none": None, "frozen_step": frozen_step, "half_batch": half_batch,
         "altered_answer": altered_dual}
