"""The benchmark's generator, byte count, reference and trace reduction."""
from __future__ import annotations

import numpy as np
import pytest

from bench.lib import bytes as least, gen, peaks, reference, trace

PARAMS = dict(instance_seed=0, num_sources=3000, num_destinations=30,
              avg_nnz_per_row=10, num_families=1, c_max=10.0,
              breadth_sigma=1.0, value_sigma=0.5, noise_sigma=0.25,
              scale_sigma=1.0, rho_low=0.5, rho_high=1.0, rhs_eps=1e-3,
              budget_s=1.0, box_ub=1.0, min_width=4)


def test_generator_is_fixed_by_the_seed():
    a, b = gen.generate(PARAMS, 2**31 + 7), gen.generate(PARAMS, 2**31 + 7)
    for f in ("src", "dst", "value", "a", "b"):
        np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
    assert np.all(np.diff(a.src) >= 0)


def test_seeds_relabel_one_instance():
    a, b = gen.generate(PARAMS, 1), gen.generate(PARAMS, 2)
    assert not np.array_equal(a.src, b.src)
    np.testing.assert_allclose(a.b, b.b, rtol=1e-12)   # summed in another order
    keys = []
    for e, seed in ((a, 1), (b, 2)):
        original = np.argsort(gen.relabel(PARAMS["num_sources"], seed))
        keys.append(np.lexsort((e.dst, original[e.src])))
    np.testing.assert_array_equal(a.value[keys[0]], b.value[keys[1]])
    np.testing.assert_array_equal(a.dst[keys[0]], b.dst[keys[1]])


def test_mean_degree_is_nu():
    e = gen.generate(dict(PARAMS, num_sources=20000), 3)
    assert abs(e.num_edges / 20000 - PARAMS["avg_nnz_per_row"]) < 0.2
    assert len(np.unique(e.src * 1000 + e.dst)) == e.num_edges


def test_least_bytes_by_hand():
    # 3 edges x (4 a + 4 c + 4 dst + 4 ub) + 2 sources x 4 + 3 x 2 x 4
    assert least.least_bytes_per_iteration(3, 2, 2, 1) == 48 + 8 + 24
    assert least.least_bytes_per_iteration(10, 4, 5, 2) == (
        10 * 20 + 16 + 3 * 2 * 5 * 4)


def test_peaks_refuse_an_unknown_device():
    assert peaks.peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    with pytest.raises(KeyError):
        peaks.peak("cpu", "hbm_bytes_per_s")


def _bisect(v, ub, s):
    """Float64 bisection for one row: the plain definition."""
    f = lambda t: np.clip(v - t, 0, ub).sum()  # noqa: E731
    if f(0.0) <= s:
        return np.clip(v, 0, ub)
    lo, hi = 0.0, float(v.max())
    for _ in range(200):
        mid = 0.5 * (lo + hi)
        lo, hi = (mid, hi) if f(mid) > s else (lo, mid)
    return np.clip(v - 0.5 * (lo + hi), 0, ub)


@pytest.mark.parametrize("v,ub,s,want", [
    ([0.6, 0.6], 1.0, 1.0, [0.5, 0.5]),
    ([0.2, 0.3], 1.0, 1.0, [0.2, 0.3]),
    ([2.0, -1.0], 1.0, 1.0, [1.0, 0.0]),
    ([0.9, 0.9, 0.9], 0.4, 1.0, [1 / 3, 1 / 3, 1 / 3]),
    ([5.0, 0.2, 0.1], 0.5, 1.0, [0.5, 0.2, 0.1]),
])
def test_projection_by_hand(v, ub, s, want):
    import jax.numpy as jnp
    v = jnp.asarray([v + [0.0]], jnp.float32)
    mask = jnp.asarray([[True] * (v.shape[1] - 1) + [False]])
    x = reference._project(v, jnp.full(v.shape, ub, jnp.float32),
                           jnp.asarray([s], jnp.float32), mask)
    np.testing.assert_allclose(np.asarray(x)[0], want + [0.0], atol=1e-6)


def test_reference_matches_a_float64_loop():
    e = gen.generate(dict(PARAMS, num_sources=300, num_destinations=8), 5)
    pre = reference.precondition(e)
    lam = np.random.default_rng(0).uniform(0, 0.5, (1, 8))
    import jax.numpy as jnp
    ev = reference.evaluate(e, pre, lam, 0.05, jnp.float32)
    x = np.zeros(e.num_edges)
    for i in range(e.num_sources):
        idx = np.flatnonzero(e.src == i)
        u = -(pre.a[0, idx] * lam[0, e.dst[idx]] + pre.c[idx]) / 0.05
        x[idx] = _bisect(u, e.box_ub, e.budget_s)
    ax = np.bincount(e.dst, weights=pre.a[0] * x, minlength=8)
    np.testing.assert_allclose(ev.ax[0], ax, rtol=1e-4, atol=1e-6)
    g = pre.c @ x + 0.025 * x @ x + lam[0] @ (ax - pre.b[0])
    assert abs(ev.g - g) <= 1e-5 * max(1.0, abs(g))


def _ev(name, s, e):
    return (name, s, e)


def test_trace_union_gaps_and_idle_share():
    ops = [_ev("a", 0.0, 1.0), _ev("b", 0.5, 2.0), _ev("c", 3.0, 4.0),
           _ev("d", 4.0, 4.5)]
    host = [_ev("host.sync", 1.95, 3.05), _ev("outer", 1.5, 3.5)]
    red = trace.reduce([ops], host, (0.0, 5.0))
    assert red.busy_s == pytest.approx(3.5)
    assert red.readings()["idle_share"] == pytest.approx(0.3)
    assert red.idle_by_host["host.sync"] == pytest.approx(1.0)
    assert red.idle_by_host["host idle"] == pytest.approx(0.5)
    assert trace.union(np.array([0.0, 0.5, 3.0]),
                       np.array([1.0, 2.0, 4.0])) == [(0.0, 2.0), (3.0, 4.0)]
    assert trace.gaps([(1.0, 2.0)], (0.0, 3.0)) == [(0.0, 1.0), (2.0, 3.0)]


def test_trace_self_time_of_nested_ops_and_window_clip():
    ops = [_ev("loop", 0.0, 10.0), _ev("gather", 1.0, 4.0),
           _ev("gather", 5.0, 7.0), _ev("inner", 5.5, 6.0),
           _ev("late", 11.0, 13.0)]
    red = trace.reduce([ops], [], (0.0, 12.0))
    assert red.op_seconds["loop"] == pytest.approx(5.0)
    assert red.op_seconds["gather"] == pytest.approx(4.5)
    assert red.op_seconds["inner"] == pytest.approx(0.5)
    assert red.op_seconds["late"] == pytest.approx(1.0)
    assert red.busy_s == pytest.approx(11.0)
    assert red.breakdown()["device_ops"][0] == ["loop", pytest.approx(5.0)]


def test_trace_without_device_ops_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce([[]], [], (0.0, 1.0))


def _evaluation():
    ax = np.array([[3.0, 1.0, 0.5]])
    b = np.array([[2.0, 2.0, 1.0]])
    return reference.Evaluation(g=-10.0, ax=ax, b=b, grad=ax - b,
                                scale=np.array([[4.0, 4.0, 2.0]]), cx=-8.0,
                                x_sq=1.0, infeas=1.0)


@pytest.mark.parametrize("field,delta,number,want", [
    ("g", 0.02, "g_rel_err", 0.002),
    ("grad", 0.01, "ax_err", 0.01 / 2.0),
    ("cx", -0.04, "primal_rel_err", 0.005),
    ("g", -20.0, "g_rel_err", 2.0),
])
def test_compared_numbers_by_hand(field, delta, number, want):
    from bench.lib import compare
    ref = _evaluation()
    prog = {"y": np.array([[0.5, 0.25, 1.0]]), "grad": ref.grad.copy(),
            "g": ref.g, "cx": ref.cx}
    assert all(v == 0 for v in compare.solve_numbers(prog, ref).values())
    prog[field] = prog[field] + delta
    got = compare.solve_numbers(prog, ref)
    assert got[number] == pytest.approx(want, abs=1e-15)
    assert all(v == 0 for k, v in got.items()
               if k != number and field != "grad")


def test_gap_reads_the_dual_gradient_at_the_point():
    from bench.lib import compare
    ref = _evaluation()
    prog = {"y": np.array([[0.5, 0.25, 1.0]]), "grad": ref.grad + 0.1,
            "g": ref.g, "cx": ref.cx}
    got = compare.solve_numbers(prog, ref)
    assert got["gap_rel_err"] == pytest.approx(0.1 * 1.75 / 10.0)
    assert got["ax_err"] == pytest.approx(0.1 / 2.0)
