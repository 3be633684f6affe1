"""The dual-tile Ax reduction (kernels/ax_dual_tile.py, DESIGN.md §3).

  * the kernel (Pallas interpret mode) against a float64 scatter-add, and
    the x-carry sweep's Ax on the dual tile against `ax_mode="scatter"`
    and the AxPlan's `ax_aligned_x`, to float32 reordering: 1e-6 of
    Σ|a·x| at each destination;
  * cases over m ∈ {1, 2} and J ∈ {130, 300, 1,000}, with a hub
    destination spread over several grid steps, destinations with no
    edge, and NaN or huge x on masked padding slots;
  * the selection rule `select_ax_path` as a pure function, the per-path
    count in `repro.obs`, and the run manifest's `ax_path`.

The objectives take the dual tile only on a TPU; these tests steer them
there by patching `objectives.platform_of`.
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro import formulations, obs
from repro.core import (InstanceSpec, Maximizer, MatchingObjective,
                        SolveConfig, generate, objectives, precondition)
from repro.core.distributed import DistributedMatchingObjective, place_lp
from repro.core.types import Slab, edge_space
from repro.kernels import ax_dual_tile as kdt
from repro.kernels import ops
from repro.launch.mesh import make_mesh
from repro.obs import ListSink, Telemetry

TOL = 1e-6          # of Σ|a·x| at a destination: float32 reordering


def _slab(rng, n, w, m, J, hub_share=0.0):
    """A random slab whose destinations avoid the top tenth of [0, J)
    (destinations with no edge); `hub_share` of the real edges go to one
    hub destination.  Padding slots hold NaN and huge x."""
    mask = rng.uniform(size=(n, w)) < 0.7
    mask[:, 0] = True
    dest = rng.integers(0, J - J // 10, (n, w))
    dest[rng.uniform(size=(n, w)) < hub_share] = 7
    dest = np.where(mask, dest, 0).astype(np.int32)
    a = np.where(mask[..., None], rng.uniform(-1, 2, (n, w, m)), 0.0)
    x = rng.uniform(0, 1, (n, w)) * rng.choice([1e-3, 1.0, 3e2], (n, w))
    x = np.where(mask, x, np.where(rng.uniform(size=(n, w)) < 0.5,
                                   np.nan, 3e38))
    return mask, dest, a.astype(np.float32), x.astype(np.float32)


def _exact(mask, dest, a, x, J):
    """float64 Ax and Σ|a·x| per destination, (m, J) each."""
    v = np.where(mask[..., None], a * np.where(mask, x, 0.0)[..., None],
                 0.0).astype(np.float64)
    d = dest.ravel()
    ax = np.stack([np.bincount(d, v[..., k].ravel(), minlength=J)
                   for k in range(a.shape[-1])])
    scale = np.stack([np.bincount(d, np.abs(v[..., k]).ravel(), minlength=J)
                      for k in range(a.shape[-1])])
    return ax, scale


def _assert_close(got, want, scale):
    err = np.abs(np.asarray(got, np.float64) - want)
    assert (err <= TOL * scale + 1e-30).all(), \
        float((err / np.maximum(scale, 1e-30)).max())


@pytest.mark.parametrize("J", [130, 300, 1_000])
@pytest.mark.parametrize("m", [1, 2])
def test_kernel_matches_float64_scatter(m, J):
    rng = np.random.default_rng(100 * m + J)
    n, w = 700, 8
    mask, dest, a, x = _slab(rng, n, w, m, J, hub_share=0.3)
    ax_want, scale = _exact(mask, dest, a, x, J)
    v = np.where(mask[..., None], a * np.where(mask, x, 0.0)[..., None], 0.0)
    # 128 source rows a grid step: six steps, the hub in every one, the
    # last step overhanging the slab
    got = kdt.ax_dual_tile(jnp.asarray(v.transpose(2, 1, 0)),
                           jnp.asarray(dest.T), J, interpret=True,
                           lanes=128)
    assert got.shape == (m, J) and got.dtype == jnp.float32
    _assert_close(got, ax_want, scale)
    assert (np.asarray(got)[:, J - J // 10:] == 0).all()


@pytest.mark.parametrize("J", [130, 1_000])
@pytest.mark.parametrize("m", [1, 2])
def test_wrapper_masks_padding_and_sums_slabs(m, J):
    """`ops.ax_dual_tile` over two slabs of different widths, NaN and huge
    x on their padding: the exact sum, and the AxPlan path's result to
    float32 reordering."""
    rng = np.random.default_rng(7 * m + J)
    cases = [_slab(rng, 300, 4, m, J, hub_share=0.2),
             _slab(rng, 50, 16, m, J)]
    slabs, xs, want, scale = [], [], 0.0, 0.0
    for mask, dest, a, x in cases:
        slabs.append(Slab(a_vals=jnp.asarray(a), c_vals=None,
                          dest_idx=jnp.asarray(dest),
                          mask=jnp.asarray(mask), ub=None, s=None,
                          source_ids=None))
        xs.append(jnp.asarray(x))
        ax_k, sc_k = _exact(mask, dest, a, x, J)
        want, scale = want + ax_k, scale + sc_k
    got = ops.ax_dual_tile(slabs, xs, J)
    assert np.isfinite(np.asarray(got)).all()
    _assert_close(got, want, scale)

    from repro.core.instance import build_ax_plan
    from repro.core.types import LPData
    lp = LPData(slabs=tuple(jax.tree.map(np.asarray, s._replace(
        c_vals=s.mask, ub=s.mask, s=s.mask[:, 0], source_ids=s.mask[:, 0]))
        for s in slabs), b=np.zeros((m, J), np.float32))
    plan = jax.tree.map(jnp.asarray, build_ax_plan(lp))
    clean = [jnp.where(s.mask, x, 0.0) for s, x in zip(slabs, xs)]
    via_plan = ops.ax_aligned_x(plan, jnp.concatenate(
        [edge_space(x) for x in clean]))
    _assert_close(got, np.asarray(via_plan, np.float64), scale)


@pytest.fixture(scope="module")
def lp2():
    spec = InstanceSpec(num_sources=400, num_destinations=300,
                        avg_nnz_per_row=10, seed=5, num_families=2)
    return precondition(jax.tree.map(jnp.asarray, generate(spec)),
                        row_norm=True)[0]


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(objectives, "platform_of", lambda tree: "tpu")


def _lam(lp, seed=0):
    return jnp.asarray(np.random.default_rng(seed).uniform(
        0.0, 1.0, (lp.m, lp.num_destinations)).astype(np.float32))


def _row_scale(lp, xs):
    """Σ|a·x| per dual row, (m, J)."""
    scale = np.zeros((lp.m, lp.num_destinations))
    for s, x in zip(lp.slabs, xs):
        v = np.abs(np.asarray(s.a_vals, np.float64)
                   * np.asarray(x, np.float64)[..., None])
        for k in range(lp.m):
            scale[k] += np.bincount(np.asarray(s.dest_idx).ravel(),
                                    v[..., k].ravel(),
                                    minlength=lp.num_destinations)
    return scale


def test_sweep_ax_matches_scatter_and_ax_plan(lp2, monkeypatch):
    lam, gamma = _lam(lp2), jnp.float32(0.05)
    plan_obj = MatchingObjective(lp2, ax_mode="aligned")
    assert plan_obj.ax_path == "ax_plan" and plan_obj._plan is not None
    scatter = MatchingObjective(lp2, ax_mode="scatter").calculate(lam, gamma)
    via_plan = plan_obj.calculate(lam, gamma)
    monkeypatch.setattr(objectives, "platform_of", lambda tree: "tpu")
    tile_obj = MatchingObjective(lp2, ax_mode="aligned")
    assert tile_obj.ax_path == "dual_tile" and tile_obj._plan is None
    tiled = tile_obj.calculate(lam, gamma)
    scale = _row_scale(lp2, tile_obj.primal(lam, gamma))
    for ref in (scatter, via_plan):
        _assert_close(tiled[2].ax, np.asarray(ref[2].ax, np.float64), scale)
        np.testing.assert_allclose(float(tiled[0]), float(ref[0]),
                                   rtol=1e-5)


def test_distributed_sweep_on_the_dual_tile(lp2, on_tpu):
    mesh = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    axes = tuple(mesh.axis_names)
    placed = place_lp(lp2, mesh, axes)
    obj = DistributedMatchingObjective(lp=placed, mesh=mesh,
                                       source_axes=axes, ax_mode="aligned")
    assert obj.ax_path == "dual_tile" and obj._plan is None
    sharded = DistributedMatchingObjective(
        lp=placed, mesh=mesh, source_axes=axes, ax_mode="aligned",
        lambda_axis="model")
    assert sharded.ax_path == "ax_plan" and sharded._plan is not None
    lam, gamma = _lam(lp2, 1), jnp.float32(0.05)
    ref = MatchingObjective(lp2, ax_mode="scatter").calculate(lam, gamma)
    got = jax.jit(obj.calculate)(lam, gamma)
    scale = _row_scale(lp2, obj.primal(lam, gamma))
    _assert_close(got[2].ax, np.asarray(ref[2].ax, np.float64), scale)
    _assert_close(got[1], np.asarray(ref[1], np.float64), scale)


def test_multi_budget_on_the_dual_tile(lp2, monkeypatch):
    """The compiled formulation's sweep (coupling rows, shift hook) takes
    the same rule: its Ax and gradient on the dual tile match the
    AxPlan's."""
    lp = jax.tree.map(np.asarray, lp2)
    plan_obj = formulations.make_objective("multi_budget", lp,
                                           ax_mode="aligned", row_norm=True)
    monkeypatch.setattr(objectives, "platform_of", lambda tree: "tpu")
    tile_obj = formulations.make_objective("multi_budget", lp,
                                           ax_mode="aligned", row_norm=True)
    assert (plan_obj.ax_path, tile_obj.ax_path) == ("ax_plan", "dual_tile")
    lam = jnp.asarray(np.random.default_rng(2).uniform(
        0.0, 1.0, tile_obj.dual_shape).astype(np.float32))
    gamma = jnp.float32(0.05)
    want, got = plan_obj.calculate(lam, gamma), tile_obj.calculate(lam,
                                                                   gamma)
    m, J = tile_obj.lp.m, tile_obj.lp.num_destinations
    scale = _row_scale(tile_obj.lp, tile_obj.primal(lam, gamma))
    _assert_close(got[2].ax, np.asarray(want[2].ax, np.float64), scale)
    _assert_close(np.asarray(got[1])[:m * J].reshape(m, J),
                  np.asarray(want[1], np.float64)[:m * J].reshape(m, J),
                  scale)
    np.testing.assert_allclose(np.asarray(got[1])[m * J:],
                               np.asarray(want[1])[m * J:], rtol=1e-6)


@pytest.mark.parametrize("platform,lambda_axis,m,J,path", [
    ("tpu", None, 1, 10_000, "dual_tile"),
    ("tpu", None, 2, 10_000, "dual_tile"),
    ("cpu", None, 1, 10_000, "ax_plan"),
    ("gpu", None, 1, 10_000, "ax_plan"),
    ("tpu", "model", 1, 10_000, "ax_plan"),
    ("tpu", None, 1, 10_000_000, "ax_plan"),
    ("tpu", None, 4, 200_000, "ax_plan"),
])
def test_selection_rule(platform, lambda_axis, m, J, path):
    assert objectives.select_ax_path(platform, lambda_axis, m, J) == path


def test_selection_rule_edge_is_max_tile_rows():
    rows = kdt.MAX_TILE_ROWS
    J = rows // kdt.PARTS * kdt.LANES            # m = 1: exactly at R_max
    assert kdt.tile_rows(1, J) <= rows < kdt.tile_rows(1, J + kdt.LANES)
    assert objectives.select_ax_path("tpu", None, 1, J) == "dual_tile"
    assert objectives.select_ax_path("tpu", None, 1, J + kdt.LANES) \
        == "ax_plan"
    assert kdt.tile_rows(2, 10_000) == 2 * kdt.tile_rows(1, 10_000) == 474


def test_ax_path_counter_and_manifest(lp2, monkeypatch):
    before = obs.ax_paths()
    cfg = SolveConfig(iterations=4, gamma=0.1)
    paths = []
    for platform in ("cpu", "tpu"):
        monkeypatch.setattr(objectives, "platform_of",
                            lambda tree, p=platform: p)
        obj = MatchingObjective(lp2, ax_mode="aligned")
        sink = ListSink()
        Maximizer(cfg).maximize(obj, telemetry=Telemetry(sink=sink))
        manifest = [r for r in sink.records if r["type"] == "manifest"][-1]
        paths.append(manifest["ax_path"])
        assert manifest["ax_path"] == obj.ax_path
    assert paths == ["ax_plan", "dual_tile"]
    MatchingObjective(lp2, ax_mode="scatter")     # no Ax path to count
    after = obs.ax_paths()
    for path in ("ax_plan", "dual_tile"):
        assert after.get(path, 0) == before.get(path, 0) + 1, path


def test_dual_tile_builds_no_ax_plan(lp2, on_tpu):
    n0 = obs.span_totals().get("build.ax_plan", (0.0, 0))[1]
    mesh = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    axes = tuple(mesh.axis_names)
    MatchingObjective(lp2, ax_mode="aligned")
    DistributedMatchingObjective(lp=place_lp(lp2, mesh, axes), mesh=mesh,
                                 source_axes=axes, ax_mode="aligned")
    assert obs.span_totals().get("build.ax_plan", (0.0, 0))[1] == n0
