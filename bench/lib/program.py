"""The system under test, built the way its launcher builds it.

Everything the benchmark times lives in the program (`src/repro`); this
module only assembles it from the benchmark's edge lists and a
configuration's `solver` settings, which mirror the launcher's
`--adaptive-continuation --tol-rel-dual 1e-6 --tol-infeas 1.0
--check-every 25` solve.
"""
from __future__ import annotations

import time


class CompileClock:
    """Sums the backend compile durations JAX reports through
    `jax.monitoring` (persistent-cache hits included, as the short
    durations they are)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.seconds += duration
            self.count += 1


def instance_spec(gen_params: dict):
    """The program's `InstanceSpec` for the slab packer and its rhs."""
    from repro.core import InstanceSpec
    return InstanceSpec(
        num_sources=gen_params["num_sources"],
        num_destinations=gen_params["num_destinations"],
        avg_nnz_per_row=gen_params["avg_nnz_per_row"],
        num_families=gen_params["num_families"],
        c_max=gen_params["c_max"], rho_low=gen_params["rho_low"],
        rho_high=gen_params["rho_high"], rhs_eps=gen_params["rhs_eps"],
        budget_s=gen_params["budget_s"], box_ub=gen_params["box_ub"],
        min_width=gen_params["min_width"],
        seed=gen_params["instance_seed"])


def build_lp(edges, gen_params: dict):
    """`pack_slabs` on the edge lists, moved to the device, then the
    launcher's row normalisation."""
    import jax
    import jax.numpy as jnp
    from repro.core import precondition
    from repro.core.instance import pack_slabs
    lp = pack_slabs(edges.src, edges.dst, edges.value, edges.a,
                    instance_spec(gen_params))
    lp = jax.tree.map(jnp.asarray, lp)
    return precondition(lp, row_norm=True)[0]


def solve_objective(lp):
    """The objective `solve_distributed` builds on a one-device mesh:
    `place_lp` plus the x-carry aligned AxPlan."""
    import jax
    from repro.core.distributed import (DistributedMatchingObjective,
                                        place_lp)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1, 1), ("data", "model"), jax.devices()[:1])
    axes = tuple(mesh.axis_names)
    return DistributedMatchingObjective(
        lp=place_lp(lp, mesh, axes), mesh=mesh, source_axes=axes,
        ax_mode="aligned")


def maximizer(solver: dict):
    """The launcher's `SolveConfig` and `StoppingCriteria`."""
    from repro.core import Maximizer, SolveConfig, StoppingCriteria
    cfg = SolveConfig(
        iterations=solver["max_iterations"], gamma=solver["gamma"],
        gamma_init=solver["gamma_init_factor"] * solver["gamma"],
        adaptive_continuation=solver["adaptive_continuation"],
        max_step=solver["max_step"], initial_step=solver["initial_step"],
        projection=solver["projection"])
    crit = StoppingCriteria(tol_rel_dual=solver["tol_rel_dual"],
                            tol_infeas=solver["tol_infeas"],
                            check_every=solver["check_every"])
    return Maximizer(cfg, criteria=crit)


def record_shape(run, edges) -> None:
    """The instance's size, for the readers that count bytes."""
    run.readings.update(edges=edges.num_edges, sources=edges.num_sources,
                        destinations=edges.num_destinations,
                        families=int(edges.a.shape[0]))


def build_solve(run):
    """Generate the cell's instance and build the program's solve path.
    Records `gen_s` (the benchmark's generator) and `build_s` (the
    program's packing, preconditioning, placement and AxPlan build).
    Returns (edges, objective, maximizer)."""
    import jax
    from . import gen
    p = run.config["generator"]
    t = time.perf_counter()
    edges = gen.generate(p, run.seed, run.traffic["relabel_sources"])
    run.readings["gen_s"] = time.perf_counter() - t
    record_shape(run, edges)
    t = time.perf_counter()
    obj = solve_objective(build_lp(edges, p))
    jax.block_until_ready((obj.lp, obj._plan))
    run.readings["build_s"] = time.perf_counter() - t
    return edges, obj, maximizer(run.config["solver"])
