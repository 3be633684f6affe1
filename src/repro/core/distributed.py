"""Distributed dual ascent — the paper's §6 pattern, SPMD-native (DESIGN.md §2).

Paper (PyTorch/NCCL):                      This repo (JAX/TPU):
  columns of 𝒯 partitioned per GPU    →     slab rows sharded over ("pod","data")
  λ, b replicated on every device     →     λ, b replicated (or λ sharded on "model")
  local grad contribution per rank    →     shard-local slab_contribution
  reduce(SUM, rank0) of ∇g            →     psum over ("pod","data")
  rank-0 AGD update                   →     replicated AGD update (identical math)
  2× broadcast(λ1, λ2)                →     — (replicated update ⇒ no broadcast)

Per-iteration communication volume is ONE all-reduce of |λ| = m·J floats plus
two scalars — independent of nnz and of the per-device source split, matching
(and improving on) the paper's 1 reduce + 2 broadcasts.

Beyond-paper option (`lambda_sharding="model"`): for m·J too large to
replicate, λ lives sharded over the "model" axis; each step all-gathers λ
before the edge pass and reduce-scatters the gradient after it.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.obs import note_ax_path, spanned

from . import objectives
from .maximizer import _infeas_scale, maximize
from .types import (AxPlan, HealthConfig, LPData, Slab, SolveConfig,
                    SolveResult, SolveState, StoppingCriteria, edge_space)


def pad_slab_rows(slab: Slab, multiple: int) -> Slab:
    """Pad a slab's row count to a multiple (mask=False rows are inert)."""
    n = slab.n
    n_pad = -(-n // multiple) * multiple
    if n_pad == n:
        return slab
    extra = n_pad - n

    def pad(a, fill=0):
        cfg = [(0, extra)] + [(0, 0)] * (a.ndim - 1)
        return jnp.pad(a, cfg, constant_values=fill)

    return Slab(
        a_vals=pad(slab.a_vals), c_vals=pad(slab.c_vals),
        dest_idx=pad(slab.dest_idx), mask=pad(slab.mask),
        ub=pad(slab.ub), s=pad(slab.s, 1.0), source_ids=pad(slab.source_ids, -1),
    )


def pad_for_sharding(lp: LPData, num_shards: int) -> LPData:
    return LPData(
        slabs=tuple(pad_slab_rows(s, num_shards) for s in lp.slabs),
        b=lp.b,
    )


@spanned("build.place")
def place_lp(lp: LPData, mesh: Mesh, source_axes: Tuple[str, ...],
             lambda_axis: Optional[str] = None) -> LPData:
    """device_put the LP with slab rows sharded over the source axes."""
    n_shards = int(np.prod([mesh.shape[a] for a in source_axes]))
    lp = pad_for_sharding(lp, n_shards)
    row = NamedSharding(mesh, P(source_axes))
    b_sharding = (NamedSharding(mesh, P(None, lambda_axis)) if lambda_axis
                  else NamedSharding(mesh, P()))
    slabs = tuple(
        Slab(*(jax.device_put(x, row) for x in s)) for s in lp.slabs)
    return LPData(slabs=slabs, b=jax.device_put(lp.b, b_sharding))


@dataclasses.dataclass
class DistributedMatchingObjective:
    """ObjectiveFunction whose calculate() runs under shard_map.

    The slab pass is fully local per shard; the ONLY communication is the
    psum of (Ax, cᵀx, ‖x‖²) over the source axes — the paper's "communicate
    only the duals" property, stated in code.
    """

    lp: LPData                      # already placed via place_lp
    mesh: Mesh
    source_axes: Tuple[str, ...]
    proj_kind: str = "boxcut"
    proj_iters: int = 40
    use_pallas: bool = False
    lambda_axis: Optional[str] = None   # beyond-paper λ sharding
    # "scatter" (paper-faithful segment-sum), "aligned" (the x-carry sweep,
    # no gvals materialization — DESIGN.md §3), or "aligned_gvals" (the
    # index-only aligned gather-reduce over materialized gvals).  "aligned"
    # reduces each shard's Ax on the dual tile where
    # `objectives.select_ax_path` picks it, else through the value-carrying
    # AxPlan.  A plan is built once per shard over its local slab-edge
    # space, and its leading shard axis is partitioned over source_axes —
    # row-wise over the λ axis too when lambda_sharding="model" makes it
    # one.
    ax_mode: str = "scatter"
    # the x-carry sweep's Ax path ("dual_tile" or "ax_plan"); None for the
    # gvals modes
    ax_path: Optional[str] = dataclasses.field(default=None, init=False)
    _plan: Optional[AxPlan] = dataclasses.field(
        default=None, init=False, repr=False)

    def __post_init__(self):
        if self.ax_mode not in ("scatter", "aligned", "aligned_gvals"):
            raise ValueError(
                f"distributed ax_mode is 'scatter', 'aligned' or "
                f"'aligned_gvals', got {self.ax_mode!r}")
        if self.ax_mode == "aligned":
            self.ax_path = objectives.select_ax_path(
                objectives.platform_of(list(self.mesh.devices.flat)),
                self.lambda_axis, self.lp.m, self.lp.num_destinations)
            note_ax_path(self.ax_path)
        if self.ax_mode == "aligned_gvals" or self.ax_path == "ax_plan":
            from .instance import build_sharded_ax_plan
            n_shards = int(np.prod([self.mesh.shape[a]
                                    for a in self.source_axes]))
            plan = build_sharded_ax_plan(
                self.lp, n_shards, carry_values=(self.ax_mode == "aligned"))
            row = NamedSharding(self.mesh, P(self.source_axes))
            self._plan = jax.tree.map(
                lambda a: jax.device_put(jnp.asarray(a), row), plan)

    @property
    def dual_shape(self):
        return (self.lp.m, self.lp.num_destinations)

    def primal(self, lam: jax.Array, gamma: jax.Array):
        """Recover the (padded) primal x*(λ) slab by slab.

        The latent gap this closes: the distributed objective previously
        had NO primal surface at all, so duals solved distributed could
        not be turned into decisions without rebuilding a single-device
        objective by hand (the same bug class as the
        GlobalCountObjective.primal misindex fixed earlier — a dual layout
        with no matching primal path).  x*(λ) is row-local, so no
        collective is needed: each shard projects its own slab rows; rows
        added by `pad_for_sharding` come back fully masked (source_id −1).
        λ must be full: in λ-sharded mode it is re-replicated first.
        """
        if self.lambda_axis is not None:
            lam = jax.device_put(
                jax.device_get(lam), NamedSharding(self.mesh, P()))
        return [
            objectives.slab_xstar(s, lam, gamma, self.proj_kind,
                                  self.proj_iters, self.use_pallas)
            for s in self.lp.slabs
        ]

    def calculate(self, lam: jax.Array, gamma: jax.Array):
        source_axes = self.source_axes
        lam_axis = self.lambda_axis
        kind, iters, pallas = self.proj_kind, self.proj_iters, self.use_pallas
        J = self.lp.num_destinations
        # slab rows are sharded over source_axes; when λ is sharded on
        # lam_axis, that axis must also be a source axis (every device owns a
        # distinct row block — no replicated compute anywhere).
        if lam_axis is not None:
            assert lam_axis in source_axes, (
                "λ-sharded mode requires the λ axis to also partition "
                "sources; pass source_axes containing lambda_axis")
        other_axes = tuple(a for a in source_axes if a != lam_axis)

        ax_mode, ax_path = self.ax_mode, self.ax_path
        row_spec = P(source_axes)
        slab_specs = tuple(Slab(*(row_spec,) * 7) for _ in self.lp.slabs)
        b_spec = P(None, lam_axis) if lam_axis else P()
        lam_spec = P(None, lam_axis) if lam_axis else P()

        def local_core(slabs, b, lam, gamma, plan):
            if lam_axis is not None:
                # beyond-paper: λ lives sharded on lam_axis; gather it for
                # the edge pass, reduce-scatter the gradient back.
                lam_full = jax.lax.all_gather(
                    lam, lam_axis, axis=1, tiled=True)
            else:
                lam_full = lam
            if ax_mode == "aligned":
                # shard-local x-carry reduce: only x is dynamic; the slabs
                # (dual tile) or the plan's a_dm carry the static weights
                from repro.kernels import ops as kops
                parts, c_x, x_sq = [], jnp.zeros((), lam_full.dtype), \
                    jnp.zeros((), lam_full.dtype)
                for slab in slabs:
                    x, c_s, sq_s = objectives.slab_xcarry(
                        slab, lam_full, gamma, kind, iters, pallas)
                    parts.append(x)
                    c_x, x_sq = c_x + c_s, x_sq + sq_s
                with jax.named_scope(objectives.AX):
                    if ax_path == "dual_tile":
                        ax = kops.ax_dual_tile(slabs, parts, J).astype(
                            lam_full.dtype)
                    else:
                        ax = kops.ax_aligned_x(
                            jax.tree.map(lambda a: a[0], plan),
                            jnp.concatenate([edge_space(x) for x in parts]),
                            use_pallas=pallas, out_dtype=lam_full.dtype)
            elif ax_mode == "aligned_gvals":
                # shard-local scatter-free reduce over materialized gvals
                from repro.kernels import ops as kops
                parts, c_x, x_sq = [], jnp.zeros((), lam_full.dtype), \
                    jnp.zeros((), lam_full.dtype)
                for slab in slabs:
                    _, gvals, c_s, sq_s = objectives.slab_xgvals(
                        slab, lam_full, gamma, kind, iters, pallas)
                    parts.append(edge_space(gvals))
                    c_x, x_sq = c_x + c_s, x_sq + sq_s
                local_plan = jax.tree.map(lambda a: a[0], plan)
                with jax.named_scope(objectives.AX):
                    ax = kops.ax_aligned(local_plan,
                                         jnp.concatenate(parts, axis=0),
                                         use_pallas=pallas,
                                         out_dtype=lam_full.dtype)
            else:
                ax = jnp.zeros((lam_full.shape[0], J), lam_full.dtype)
                c_x = jnp.zeros((), lam_full.dtype)
                x_sq = jnp.zeros((), lam_full.dtype)
                for slab in slabs:
                    ax_s, c_s, sq_s = objectives.slab_contribution(
                        slab, lam_full, gamma, J, kind, iters, pallas)
                    ax, c_x, x_sq = ax + ax_s, c_x + c_s, x_sq + sq_s
            with jax.named_scope(objectives.COLLECTIVE):
                # the ONE collective round of the paper's iteration:
                c_x = jax.lax.psum(c_x, source_axes)
                x_sq = jax.lax.psum(x_sq, source_axes)
                if lam_axis is not None:
                    # sum row contributions across lam_axis while
                    # scattering J
                    ax = jax.lax.psum_scatter(
                        ax, lam_axis, scatter_dimension=1, tiled=True)
                    if other_axes:
                        ax = jax.lax.psum(ax, other_axes)
                else:
                    ax = jax.lax.psum(ax, source_axes)
                grad = ax - b
                g_local = jnp.vdot(lam, grad)
                if lam_axis is not None:
                    g_local = jax.lax.psum(g_local, lam_axis)
                g = c_x + 0.5 * gamma * x_sq + g_local
                sq_pos = jnp.sum(jnp.maximum(grad, 0.0) ** 2)
                if lam_axis is not None:
                    sq_pos = jax.lax.psum(sq_pos, lam_axis)
                infeas = jnp.sqrt(sq_pos)
            aux = objectives.ObjectiveAux(primal_obj=c_x, x_sq=x_sq, ax=ax,
                                          infeas=infeas)
            return g, grad, aux

        out_aux_spec = objectives.ObjectiveAux(
            primal_obj=P(), x_sq=P(), ax=P(None, lam_axis) if lam_axis else P(),
            infeas=P())
        out_specs = (P(), lam_spec, out_aux_spec)
        if self._plan is not None:
            plan_specs = jax.tree.map(lambda _: row_spec, self._plan)

            def local(slabs, b, plan, lam, gamma):
                return local_core(slabs, b, lam, gamma, plan)

            fn = jax.shard_map(
                local, mesh=self.mesh,
                in_specs=(slab_specs, b_spec, plan_specs, lam_spec, P()),
                out_specs=out_specs, check_vma=False,
            )
            return fn(self.lp.slabs, self.lp.b, self._plan, lam, gamma)

        def local(slabs, b, lam, gamma):
            return local_core(slabs, b, lam, gamma, None)

        fn = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(slab_specs, b_spec, lam_spec, P()),
            out_specs=out_specs, check_vma=False,
        )
        return fn(self.lp.slabs, self.lp.b, lam, gamma)


def solve_distributed(
    lp: LPData,
    config: SolveConfig,
    mesh: Mesh,
    source_axes: Optional[Tuple[str, ...]] = None,
    lambda_axis: Optional[str] = None,
    algorithm: str = "agd",
    lam0: Optional[jax.Array] = None,
    ax_mode: str = "scatter",
    criteria: Optional[StoppingCriteria] = None,
    diagnostics_fn=None,
    health: Optional[HealthConfig] = None,
    checkpoint_fn=None,
    preempt_fn=None,
    initial_state: Optional[SolveState] = None,
    resume_meta: Optional[dict] = None,
    telemetry=None,
    profiler=None,
    sampler=None,
) -> SolveResult:
    """End-to-end distributed solve: place data, build objective, maximize.

    `source_axes` defaults to ALL mesh axes (the paper partitions sources
    over every GPU).  The AGD update itself runs replicated (or λ-sharded):
    identical on every device, so no broadcast step exists at all.

    Routes through the same chunked SolveEngine as the single-device paths
    (DESIGN.md §4): with `criteria` set, the host controller evaluates the
    stopping rules at chunk boundaries, and the only data crossing the
    host/device boundary per chunk are the per-iteration scalar stats —
    λ and the rest of the solver state stay device-resident (sharded or
    replicated) for the whole solve.
    """
    if source_axes is None:
        source_axes = tuple(mesh.axis_names)
    lp = place_lp(lp, mesh, source_axes, lambda_axis)
    obj = DistributedMatchingObjective(
        lp=lp, mesh=mesh, source_axes=source_axes,
        proj_kind=config.projection, use_pallas=config.use_pallas,
        lambda_axis=lambda_axis, ax_mode=ax_mode)
    if lam0 is None:
        lam0 = jnp.zeros(obj.dual_shape, jnp.float32)
    lam_sharding = (NamedSharding(mesh, P(None, lambda_axis)) if lambda_axis
                    else NamedSharding(mesh, P()))
    lam0 = jax.device_put(lam0, lam_sharding)
    return maximize(obj.calculate, lam0, config, algorithm,
                    criteria=criteria, diagnostics_fn=diagnostics_fn,
                    infeas_scale=_infeas_scale(obj, criteria),
                    health=health, checkpoint_fn=checkpoint_fn,
                    preempt_fn=preempt_fn, initial_state=initial_state,
                    resume_meta=resume_meta, telemetry=telemetry,
                    profiler=profiler, sampler=sampler)
