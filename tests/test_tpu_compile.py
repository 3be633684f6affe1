"""The TPU compiler's verdict on the main path, without a chip.

Compiles — never runs — the five Pallas kernels and one `calculate` of the
default aligned XLA path for a described TPU v5e chip, at the widths of
`chip_smoke.py`'s instance (5M sources x 10k destinations x 10 edges per
source).  Interpret-mode tests cannot see what Mosaic refuses (rank-1
blocks, in-kernel gathers, VMEM overruns) nor what the XLA TPU backend
makes of a layout; these compiles can, at a couple of seconds each.

The topology is described inside the fixture, never at import: only one
process at a time may load the TPU compiler's library, and every test
worker imports this file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import MatchingObjective
from repro.core.types import AxBucket, AxPlan, LPData, Slab
from repro.kernels import ax_reduce, dual_grad, proj

J, M = 10_000, 1
# (rows, width) of the instance's source slabs and destination buckets
SLABS = [(145_500, 4), (1_517_000, 8), (3_202_000, 16), (135_000, 32),
         (8, 64)]
BUCKETS = [(2, 16), (10, 64), (90, 128), (590, 256), (2_270, 512),
           (2_440, 1_024), (2_581, 2_048), (1_938, 4_096), (855, 8_192),
           (251, 16_384), (35, 32_768), (4, 65_536), (1, 262_144)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the cache but cannot be
    # read back without one: keep the cache out of it
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding):
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _slab_args(S, n, w):
    return (S((n, w, M)), S((n, w)), S((n, w), jnp.int32),
            S((n, w), jnp.bool_), S((n, w)), S((n,)), S((M, J)), S(()))


@pytest.mark.parametrize("n,w", SLABS[:4])
@pytest.mark.parametrize("kernel", ["proj_boxcut", "dual_x_slab",
                                    "dual_grad_slab"])
def test_slab_kernel_compiles(one_chip, kernel, n, w):
    S = _sds(one_chip)
    if kernel == "proj_boxcut":
        c = _compile(lambda v, ub, s, mask: proj.proj_boxcut(
            v, ub, s, mask, interpret=False),
            S((n, w)), S((n, w)), S((n,)), S((n, w), jnp.bool_))
    else:
        fn = getattr(dual_grad, kernel)
        c = _compile(lambda *a: fn(*a, interpret=False), *_slab_args(S, n, w))
    assert "tpu_custom_call" in c.as_text()


@pytest.mark.parametrize("r,w", [BUCKETS[0], BUCKETS[6], BUCKETS[-1]])
@pytest.mark.parametrize("kernel", ["ax_reduce_bucket_x", "ax_reduce_bucket"])
def test_ax_kernel_compiles(one_chip, kernel, r, w):
    S = _sds(one_chip)
    E = 68_000_000
    idx, mask = S((r, w), jnp.int32), S((r, w), jnp.bool_)
    if kernel == "ax_reduce_bucket_x":
        c = _compile(lambda x, a, i, mk: ax_reduce.ax_reduce_bucket_x(
            x, a, i, mk, interpret=False), S((E,)), S((r, w, M)), idx, mask)
    else:
        c = _compile(lambda g, i, mk: ax_reduce.ax_reduce_bucket(
            g, i, mk, interpret=False), S((E, M)), idx, mask)
    assert "tpu_custom_call" in c.as_text()


def test_aligned_calculate_compiles_and_fits(one_chip):
    """The default hot path (XLA, x-carry aligned Ax) at full size: no
    Mosaic kernel in it, it fits the chip's 16 GB, and its code stays
    small — a gather through an (n, w) index with a small w, or a
    row-major flatten of such a slab, compiles into code linear in n
    (about 95 MB and two minutes here before `dest_gather`/`edge_space`)."""
    S = _sds(one_chip)
    lp = LPData(
        slabs=tuple(Slab(a_vals=S((n, w, M)), c_vals=S((n, w)),
                         dest_idx=S((n, w), jnp.int32),
                         mask=S((n, w), jnp.bool_), ub=S((n, w)),
                         s=S((n,)), source_ids=S((n,), jnp.int32))
                    for n, w in SLABS),
        b=S((M, J)))
    plan = AxPlan(
        buckets=tuple(AxBucket(edge_idx=S((r, w), jnp.int32),
                               mask=S((r, w), jnp.bool_),
                               dest_ids=S((r,), jnp.int32),
                               a_dm=S((r, w, M))) for r, w in BUCKETS),
        inv_perm=S((J,), jnp.int32))

    def calculate(lp, plan, lam, gamma):
        return MatchingObjective(lp, ax_mode="aligned",
                                 ax_plan=plan).calculate(lam, gamma)

    c = _compile(calculate, lp, plan, S((M, J)), S(()))
    assert "tpu_custom_call" not in c.as_text()
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
    assert mem.generated_code_size_in_bytes < 48e6
