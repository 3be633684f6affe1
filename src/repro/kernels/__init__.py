"""Pallas TPU kernels for the DuaLip hot path (paper §6).

  proj.py       batched box-cut projection via τ-bisection
  dual_grad.py  fused x*(λ) + per-edge gradient values + local scalars
  ax_reduce.py  constraint-aligned gather-reduce for Ax (scatter-free)
  ops.py        public wrappers (Mosaic on TPU, interpret mode elsewhere)
  ref.py        pure-jnp oracles (ground truth for tests)
"""
from . import ops, ref  # noqa: F401
