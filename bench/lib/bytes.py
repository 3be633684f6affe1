"""The least bytes one dual-ascent iteration must move through HBM.

Every iteration reads every real edge's constraint weights (m x 4 B), cost,
destination and upper bound (4 B each), every source's budget (4 B), and
lambda, b and the gradient once (m x J x 4 B each).  The count depends on
the LP alone: not on slab padding, on the AxPlan, or on whether Pallas or
XLA runs the sweep.  It is a lower bound on the traffic, so the share of
the roofline it gives cannot pass 100%.
"""
from __future__ import annotations


def least_bytes_per_iteration(edges: int, sources: int, destinations: int,
                              families: int) -> int:
    per_edge = 4 * families + 4 + 4 + 4
    return edges * per_edge + 4 * sources + 3 * 4 * families * destinations
