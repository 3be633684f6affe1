"""The numbers `correct` compares, program against reference.

Each number is a worst-case gap scaled so that it reads the same at every
size; each has a limit of its own in the cell's workload file.
"""
from __future__ import annotations

import numpy as np

from . import reference


def point_of(result) -> dict:
    """What the timed solve path produced at its last evaluation: the point
    y it evaluated, and the gradient, g, c.x and gamma of that iteration."""
    st = result.final_state
    stats = result.stats
    return {"y": np.asarray(st.y_prev, np.float64),
            "grad": np.asarray(st.grad_prev, np.float64),
            "g": float(np.asarray(stats.dual_obj)[-1]),
            "cx": float(np.asarray(stats.primal_obj)[-1]),
            "gamma": float(np.asarray(stats.gamma)[-1])}


def solve_numbers(prog: dict, ref: reference.Evaluation) -> dict:
    """g, Ax, c.x and the duality gap lam.(b - Ax) at the program's point."""
    g_scale = max(1.0, abs(ref.g))
    return {
        "g_rel_err": abs(prog["g"] - ref.g) / g_scale,
        "ax_err": float(np.max(np.abs(prog["grad"] + ref.b - ref.ax)
                               / ref.scale)),
        "primal_rel_err": abs(prog["cx"] - ref.cx) / max(1.0, abs(ref.cx)),
        "gap_rel_err": abs(float(np.vdot(prog["y"], prog["grad"]))
                           - float(np.vdot(prog["y"], ref.grad))) / g_scale,
    }


def _evaluate(edges, point, dtype):
    return reference.evaluate(edges, reference.precondition(edges),
                              point["y"], point["gamma"], dtype)


def _float32():
    import jax.numpy as jnp
    return jnp.float32


def _bfloat16():
    import jax.numpy as jnp
    return jnp.bfloat16


def judge_solve(run, evidence) -> None:
    """Check the program's last evaluation against the reference."""
    edges, point = evidence
    ref = _evaluate(edges, point, _float32())
    run.readings["reference_infeas"] = ref.infeas
    for name, value in solve_numbers(point, ref).items():
        run.check(name, value)


def control_solve(evidence) -> dict:
    """The numbers the reference computed in bfloat16 reads at the same
    point, in the program's place."""
    edges, point = evidence
    ref = _evaluate(edges, point, _float32())
    low = _evaluate(edges, point, _bfloat16())
    return solve_numbers({"y": point["y"], "grad": low.grad, "g": low.g,
                          "cx": low.cx}, ref)
