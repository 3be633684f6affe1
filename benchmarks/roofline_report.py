"""Aggregate the dry-run JSONs into the §Roofline table (EXPERIMENTS.md).

Reads benchmarks/results/dryrun/<mesh>/*.json (produced by
repro.launch.dryrun) and emits one row per (arch × shape × mesh) with the
three roofline terms, the dominant bottleneck, MODEL_FLOPS/HLO_FLOPs, and a
one-line "what would move the dominant term" note.
"""
from __future__ import annotations

import glob
import json
import os
from typing import Dict, List

RESULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "results", "dryrun")

# Published HBM bandwidth per chip, bytes/s, keyed by jax's device_kind,
# with the source of each entry.  A device missing here is an error, not a
# default: a roofline share against a guessed peak is not a measurement.
PEAK_BYTES_PER_S = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM2 at 819 GB/s
    "TPU v5 lite": (819e9, "Google Cloud documentation, TPU v5e"),
}


def bytes_bound(bytes_per_call: float, seconds_per_call: float,
                device_kind: str = None) -> Dict:
    """Achieved-vs-peak memory-bandwidth verdict for one timed kernel.

    `bytes_per_call` comes from the hlo_cost census of the compiled
    module; the peak is the published one for `device_kind` (default:
    the first device JAX reports), and a device kind missing from
    PEAK_BYTES_PER_S raises KeyError.  Returns the achieved bandwidth,
    the peak used, the fraction of peak, and the roofline floor (the
    wall-clock the transfer alone would take at peak) — the fields
    benchmarks/run.py attaches to kernel rows.

    Convention caveat: hlo_cost counts operand+result bytes per
    instruction execution (trip-count aware), i.e. an UPPER bound on
    main-memory traffic — a value re-read from cache is counted each
    time.  `fraction_of_peak` > 1 therefore means the census traffic is
    being served from cache, not that the hardware beat its roofline;
    values << 1 mean the kernel genuinely has bandwidth headroom.
    """
    if device_kind is None:
        import jax
        device_kind = jax.devices()[0].device_kind
    if device_kind not in PEAK_BYTES_PER_S:
        raise KeyError(
            f"no published HBM bandwidth on record for device kind "
            f"{device_kind!r}; add it to PEAK_BYTES_PER_S with its source")
    peak, source = PEAK_BYTES_PER_S[device_kind]
    achieved = bytes_per_call / seconds_per_call if seconds_per_call else 0.0
    return {
        "bytes_per_call": float(bytes_per_call),
        "achieved_bytes_per_s": achieved,
        "device_kind": device_kind,
        "peak_bytes_per_s": peak,
        "peak_source": source,
        "fraction_of_peak": achieved / peak,
        "memory_bound_floor_s": bytes_per_call / peak,
    }


_ADVICE = {
    "compute": ("cut dead FLOPs: gather-based MoE dispatch, pad-free head "
                "sharding, block-sparse causal attention"),
    "memory": ("raise arithmetic intensity: fuse projections, wider xent "
               "chunks, bf16 optimizer reads"),
    "collective": ("cheaper collective schedule: fewer all-gathers via "
                   "2D-sharded matmuls, overlap psum with trailing compute, "
                   "bf16 gradient compression"),
}


def load_cells(mesh: str = "single", tag: str = "") -> List[Dict]:
    """Baseline cells only by default: a baseline file is named exactly
    <arch>__<shape>.json; hillclimb variants carry a suffix tag."""
    out = []
    for path in sorted(glob.glob(os.path.join(RESULTS, mesh, "*.json"))):
        base = os.path.basename(path)
        with open(path) as f:
            d = json.load(f)
        canonical = f"{d.get('arch')}__{d.get('shape')}.json"
        if tag:
            if tag in base:
                out.append(d)
        elif base == canonical:
            out.append(d)
    return out


def table_rows(mesh: str = "single") -> List[Dict]:
    rows = []
    for cell in load_cells(mesh):
        name = f"{cell.get('arch')}/{cell.get('shape')}"
        if cell["status"] == "SKIP":
            rows.append({"cell": name, "status": "SKIP",
                         "reason": cell.get("reason", "")})
            continue
        if cell["status"] == "FAIL":
            rows.append({"cell": name, "status": "FAIL",
                         "reason": cell.get("error", "")[:100]})
            continue
        r = cell["roofline"]
        rows.append({
            "cell": name, "status": "OK",
            "t_compute_s": r["t_compute_s"],
            "t_memory_s": r["t_memory_s"],
            "t_collective_s": r["t_collective_s"],
            "dominant": r["dominant"],
            "bound_step_s": r["bound_step_time_s"],
            "model_flops": cell.get("model_flops", {}).get("model_flops"),
            "useful_ratio": cell.get("useful_compute_ratio"),
            "hbm_gb": cell.get("hbm_per_device_gb"),
            "advice": _ADVICE[r["dominant"]],
        })
    return rows


def run(quick: bool = False):
    out = []
    for mesh in ("single", "multipod"):
        if not os.path.isdir(os.path.join(RESULTS, mesh)):
            continue
        for row in table_rows(mesh):
            if row["status"] != "OK":
                out.append({"name": f"roofline/{mesh}/{row['cell']}",
                            "us_per_call": 0.0,
                            "derived": {"status": row["status"],
                                        "reason": row.get("reason", "")}})
                continue
            out.append({
                "name": f"roofline/{mesh}/{row['cell']}",
                "us_per_call": row["bound_step_s"] * 1e6,
                "derived": {
                    "t_compute_s": row["t_compute_s"],
                    "t_memory_s": row["t_memory_s"],
                    "t_collective_s": row["t_collective_s"],
                    "dominant": row["dominant"],
                    "useful_compute_ratio": row["useful_ratio"],
                    "hbm_per_device_gb": row["hbm_gb"],
                },
            })
    return out


def markdown_table(mesh: str = "single") -> str:
    lines = [
        "| cell | t_comp (s) | t_mem (s) | t_coll (s) | dominant | "
        "MODEL/HLO | HBM/dev (GB) |",
        "|---|---|---|---|---|---|---|",
    ]
    for r in table_rows(mesh):
        if r["status"] == "SKIP":
            lines.append(f"| {r['cell']} | — | — | — | SKIP | — | — |")
        elif r["status"] == "FAIL":
            lines.append(f"| {r['cell']} | — | — | — | **FAIL** | — | — |")
        else:
            lines.append(
                f"| {r['cell']} | {r['t_compute_s']:.4f} | "
                f"{r['t_memory_s']:.4f} | {r['t_collective_s']:.4f} | "
                f"{r['dominant']} | {r['useful_ratio']:.2f} | "
                f"{r['hbm_gb']:.2f} |")
    return "\n".join(lines)


if __name__ == "__main__":
    import sys
    print(markdown_table(sys.argv[1] if len(sys.argv) > 1 else "single"))
