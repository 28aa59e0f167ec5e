"""§Roofline: three-term roofline per (arch × shape × mesh) from the
dry-run's compiled artifacts.

  compute term    = per-device HLO FLOPs / peak FLOP/s
  memory term     = per-device HLO bytes / HBM bandwidth
  collective term = per-device collective bytes / ICI link bandwidth

Hardware model: the published peaks of one TPU v5e chip
(``repro.tuning.hardware_model("TPU v5 lite")``: 197 TFLOP/s bf16, 819 GB/s
HBM, 50 GB/s per ICI link, one link per collective hop).  The records are
compiled on host CPU devices, so these terms are a model of the chip, never
a measurement on it.

Also reported: MODEL_FLOPS / HLO_FLOPs ("useful fraction" — catches remat
and redundancy waste) and the dominant bottleneck term.
"""
from __future__ import annotations

import glob
import json
import os

from repro.tuning import hardware_model

_HW = hardware_model("TPU v5 lite")
PEAK_FLOPS = _HW["peak_flops"]
HBM_BW = _HW["hbm_bw"]
ICI_BW = _HW["ici_bw"]

RESULTS_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "results", "dryrun"
)


def load_records(results_dir=RESULTS_DIR):
    recs = []
    for path in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        with open(path) as fh:
            recs.append(json.load(fh))
    return recs


def roofline_terms(rec):
    if not rec.get("ok"):
        return None
    nd = rec["n_devices"]
    compute_s = rec["flops_per_device"] / PEAK_FLOPS
    memory_s = rec["bytes_per_device"] / HBM_BW
    coll_s = rec["collective_bytes"]["total"] / ICI_BW
    dom = max(
        [("compute", compute_s), ("memory", memory_s), ("collective", coll_s)],
        key=lambda kv: kv[1],
    )[0]
    total_flops = rec["flops_per_device"] * nd
    useful = rec["model_flops"] / total_flops if total_flops > 0 else 0.0
    # roofline fraction: compute time / critical-path bound (max of terms)
    bound = max(compute_s, memory_s, coll_s, 1e-30)
    frac = compute_s / bound
    return dict(
        arch=rec["arch"],
        shape=rec["shape"],
        mesh=rec["mesh"],
        kind=rec["kind"],
        compute_s=compute_s,
        memory_s=memory_s,
        collective_s=coll_s,
        dominant=dom,
        useful_flops_ratio=useful,
        roofline_fraction=frac,
        peak_gb=(rec["memory"]["peak_bytes"] or 0) / 1e9,
    )


def table(records=None, mesh_filter="single_pod_16x16"):
    records = records or load_records()
    rows = []
    for rec in records:
        if mesh_filter and rec.get("mesh") != mesh_filter:
            continue
        t = roofline_terms(rec)
        if t:
            rows.append(t)
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    return rows


def markdown(rows):
    hdr = (
        "| arch | shape | compute s | memory s | collective s | dominant | "
        "useful flops | roofline frac | peak GB/dev |\n"
        "|---|---|---|---|---|---|---|---|---|"
    )
    lines = [hdr]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} | "
            f"{r['memory_s']:.3e} | {r['collective_s']:.3e} | {r['dominant']} | "
            f"{r['useful_flops_ratio']:.2f} | {r['roofline_fraction']:.2f} | "
            f"{r['peak_gb']:.2f} |"
        )
    return "\n".join(lines)


def run():
    rows = table()
    out = []
    for r in rows:
        out.append(
            dict(
                name=f"roofline_{r['arch']}_{r['shape']}",
                us_per_call=r["compute_s"] * 1e6,
                derived=(
                    f"dominant={r['dominant']} frac={r['roofline_fraction']:.2f} "
                    f"useful={r['useful_flops_ratio']:.2f}"
                ),
            )
        )
    return out


if __name__ == "__main__":
    print(markdown(table()))
