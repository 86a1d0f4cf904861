"""§Roofline: three-term roofline per (arch × shape) from the dry-run JSONs.

  compute term    = HLO_FLOPs(per-device) / peak_FLOP/s
  memory term     = HLO_bytes(per-device) / HBM_bw
  collective term = wire_bytes(per-device) / (n_links × link_bw)

All terms are per-chip seconds (cost_analysis reports per-device numbers for
the SPMD module). Dominant term = bottleneck. MODEL_FLOPS/HLO_FLOPs ratios
use the 6·N·D (dense) / 6·N_active·D (MoE) convention recorded in the cell
meta at dry-run time. Outputs results/roofline.json and a markdown table.
"""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List

from repro.launch.mesh import device_peaks

DRYRUN_DIR = os.path.join(os.path.dirname(__file__), "..", "results", "dryrun")
#: the chip the dry-run cells are compiled for (a described v5e topology)
TARGET_KIND = "TPU v5 lite"


def analyse(rec: Dict) -> Dict:
    n_chips = rec["n_chips"]
    flops = rec["hlo_flops_per_device"]
    byts = rec["hlo_bytes_per_device"]
    wire = rec["collectives"]["per_device_wire_bytes"]
    peaks = device_peaks(rec.get("device_kind", TARGET_KIND))
    t_compute = flops / peaks["bf16_flops"]
    t_memory = byts / peaks["hbm_bw"]
    t_coll = wire / (peaks["ici_links"] * peaks["ici_link_bw"])
    terms = {"compute": t_compute, "memory": t_memory, "collective": t_coll}
    dominant = max(terms, key=terms.get)
    bound = max(terms.values())
    model_flops = rec.get("meta", {}).get("model_flops", 0)
    model_per_dev = model_flops / n_chips if n_chips else 0.0
    useful = model_per_dev / flops if flops else 0.0
    # roofline fraction: useful-compute time / achievable step time
    # (perfect overlap assumption: step time = max of the three terms)
    frac = (model_per_dev / peaks["bf16_flops"]) / bound if bound > 0 else 0.0
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        "kind": rec.get("kind"),
        "t_compute_s": t_compute, "t_memory_s": t_memory,
        "t_collective_s": t_coll, "dominant": dominant,
        "useful_flops_ratio": useful, "roofline_fraction": frac,
        "model_flops_per_dev": model_per_dev,
    }


def fix_hint(row: Dict) -> str:
    d = row["dominant"]
    if d == "compute":
        if row["useful_flops_ratio"] < 0.5:
            return ("compute-bound but <50% of HLO FLOPs are model FLOPs — "
                    "cut remat/recompute or fuse redundant ops")
        return "compute-bound near useful peak — increase arithmetic intensity only via algorithmic change"
    if d == "memory":
        return ("memory-bound — raise arithmetic intensity: fuse elementwise "
                "chains, bf16/fp8 activations, or larger per-chip tiles")
    return ("collective-bound — reshard to cut wire bytes (e.g. different "
            "batch/model split), overlap collectives with compute, or "
            "compress gradients")


def markdown_table(rows: List[Dict]) -> str:
    hdr = ("| arch | shape | mesh | compute (s) | memory (s) | collective (s) "
           "| dominant | useful/HLO | roofline frac |\n"
           "|---|---|---|---|---|---|---|---|---|\n")
    body = ""
    for r in rows:
        body += (f"| {r['arch']} | {r['shape']} | {r['mesh']} "
                 f"| {r['t_compute_s']:.3e} | {r['t_memory_s']:.3e} "
                 f"| {r['t_collective_s']:.3e} | **{r['dominant']}** "
                 f"| {r['useful_flops_ratio']:.2f} "
                 f"| {r['roofline_fraction']:.2f} |\n")
    return hdr + body


def run(mesh_filter: str = "16x16") -> List[Dict]:
    rows = []
    for path in sorted(glob.glob(os.path.join(DRYRUN_DIR, "*.json"))):
        with open(path) as f:
            rec = json.load(f)
        if rec.get("status") != "ok":
            continue
        if mesh_filter and rec["mesh"] != mesh_filter:
            continue
        row = analyse(rec)
        row["fix"] = fix_hint(row)
        rows.append(row)
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    out = os.path.join(os.path.dirname(DRYRUN_DIR), "roofline.json")
    with open(out, "w") as f:
        json.dump(rows, f, indent=1)
    return rows


def main():
    rows = run()
    print(markdown_table(rows))
    for r in rows:
        print(f"{r['arch']} × {r['shape']}: {r['fix']}")
    if rows:
        worst = min(rows, key=lambda r: r["roofline_fraction"])
        coll = max(rows, key=lambda r: r["t_collective_s"] /
                   max(r["t_compute_s"], 1e-30))
        print(f"\nworst roofline fraction: {worst['arch']} × {worst['shape']} "
              f"({worst['roofline_fraction']:.3f})")
        print(f"most collective-bound: {coll['arch']} × {coll['shape']}")


if __name__ == "__main__":
    main()
