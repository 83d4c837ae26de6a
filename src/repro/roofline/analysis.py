"""Roofline synthesis: dry-run artifacts -> three-term roofline table.

Terms (per device, per step; constants read from the v5e link profile —
core/linkmodel.py is the single link model of the tree):
  compute    = dot_flops / peak_flops        (bf16 peak, 197e12 on v5e)
  memory     = hbm_bytes / hbm_bw            (819e9 on v5e)
  collective = ici_wire / intra_bw + dci_wire / inter_bw
               (per-link ICI 50e9; DCI modeled at 1/8 ICI per pod-boundary
                link — assumption recorded in the profile and
                EXPERIMENTS.md)

MODEL_FLOPS uses 6·N·D for training (N = active params for MoE) and 2·N·D
for inference shapes, divided across all chips; the ratio MODEL/HLO exposes
remat + padded-head waste.
"""

from __future__ import annotations

import json
import pathlib

from repro.core.linkmodel import V5E

PEAK_BF16 = V5E.peak_flops
HBM_BW = V5E.hbm_bw
ICI_BW = V5E.intra.bandwidth
DCI_BW = V5E.inter.bandwidth

ART = pathlib.Path(__file__).resolve().parents[3] / "artifacts"


def model_flops_per_device(rec: dict) -> float:
    n_chips = 512 if rec["mesh"] == "2x16x16" else 256
    n_active = rec["active_params"]
    tokens = rec["seq"] * rec["global_batch"] if rec["kind"] != "decode" \
        else rec["global_batch"]
    mult = 6.0 if rec["kind"] == "train" else 2.0
    return mult * n_active * tokens / n_chips


def roofline_terms(rec: dict) -> dict:
    s = rec["stats"]
    compute = s["dot_flops"] / PEAK_BF16
    memory = s["hbm_bytes"] / HBM_BW
    ici = s["ici_wire_bytes"] / ICI_BW
    dci = s["dci_wire_bytes"] / DCI_BW
    coll = ici + dci
    terms = {"compute_s": compute, "memory_s": memory,
             "collective_s": coll, "ici_s": ici, "dci_s": dci}
    dominant = max(("compute_s", "memory_s", "collective_s"),
                   key=lambda k: terms[k])
    mf = model_flops_per_device(rec)
    bound = max(terms["compute_s"], terms["memory_s"], terms["collective_s"])
    return {
        **terms,
        "dominant": dominant.replace("_s", ""),
        "model_flops": mf,
        "useful_ratio": mf / s["dot_flops"] if s["dot_flops"] else 0.0,
        "step_bound_s": bound,
        # fraction of bf16 peak achievable if the step ran exactly at the
        # max(term) bound — the roofline fraction reported in §Perf
        "roofline_fraction": (mf / PEAK_BF16) / bound if bound else 0.0,
    }


_SUGGESTIONS = {
    "compute": ("compute-bound: reduce padded-head / remat "
                "waste, or increase per-chip batch to amortize fixed work"),
    "memory": ("memory-bound: fuse the attention softmax (Pallas flash "
               "kernel keeps scores in VMEM) and keep activations bf16"),
    "collective": ("collective-bound: shrink the gather scale (smaller "
                   "partition group / hierarchical staging) or trade TP for "
                   "data parallelism on the over-sharded axis"),
}


def load_records(tag: str = "") -> list[dict]:
    recs = []
    for p in sorted((ART / "dryrun").glob("*.json")):
        rec = json.loads(p.read_text())
        if (rec.get("tag") or "") == tag:
            recs.append(rec)
    return recs


def build_table(tag: str = "") -> list[dict]:
    rows = []
    for rec in load_records(tag):
        t = roofline_terms(rec)
        rows.append({
            "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
            "p": rec["partition_size"],
            **{k: t[k] for k in ("compute_s", "memory_s", "collective_s",
                                 "ici_s", "dci_s", "dominant",
                                 "useful_ratio", "roofline_fraction")},
            "note": _SUGGESTIONS[t["dominant"]],
        })
    return rows


def markdown_table(rows: list[dict], mesh: str | None = "16x16") -> str:
    cols = ("arch", "shape", "mesh", "p", "compute_s", "memory_s",
            "collective_s", "dci_s", "dominant", "useful_ratio",
            "roofline_fraction")
    out = ["| " + " | ".join(cols) + " |",
           "|" + "|".join("---" for _ in cols) + "|"]
    for r in sorted(rows, key=lambda r: (r["arch"], r["shape"], r["mesh"])):
        if mesh and r["mesh"] != mesh:
            continue
        cells = []
        for c in cols:
            v = r[c]
            cells.append(f"{v:.4g}" if isinstance(v, float) else str(v))
        out.append("| " + " | ".join(cells) + " |")
    return "\n".join(out)


if __name__ == "__main__":
    rows = build_table()
    print(markdown_table(rows, mesh=None))
    (ART / "roofline.json").write_text(json.dumps(rows, indent=1))
