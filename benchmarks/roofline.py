"""Roofline analysis from the dry-run artifacts (EXPERIMENTS.md §Roofline).

Per (arch × shape × mesh) cell, derive the three terms from
``compiled.cost_analysis()`` + the HLO collective census (all per-device,
post-SPMD — multiplying back by chip count and dividing again per the
assignment's formulas is an identity, noted in EXPERIMENTS.md):

    compute_s    = HLO_FLOPs / (chips × peak bf16 FLOP/s)
    memory_s     = HLO_bytes / (chips × peak HBM bytes/s)
    collective_s = collective_operand_bytes / (chips × link bytes/s)

with the peaks of the record's ``device_kind`` (the chip the dry-run
mesh models) from ``PEAKS``; a kind not in the table is an error.

plus MODEL_FLOPS (6·N·D train / 2·N·D inference, N_active for MoE), the
useful-compute ratio, the dominant term, and a what-would-move-it note.
"""
from __future__ import annotations

import json
from pathlib import Path

#: per-chip peaks keyed by ``jax.Device.device_kind``.  TPU v5e: Google
#: Cloud documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM,
#: 1,600 Gbit/s chip-to-chip interconnect over 4 links (50 GB/s each).
PEAKS = {
    "TPU v5 lite": {"flops": 197e12, "hbm_bw": 819e9, "link_bw": 50e9},
}


def peaks(device_kind: str) -> dict:
    """The peak-rate row for ``device_kind``; raises on a kind the table
    does not list instead of assuming another chip's numbers."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peak rates for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None

DRYRUN_DIR = Path(__file__).resolve().parents[1] / "experiments" / "dryrun"


def load_cells(mesh="pod16x16", tag=None):
    out = []
    for f in sorted(DRYRUN_DIR.glob("*.json")):
        rec = json.loads(f.read_text())
        if not rec.get("ok"):
            continue
        if rec["mesh"] != mesh:
            continue
        ftag = f.stem.split("__")[3] if len(f.stem.split("__")) > 3 else None
        if ftag != tag:
            continue
        out.append(rec)
    return out


def analyze(rec):
    # hlo_cost: trip-count-aware re-derivation (launch/hlo_cost.py);
    # XLA's own cost_analysis counts loop bodies once (EXPERIMENTS.md).
    hc = rec.get("hlo_cost") or {}
    ca = rec.get("cost_analysis") or {}
    flops_dev = hc.get("flops") or ca.get("flops", 0.0)   # per-device
    flops_dev += 10.0 * hc.get("transcendental_elems", 0.0)
    bytes_dev = hc.get("bytes") or ca.get("bytes accessed", 0.0)
    coll_dev = rec["collectives"]["total_bytes"]
    link_dev = rec["collectives"].get("total_link_bytes", coll_dev)

    peak = peaks(rec.get("device_kind"))
    compute_s = flops_dev / peak["flops"]
    memory_s = bytes_dev / peak["hbm_bw"]
    collective_s = coll_dev / peak["link_bw"]
    collective_link_s = link_dev / peak["link_bw"]

    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)

    n, n_act = rec["params"], rec["active_params"]
    tokens = rec["tokens_per_step"]
    shape = rec["shape"]
    mult = 6 if shape.startswith("train") else 2
    model_flops = mult * (n_act if n_act < n else n) * tokens
    chips = rec["n_devices"]
    hlo_flops_global = flops_dev * chips
    useful = model_flops / hlo_flops_global if hlo_flops_global else 0.0

    bound_s = max(terms.values())
    # roofline fraction: useful model FLOPs per second at the bound vs peak
    ach_flops = model_flops / chips / bound_s if bound_s else 0.0
    frac = ach_flops / peak["flops"]

    return {
        "arch": rec["arch"], "shape": shape, "mesh": rec["mesh"],
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s, "collective_link_s": collective_link_s,
        "dominant": dominant,
        "model_flops": model_flops, "hlo_flops_global": hlo_flops_global,
        "useful_ratio": useful, "roofline_frac": frac,
        "hint": _hint(dominant, rec, useful),
    }


def _hint(dominant, rec, useful):
    shape = rec["shape"]
    if dominant == "memory" and shape.startswith(("decode", "long")):
        return ("memory-bound decode: cut weight/KV bytes (DIMA w8/w4 "
                "sub-ranged weights, int8 KV) or raise batch")
    if dominant == "memory":
        return "fuse/remat to cut HBM round-trips; check layout copies"
    if dominant == "collective":
        return ("collective-bound: reshard to shrink the largest gather "
                "(KV all-gather / logits) or overlap with compute")
    if useful < 0.4:
        return ("compute-bound but low useful ratio: remat recompute or "
                "masked-causal waste dominates — tighten the remat policy "
                "/ causal block skipping")
    return "compute-bound: good; push MXU utilization (tile alignment)"


def table(mesh="pod16x16", tag=None):
    rows = [analyze(r) for r in load_cells(mesh, tag)]
    rows.sort(key=lambda r: (r["arch"], r["shape"]))
    return rows


def render_markdown(rows):
    hdr = ("| arch | shape | compute_s | memory_s | collective_s | dominant "
           "| MODEL/HLO | roofline_frac |\n|---|---|---|---|---|---|---|---|")
    lines = [hdr]
    for r in rows:
        lines.append(
            f"| {r['arch']} | {r['shape']} | {r['compute_s']:.3e} "
            f"| {r['memory_s']:.3e} | {r['collective_s']:.3e} "
            f"| **{r['dominant']}** | {r['useful_ratio']:.2f} "
            f"| {r['roofline_frac']:.3f} |")
    return "\n".join(lines)


if __name__ == "__main__":
    rows = table()
    print(render_markdown(rows))
