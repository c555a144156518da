"""§Roofline table generator: reads dryrun_results/*.json and emits the
per-(arch x shape x mesh) roofline analysis (markdown + CSV rows)."""

from __future__ import annotations

import glob
import json
import os
from typing import Dict, List

from benchmarks.common import fmt_row
from repro.launch.hlo_cost import PEAKS, TARGET_DEVICE_KIND

RESULTS_DIR = os.environ.get("DRYRUN_RESULTS", "dryrun_results")



def _mesh_name(rec: Dict) -> str:
    m = rec.get("mesh")
    if isinstance(m, str):
        return m
    return "pod2x16x16" if "pod" in m else "pod16x16"


def load_records(results_dir: str = RESULTS_DIR) -> List[Dict]:
    recs = []
    for f in sorted(glob.glob(os.path.join(results_dir, "*.json"))):
        with open(f) as fh:
            recs.append(json.load(fh))
    return recs


def one_sentence(rec: Dict) -> str:
    """What would move the dominant term down (per-cell guidance)."""
    dom = rec["roofline"]["dominant"]
    arch, shape = rec["arch"], rec["shape"]
    if dom == "memory":
        if arch in ("rwkv6-3b",) or (arch == "jamba-v0.1-52b" and shape != "decode_32k"):
            return "chunk the recurrence (block-parallel scan) to amortize state traffic over many tokens per HBM round-trip"
        if shape == "train_4k":
            return "fewer microbatches / fused attention (no materialized scores) to cut re-read of weights and score tensors"
        return "fuse attention (chunked online softmax) and keep KV in bf16 to cut score-tensor traffic"
    if dom == "collective":
        return "re-shard to cut all-gathers (2D weight sharding aligned with use), overlap collectives with compute, compress gradients"
    return "raise arithmetic intensity: larger per-device microbatch or cheaper dispatch (chunked MoE routing)"


def markdown_table(recs: List[Dict], with_guidance: bool = True) -> str:
    head = "| arch | shape | mesh | compute_s | memory_s | collective_s | dominant | MODEL_FLOPS/dev | useful ratio | fits 16GB |"
    sep = "|---|---|---|---|---|---|---|---|---|---|"
    if with_guidance:
        head += " what moves the dominant term down |"
        sep += "---|"
    lines = [head, sep]
    for r in sorted(recs, key=lambda r: (r["arch"], r["shape"], _mesh_name(r))):
        if r["status"] == "skipped":
            row = f"| {r['arch']} | {r['shape']} | {_mesh_name(r)} | — | — | — | skipped | — | — | {r['reason'][:60]} |"
            lines.append(row + (" |" if with_guidance else ""))
            continue
        if r["status"] != "ok":
            lines.append(f"| {r['arch']} | {r['shape']} | {_mesh_name(r)} | ERROR | | | | | | |" + (" |" if with_guidance else ""))
            continue
        rl = r["roofline"]
        fits = (r["memory"]["temp_bytes"] + r["memory"]["argument_bytes"]) < PEAKS[TARGET_DEVICE_KIND]["hbm_bytes"]
        row = (
            f"| {r['arch']} | {r['shape']} | {_mesh_name(r)} "
            f"| {rl['compute_s']:.3g} | {rl['memory_s']:.3g} | {rl['collective_s']:.3g} "
            f"| {rl['dominant']} | {r['model_flops_per_device']:.3g} "
            f"| {r['useful_flops_ratio']:.3f} | {'yes' if fits else 'NO'} |"
        )
        if with_guidance:
            row += f" {one_sentence(r)} |"
        lines.append(row)
    return "\n".join(lines)


def run():
    recs = load_records()
    rows = []
    for r in recs:
        if r["status"] != "ok":
            continue
        rl = r["roofline"]
        rows.append(
            fmt_row(
                f"roofline/{r['arch']}/{r['shape']}/{_mesh_name(r)}",
                rl["bound_s"] * 1e6,
                f"dom={rl['dominant']};compute_s={rl['compute_s']:.3g};memory_s={rl['memory_s']:.3g};"
                f"collective_s={rl['collective_s']:.3g};useful={r['useful_flops_ratio']:.3f}",
            )
        )
    if not rows:
        rows.append(fmt_row("roofline/NO_RESULTS", 0.0, f"run dryrun first (dir={RESULTS_DIR})"))
    return rows


if __name__ == "__main__":
    recs = load_records()
    print(markdown_table(recs))
