"""Roofline table generator: experiments/dryrun/*.json -> markdown.

Hardware model: the per-chip peaks of ``PEAKS``, keyed by the
``device_kind`` JAX reports; ``peaks`` raises for a kind it has no numbers
for. The tables model the ``TARGET_KIND`` chip.
Terms (per device == per chip, post-SPMD HLO):
    compute    = flops / peak_flops
    memory     = hbm_bytes / hbm_bw
    collective = coll_bytes / ici_bw
MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) per chip for train cells;
forward-only cells use 2*N*D. The useful-fraction column flags remat/
replication waste. Usage:
    PYTHONPATH=src python -m benchmarks.roofline [--mesh sp|mp]
"""
from __future__ import annotations

import argparse
import glob
import json

# Per-chip peaks by ``jax.Device.device_kind``. TPU v5e (JAX reports it as
# "TPU v5 lite"), Google Cloud "TPU v5e" system architecture page: 197
# TFLOP/s bf16, 819 GB/s HBM, 1600 Gbps ICI over 4 links (50 GB/s a link).
PEAKS = {
    "TPU v5 lite": {"peak_flops": 197e12, "hbm_bw": 819e9, "ici_bw": 50e9},
}
# The chip the dry-run meshes and the fold model are sized for.
TARGET_KIND = "TPU v5 lite"


def peaks(device_kind: str) -> dict:
    """The per-chip peak rates of ``device_kind``; raises for a kind with
    no published numbers here rather than lending it another chip's."""
    if device_kind not in PEAKS:
        raise ValueError(f"no peak rates for device kind {device_kind!r}; "
                         f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def model_flops_per_chip(arch: str, shape_name: str, chips: int) -> float:
    from repro.configs.registry import get_config
    from repro.configs.shapes import SHAPES
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    n = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.seq_len * shape.global_batch
        mult = 6
    elif shape.kind == "prefill":
        tokens = shape.seq_len * shape.global_batch
        mult = 2
    else:  # decode: one token per sequence
        tokens = shape.global_batch
        mult = 2
    return mult * n * tokens / chips


def load_rows(mesh_tag: str):
    rows = []
    for f in sorted(glob.glob(f"experiments/dryrun/*__{mesh_tag}.json")):
        r = json.load(open(f))
        rows.append(r)
    return rows


def render(mesh_tag: str = "sp", fmt: str = "md"):
    pk = peaks(TARGET_KIND)
    chips = 256 if mesh_tag == "sp" else 512
    rows = load_rows(mesh_tag)
    out = []
    hdr = ("| arch | shape | compute s | memory s | collective s | dominant "
           "| MODEL_FLOPS/HLO | temp GB | fits | note |")
    out.append(hdr)
    out.append("|" + "---|" * 10)
    for r in rows:
        if r["status"] == "skipped":
            out.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | — |"
                       f" — | — | SKIP: {r['reason']} |")
            continue
        if r["status"] != "ok":
            out.append(f"| {r['arch']} | {r['shape']} | — | — | — | — | — |"
                       f" — | — | {r['status']} |")
            continue
        h = r["hlo_cost"]
        ct = h["flops"] / pk["peak_flops"]
        mt = h["hbm_bytes"] / pk["hbm_bw"]
        lt = h["coll_bytes"] / pk["ici_bw"]
        dom = max((("compute", ct), ("memory", mt), ("collective", lt)),
                  key=lambda x: x[1])[0]
        mf = model_flops_per_chip(r["arch"], r["shape"], chips)
        useful = mf / max(h["flops"], 1)
        temp = r["memory"]["temp_size_in_bytes"] / 1e9
        args = r["memory"]["argument_size_in_bytes"] / 1e9
        fits = "yes" if (temp + args) < 17.18 else f"NO ({temp+args:.0f}GB)"  # 16 GiB HBM
        mb = r.get("microbatch", 0)
        note = f"mb={mb}" if mb and mb > 1 else ""
        if r.get("overrides"):
            note += f" {r['overrides']}"
        out.append(
            f"| {r['arch']} | {r['shape']} | {ct:.3f} | {mt:.3f} | {lt:.3f} "
            f"| {dom} | {useful:.2f} | {temp:.1f} | {fits} | {note} |")
    return "\n".join(out)


def fold_bytes_moved(slab_bytes: int, chunk_rows: int, num_shards: int,
                     absorb_time: bool = True) -> dict:
    """Bytes-moved model for ONE absorb epoch of the serving engine
    (launch.query), in the roofline's memory term.

    The shard fold reads the target shard's slab plus the chunk
    (int32 key + float32 weight + bool active = 9 B/row) and writes the
    slab back; absorb-time maintenance adds the merged-slab delta fold
    (read merged + the post-fold shard slab, write merged). The lazy
    engine instead pays a full stacked re-merge at the NEXT query: read
    all ``num_shards`` slabs, write one. Every fold is re-selection-
    bound, so bytes / HBM bandwidth is the floor for the epoch's device time.
    """
    chunk_bytes = 9 * chunk_rows
    shard_fold = 2 * slab_bytes + chunk_bytes
    maintain = 3 * slab_bytes if absorb_time else 0
    lazy_remerge = 0 if absorb_time else (num_shards + 1) * slab_bytes
    total = shard_fold + maintain + lazy_remerge
    return {
        "shard_fold_bytes": shard_fold,
        "maintain_bytes": maintain,
        "lazy_remerge_bytes": lazy_remerge,
        "epoch_bytes": total,
        "min_epoch_s": total / peaks(TARGET_KIND)["hbm_bw"],
    }


def render_fold_model() -> str:
    """Markdown table of the absorb/fold bytes-moved model across the
    serving configurations the benches exercise."""
    from repro.core import (COUNT, SUM, MultiSketchSpec, multisketch_slab_bytes,
                            thresh)
    spec = MultiSketchSpec(objectives=((SUM, 64), (COUNT, 64),
                                       (thresh(2.0), 64)), seed=0)
    b = multisketch_slab_bytes(spec)
    out = ["| mode | shards | chunk | epoch bytes | min epoch time |",
           "|" + "---|" * 5]
    for absorb_time in (True, False):
        for shards in (2, 8):
            for chunk in (2048, 8192):
                m = fold_bytes_moved(b, chunk, shards, absorb_time)
                mode = "absorb-time" if absorb_time else "lazy"
                out.append(f"| {mode} | {shards} | {chunk} "
                           f"| {m['epoch_bytes']} "
                           f"| {m['min_epoch_s']*1e9:.1f} ns |")
    return "\n".join(out)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="sp", choices=("sp", "mp"))
    ap.add_argument("--fold-model", action="store_true",
                    help="print the absorb/fold bytes-moved model instead "
                         "of the dry-run table")
    args = ap.parse_args()
    if args.fold_model:
        print(render_fold_model())
    else:
        print(render(args.mesh))


if __name__ == "__main__":
    main()
