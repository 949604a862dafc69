"""Serving driver: prefill + batched decode behind the fault-tolerant pool.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b --smoke \
        --batch 4 --prompt-len 16 --gen 16

Demonstrates the inference path the decode_* dry-run cells lower: a prompt
batch is prefilled (building the KV/SSM cache), then tokens are decoded
step-by-step with greedy sampling. Request-level statistics (prompt length,
generated tokens) flow through the multi-tenant ``EnginePool``
(launch.pool): admission-queued, quarantined per row, answered with the
degradation ladder's staleness/overflow labels — the dashboard path a real
deployment serves from, not a bare collector.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.registry import get_config, get_smoke_config, list_archs
from repro.core import (EVERYTHING, SUM, COUNT, MultiSketchSpec,
                        hash_fraction, thresh)
from repro.launch.mesh import make_host_mesh
from repro.launch.pool import EnginePool
from repro.models import model as Mod


def _positive_int(v: str) -> int:
    i = int(v)
    if i < 1:
        raise argparse.ArgumentTypeError(f"must be >= 1, got {i}")
    return i


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=_positive_int, default=4)
    ap.add_argument("--prompt-len", type=_positive_int, default=16)
    ap.add_argument("--gen", type=_positive_int, default=16,
                    help="tokens to generate (>= 1; 1 = prefill-only "
                         "argmax, no decode steps)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    if cfg.family == "encoder":
        raise SystemExit("encoder-only arch has no decode step")
    mesh = make_host_mesh()
    key = jax.random.PRNGKey(args.seed)
    max_len = args.prompt_len + args.gen

    with jax.set_mesh(mesh):
        params, _ = Mod.init_model(key, cfg)
        prompts = jax.random.randint(key, (args.batch, args.prompt_len),
                                     0, cfg.vocab_size)
        batch = {"tokens": prompts}
        if cfg.family == "vlm":
            batch["patches"] = jax.random.normal(
                key, (args.batch, cfg.frontend_tokens, cfg.d_model),
                jnp.bfloat16)

        t0 = time.time()
        logits, cache = Mod.prefill(params, cfg, batch)
        cache = Mod.grow_cache(cfg, cache, args.gen)  # room for decode steps
        t_prefill = time.time() - t0

        decode = jax.jit(lambda p, t, c, i: Mod.serve_step(p, cfg, t, c, i))
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        outs = [tok]
        t0 = time.time()
        idx0 = args.prompt_len
        for t in range(args.gen - 1):
            logits, cache = decode(params, tok, cache, jnp.int32(idx0 + t))
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            outs.append(tok)
        jax.block_until_ready(tok)
        t_decode = time.time() - t0
        gen = jnp.stack(outs, 1)

        print(f"prefill {args.batch}x{args.prompt_len}: {t_prefill*1e3:.1f} ms")
        if args.gen > 1:   # gen==1 decodes zero steps: no per-token rate
            print(f"decode {args.gen-1} steps: "
                  f"{t_decode*1e3/(args.gen-1):.1f} ms/token")
        else:
            print("decode 0 steps (prefill-only argmax)")
        print("generated token ids (first row):",
              np.asarray(gen[0])[:12].tolist())

        # request telemetry through the fault-tolerant serving tier: one
        # named stream per tenant behind the pool's admission queue —
        # ingest is per-row quarantined, the dashboard batch coalesces
        # into ONE fused segment-query launch, and every answer carries
        # its degradation-ladder label (FRESH/STALE) + overflow flag.
        pool = EnginePool(queue_depth=64)
        pool.create_stream("requests", MultiSketchSpec(
            objectives=((SUM, 64), (COUNT, 64), (thresh(16.0), 64))))
        receipt = pool.absorb(
            "requests", np.arange(args.batch),
            np.full(args.batch, float(args.prompt_len + args.gen)))
        fut = pool.submit("requests", (SUM, COUNT, thresh(16.0)),
                          (EVERYTHING, hash_fraction(0.5, salt=1)))
        pool.pump()
        resp = fut.result(timeout=30.0)
        stats = resp.values
        print(f"[pool] stream=requests status={resp.status} "
              f"lag={resp.epoch_lag} overflow={resp.overflow} "
              f"quarantined={receipt.quarantined}")
        print("[telemetry] est total tokens served:", float(stats[0, 0]))
        print("[telemetry] est requests:", float(stats[1, 0]))
        print("[telemetry] est requests >= 16 tokens:", float(stats[2, 0]))
        print("[telemetry] est tokens, 50% coordinated key sample:",
              float(stats[0, 1]))

        # request-shape clustering: the metric-domain tier over the same
        # request log — a resident sampled point slab scored by the fused
        # service-cost kernel (launch.cluster); a sharded server absorbs
        # per-replica request features and answers capacity-planning
        # queries (k typical request shapes, coverage radii) from the
        # sample alone.
        from repro.launch.cluster import ClusterEngine, local_search
        gen_np = np.asarray(gen)
        feats = np.stack(
            [np.full(args.batch, args.prompt_len + args.gen, np.float32),
             np.array([len(np.unique(r)) for r in gen_np], np.float32)], 1)
        ceng = ClusterEngine(dim=2, k=16, mu=2.0,
                             n_anchors=min(4, args.batch), seed=args.seed)
        ceng.absorb(feats)
        res = local_search(ceng, k=min(2, args.batch), rounds=4, n_cand=8)
        print("[cluster] request-shape centers:",
              np.round(res.centers, 2).tolist())
        print("[cluster] est k-means service cost:", round(res.est_cost, 3))


if __name__ == "__main__":
    main()
