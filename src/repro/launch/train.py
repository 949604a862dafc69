"""End-to-end training driver.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --steps 200 --batch 8 --seq 128 --smoke --ckpt-dir /tmp/ckpt \
        [--resume] [--compress] [--importance-sampling] [--mesh 2x2x2]

Wires together: config registry, synthetic data pipeline (+ optional
multi-objective importance sampling), AdamW, checkpoint manager (atomic,
keep-k, resume-from-latest), telemetry sketches, optional sampled gradient
exchange, and preemption handling (SIGTERM -> checkpoint -> exit 0).
"""
from __future__ import annotations

import argparse
import dataclasses
import signal
import sys
import time

import jax
import jax.numpy as jnp

from repro.ckpt.manager import CheckpointManager
from repro.configs.registry import get_config, get_smoke_config, list_archs
from repro.core import (COUNT, SUM, MultiSketchSpec, multisketch_empty,
                        sketch_estimate, thresh)
from repro.data.pipeline import DataConfig, Loader, SyntheticCorpus
from repro.launch import steps as St
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.models import model as Mod
from repro.optim import adamw


def parse_mesh(spec: str):
    if not spec:
        return make_host_mesh()
    dims = tuple(int(x) for x in spec.split("x"))
    names = {1: ("data",), 2: ("data", "model"),
             3: ("pod", "data", "model")}[len(dims)]
    return make_mesh(dims, names)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b", choices=list_archs())
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--mesh", default="", help="e.g. 2x2x2 (pod,data,model)")
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--compress", action="store_true",
                    help="sampled cross-pod gradient exchange")
    ap.add_argument("--importance-sampling", action="store_true")
    ap.add_argument("--microbatch", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    mesh = parse_mesh(args.mesh)
    opt_cfg = adamw.OptConfig(peak_lr=args.lr, warmup_steps=args.steps // 20 + 1,
                              total_steps=args.steps)
    dcfg = DataConfig(vocab_size=cfg.vocab_size, seq_len=args.seq,
                      global_batch=args.batch, seed=args.seed,
                      n_docs=20_000)
    corpus = SyntheticCorpus(dcfg)
    loader = Loader(corpus, dcfg, importance=args.importance_sampling)
    # device-resident per-step telemetry: folded INSIDE the jitted train
    # step (donated MultiSketch state), merged/queried whenever asked
    tel_spec = MultiSketchSpec(
        objectives=((SUM, 64), (COUNT, 64), (thresh(5.0), 64)), seed=1234)
    mgr = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None

    with jax.set_mesh(mesh):
        step_fn, st_sh = St.make_train_step(
            cfg, opt_cfg, mesh, donate=False,
            microbatch=args.microbatch or None,
            compress=dict(k=256, min_size=65536) if args.compress else None,
            telemetry=tel_spec)

        params, _ = Mod.init_model(jax.random.PRNGKey(args.seed), cfg)
        state = {"params": params, "opt": adamw.init_opt_state(params),
                 "tel": multisketch_empty(tel_spec)}
        state = jax.device_put(state, st_sh)
        start = 0
        if mgr and args.resume:
            restored, rstep = mgr.restore_latest(state, st_sh)
            if restored is None:
                # checkpoints from before the telemetry sketch lack the
                # "tel" arrays — restore params/opt and start telemetry fresh
                core_tpl = {kk: state[kk] for kk in ("params", "opt")}
                core_sh = {kk: st_sh[kk] for kk in ("params", "opt")}
                restored, rstep = mgr.restore_latest(core_tpl, core_sh)
                if restored is not None:
                    restored = {**restored, "tel": state["tel"]}
            if restored is not None:
                state, start = restored, rstep
                print(f"[train] resumed from step {start}")

        # preemption: checkpoint on SIGTERM, exit cleanly (fault tolerance)
        preempted = {"flag": False}

        def _on_sigterm(signum, frame):
            preempted["flag"] = True
        signal.signal(signal.SIGTERM, _on_sigterm)

        t0 = time.time()
        for step in range(start, args.steps):
            raw = loader.batch(step)
            batch = make_batch(cfg, raw, dcfg)
            state, metrics = step_fn(state, batch)
            if (step + 1) % args.log_every == 0 or step == start:
                loss = float(metrics["loss"])
                gn = float(metrics["grad_norm"])
                dt = (time.time() - t0) / max(step - start + 1, 1)
                print(f"step {step+1:5d} loss {loss:8.4f} gnorm {gn:8.3f} "
                      f"{dt*1e3:7.1f} ms/step", flush=True)
            if mgr and ((step + 1) % args.ckpt_every == 0 or preempted["flag"]):
                mgr.save(step + 1, state, blocking=False)
            if preempted["flag"]:
                print(f"[train] preempted at step {step+1}; checkpointed")
                mgr and mgr.wait()
                sys.exit(0)

        if mgr:
            mgr.save(args.steps, state, blocking=True)

        # telemetry demo: the device-resident multi-objective summary
        # answers several f-statistics over the whole training history
        tel = state["tel"]
        print("[telemetry] sketch size:", int(jnp.sum(tel.member)))
        print("[telemetry] est total loss mass:",
              float(sketch_estimate(tel, SUM)))
        print("[telemetry] est #obs with loss>=5:",
              float(sketch_estimate(tel, thresh(5.0))))
    return state


def make_batch(cfg, raw, dcfg):
    toks = jnp.asarray(raw["tokens"])
    if cfg.family == "encoder":
        B, S = toks.shape
        emb = jax.random.normal(jax.random.PRNGKey(0), (B, S, cfg.d_model),
                                jnp.bfloat16)  # stub frontend features
        return {"frames": emb, "labels": toks % cfg.vocab_size}
    if cfg.family == "vlm":
        P = cfg.frontend_tokens
        B, S = toks.shape
        patches = jax.random.normal(jax.random.PRNGKey(1), (B, P, cfg.d_model),
                                    jnp.bfloat16)
        return {"tokens": toks[:, :max(S - P, 8)], "patches": patches}
    return {"tokens": toks}


if __name__ == "__main__":
    main()
