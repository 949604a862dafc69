"""Sampled gradient exchange demo (paper technique -> collective term).

    PYTHONPATH=src python examples/gradient_compression_demo.py

Runs the same training twice on a simulated 2x2x2 (pod,data,model) mesh:
once with dense cross-pod all-reduce, once with the multi-objective sampled
exchange (distopt.compression), and reports loss curves + wire bytes.
"""
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs.registry import get_smoke_config  # noqa: E402
from repro.launch import steps as St  # noqa: E402
from repro.launch.mesh import make_mesh  # noqa: E402
from repro.models import model as Mod  # noqa: E402
from repro.optim import adamw  # noqa: E402


def run(compress):
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = get_smoke_config("qwen2-1.5b")
    key = jax.random.PRNGKey(0)
    opt = adamw.OptConfig(total_steps=60, warmup_steps=2, peak_lr=5e-3)
    with jax.set_mesh(mesh):
        params, _ = Mod.init_model(key, cfg)
        step, sh = St.make_train_step(
            cfg, opt, mesh, donate=False,
            compress=dict(k=256, min_size=1024) if compress else None)
        state = jax.device_put(
            {"params": params, "opt": adamw.init_opt_state(params)}, sh)
        batch = {"tokens": jax.random.randint(key, (8, 64), 0,
                                              cfg.vocab_size)}
        losses = []
        for i in range(12):
            state, m = step(state, batch)
            losses.append(float(m["loss"]))
    return losses


if __name__ == "__main__":
    dense = run(False)
    sampled = run(True)
    n_params = sum(x.size for x in jax.tree.leaves(
        Mod.init_model(jax.random.PRNGKey(0),
                       get_smoke_config("qwen2-1.5b"))[0]))
    print("step | dense loss | sampled-exchange loss")
    for i, (d, s) in enumerate(zip(dense, sampled)):
        print(f"{i:4d} | {d:10.4f} | {s:10.4f}")
    wire = 3 * 256 * 12  # 3k slots x (idx,val,prob) per big leaf
    print(f"\ncross-pod bytes per big leaf: dense = leaf_size*4, "
          f"sampled = {wire} (fixed)")
