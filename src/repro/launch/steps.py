"""The jitted, sharded train step and the abstract state it shards.

The state's shardings come from ShapeDtypeStructs (``jax.eval_shape``),
so no device allocation happens at build time.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.multi_sketch import (MultiSketchSpec,
                                     multisketch_absorb_inline)
from repro.models import model as Mod
from repro.models.config import ModelConfig
from repro.optim import adamw
from . import sharding as Sh


# ---------------------------------------------------------------------------
# abstract state (ShapeDtypeStruct stand-ins — never allocated)
# ---------------------------------------------------------------------------

def abstract_params(cfg: ModelConfig):
    """(param ShapeDtypeStructs, logical spec tree) without allocation.

    The spec tree is plain python (tuples of axis names) built during
    tracing, so we capture it via closure instead of returning it through
    eval_shape.
    """
    captured = {}

    def build():
        p, s = Mod.init_model(jax.random.PRNGKey(0), cfg)
        captured["specs"] = s
        return p

    shapes = jax.eval_shape(build)
    return shapes, captured["specs"]


def abstract_state(cfg: ModelConfig):
    p_shapes, specs = abstract_params(cfg)
    opt_shapes = jax.eval_shape(adamw.init_opt_state, p_shapes)
    return {"params": p_shapes, "opt": opt_shapes}, specs


def state_shardings(cfg: ModelConfig, mesh):
    state_shapes, specs = abstract_state(cfg)
    psh = Sh.param_shardings(specs, state_shapes["params"], mesh,
                             fsdp=cfg.fsdp)
    rep = Sh.replicated(mesh)
    return {
        "params": psh,
        "opt": {"m": psh, "v": psh, "step": rep},
    }, state_shapes


# ---------------------------------------------------------------------------
# step factories
# ---------------------------------------------------------------------------

def make_train_step(cfg: ModelConfig, opt_cfg: adamw.OptConfig, mesh,
                    grad_transform=None, microbatch: Optional[int] = None,
                    donate: bool = True, compress: Optional[dict] = None,
                    telemetry: Optional[MultiSketchSpec] = None):
    """Returns (jitted_step, state_shardings_tree).

    grad_transform: optional fn(grads, params, step) -> grads applied between
    backward and optimizer.
    microbatch: if set, split the batch into `microbatch` sequential
    accumulation steps (grad accumulation via lax.scan).
    compress: if set (dict of distopt.compression kwargs) and the mesh has a
    "pod" axis, the cross-pod gradient reduction becomes the paper's sampled
    exchange (multi-objective bottom-k sketches over DCN) instead of a dense
    all-reduce.
    telemetry: if set (a MultiSketchSpec), the train state carries a
    device-resident MultiSketch under key "tel" and every step folds the
    per-example loss proxies into it INSIDE the jitted step (donated
    buffers, no host round-trip) — queryable any time via sketch_estimate.
    """
    st_shard, _ = state_shardings(cfg, mesh)
    if telemetry is not None:
        from repro.launch.summary import multisketch_shape
        rep = Sh.replicated(mesh)
        st_shard["tel"] = jax.tree.map(lambda _: rep,
                                       multisketch_shape(telemetry))

    def loss_of(params, batch):
        return Mod.loss_fn(params, cfg, batch)

    def compute_grads_once(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_of, has_aux=True)(params, batch)
        return loss, metrics, grads

    def compute_grads(params, batch):
        """Full-batch grads, with optional microbatch accumulation."""
        if not (microbatch and microbatch > 1):
            return compute_grads_once(params, batch)
        # under compression this runs INSIDE a pod-manual shard_map: the
        # batch is already pod-local, so constrain on "data" only
        baxis = "data" if compress is not None else Sh.batch_pspec(mesh)[0]

        def split(leaf):
            b = leaf.shape[0]
            out = leaf.reshape(microbatch, b // microbatch, *leaf.shape[1:])
            # keep each microbatch sharded on the data axes — without this
            # the reshape decays to replicated and compute is duplicated
            spec = P(*([None, baxis] + [None] * (out.ndim - 2)))
            return jax.lax.with_sharding_constraint(
                out, NamedSharding(mesh, spec))
        micro = jax.tree.map(split, batch)

        def acc(carry, mb):
            loss_a, grads_a = carry
            loss, metrics, grads = compute_grads_once(params, mb)
            return (loss_a + loss,
                    jax.tree.map(jnp.add, grads_a, grads)), metrics

        zeros = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                             params)
        (loss, grads), metrics = jax.lax.scan(
            acc, (jnp.float32(0), zeros), micro)
        return (loss / microbatch,
                jax.tree.map(lambda m: m[-1], metrics),
                jax.tree.map(lambda g: g / microbatch, grads))

    compressed = None
    if compress is not None:
        from repro.distopt.compression import compressed_grads_fn
        compressed = compressed_grads_fn(compute_grads, mesh, **compress)

    def step_fn(state, batch):
        params = state["params"]
        if compressed is not None:
            pspecs = jax.tree.map(lambda ns: ns.spec, st_shard["params"])
            loss, metrics, grads = compressed(params, batch,
                                              state["opt"]["step"], pspecs)
        else:
            loss, metrics, grads = compute_grads(params, batch)

        if grad_transform is not None:
            grads = grad_transform(grads, params, state["opt"]["step"])

        new_params, new_opt, om = adamw.apply_updates(
            params, grads, state["opt"], opt_cfg)
        new_state = {"params": new_params, "opt": new_opt}
        if telemetry is not None:
            # fold per-example loss proxies keyed step * 2^16 + example. The
            # stride is a CONSTANT (not the batch size) so keys stay unique
            # across a resume with a different --batch; bounds: b <= 65536
            # per step, step < 32768 before int32 wrap (past either, keys
            # collide and the dedup silently merges observations)
            b = jax.tree_util.tree_leaves(batch)[0].shape[0]
            step_id = state["opt"]["step"].astype(jnp.int32)
            tkeys = step_id * jnp.int32(1 << 16) + jnp.arange(b, dtype=jnp.int32)
            new_state["tel"] = multisketch_absorb_inline(
                telemetry, state["tel"], tkeys, jnp.full((b,), loss))
        return (new_state, {"loss": loss, **metrics, **om})

    jitted = jax.jit(
        step_fn,
        in_shardings=(st_shard, None),
        out_shardings=(st_shard, None),
        donate_argnums=(0,) if donate else ())
    return jitted, st_shard
