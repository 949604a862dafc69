"""Mixture-of-Experts block (granite-moe, qwen2-moe).

Dispatch is capacity-based scatter/gather (GShard-style semantics) WITHOUT the
[T, E, C] one-hot dispatch einsum: slot positions come from a per-row cumsum
of expert one-hots (local to each batch row, so no cross-device cumsum), and
tokens move via batched scatter/gather. Expert weights are sharded on the
"expert" logical axis (-> mesh "model"); the data->expert redistribution is
what surfaces as all-to-all / collective traffic in the compiled HLO.

FLOPs are proportional to ACTIVE params (top-k + shared), matching the MoE
roofline convention MODEL_FLOPS = 6 * N_active * D.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from .layers import apply_mlp, dense_init, init_mlp


def moe_capacity(tokens_per_row: int, cfg) -> int:
    c = math.ceil(tokens_per_row * cfg.moe_top_k * cfg.capacity_factor
                  / cfg.num_experts)
    return max(8 * math.ceil(c / 8), 8)  # lane-aligned


def _n_experts(cfg) -> int:
    return max(cfg.num_experts_padded, cfg.num_experts)


def init_moe(key, cfg):
    ks = jax.random.split(key, 6)
    d, f, E = cfg.d_model, cfg.d_ff, _n_experts(cfg)
    p, s = {}, {}
    p["router"], s["router"] = dense_init(ks[0], d, E, ("embed", None))
    scale = 1.0 / math.sqrt(d)
    shape = (E, d, f)
    p["wi"] = scale * jax.random.truncated_normal(ks[1], -2, 2, shape, jnp.float32)
    p["wg"] = scale * jax.random.truncated_normal(ks[2], -2, 2, shape, jnp.float32)
    p["wo"] = (1.0 / math.sqrt(f)) * jax.random.truncated_normal(
        ks[3], -2, 2, (E, f, d), jnp.float32)
    s["wi"] = ("expert", "embed", "mlp")
    s["wg"] = ("expert", "embed", "mlp")
    s["wo"] = ("expert", "mlp", "embed")
    if cfg.num_shared_experts:
        p["shared"], s["shared"] = init_mlp(
            ks[4], cfg, d_ff=cfg.num_shared_experts * cfg.d_ff)
    return p, s


def apply_moe(p, x, cfg):
    """x: [B, S, D] -> ([B, S, D], aux_losses dict)."""
    B, S, D = x.shape
    E, k = _n_experts(cfg), cfg.moe_top_k
    C = moe_capacity(S, cfg)
    dt = x.dtype

    logits = (x @ p["router"].astype(dt)).astype(jnp.float32)   # [B,S,E]
    if E > cfg.num_experts:  # padded experts are masked out of routing
        pad_mask = (jnp.arange(E) >= cfg.num_experts) * -1e30
        logits = logits + pad_mask
    gates = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(gates, k)                        # [B,S,k]
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)

    # --- load-balancing + z losses (Switch-style) ---
    me = jnp.mean(gates, axis=(0, 1))                           # [E]
    onehot_top = jax.nn.one_hot(topi, E, dtype=jnp.float32)     # [B,S,k,E]
    ce = jnp.mean(onehot_top.sum(2), axis=(0, 1))               # frac routed
    aux_loss = E * jnp.sum(me * ce)
    z_loss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)

    # --- slot assignment: per-row cumsum over the flattened (S*k) choices ---
    flat_e = topi.reshape(B, S * k)                             # [B, S*k]
    oh = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)             # [B, S*k, E]
    pos = jnp.cumsum(oh, axis=1) - 1                            # prior count
    slot = jnp.take_along_axis(pos, flat_e[..., None], axis=-1)[..., 0]
    keep = slot < C
    dest = jnp.where(keep, flat_e * C + slot, E * C)            # OOB => dropped

    # --- scatter tokens to [B, E*C, D] expert buffers ---
    xk = jnp.repeat(x, k, axis=1)                               # [B, S*k, D]

    def scatter_row(dst_idx, vals):
        buf = jnp.zeros((E * C + 1, D), vals.dtype)
        return buf.at[dst_idx].add(vals, mode="drop")[:-1]

    expert_in = jax.vmap(scatter_row)(dest, xk)                 # [B, E*C, D]
    expert_in = expert_in.reshape(B, E, C, D)

    # --- expert FFN (swiglu), E sharded on "model" axis ---
    h = jnp.einsum("becd,edf->becf", expert_in, p["wi"].astype(dt))
    g = jnp.einsum("becd,edf->becf", expert_in, p["wg"].astype(dt))
    h = jax.nn.silu(g) * h
    expert_out = jnp.einsum("becf,efd->becd", h, p["wo"].astype(dt))
    expert_out = expert_out.reshape(B, E * C, D)

    # --- gather back + combine with (renormalized) gate weights ---
    def gather_row(buf, idx):
        return jnp.take(buf, idx, axis=0, mode="fill", fill_value=0)

    back = jax.vmap(gather_row)(expert_out, jnp.where(keep, dest, E * C))
    wts = (topv.reshape(B, S * k) * keep.astype(jnp.float32)).astype(dt)
    out = (back * wts[..., None]).reshape(B, S, k, D).sum(axis=2)

    if cfg.num_shared_experts:
        out = out + apply_mlp(p["shared"], x, cfg)
    return out, {"moe_aux": aux_loss, "moe_z": z_loss}
