"""Model assembly for all architecture families.

Public API:
  init_model(key, cfg)            -> (params, specs)  (specs: logical axes)
  loss_fn(params, cfg, batch)     -> (loss, metrics)  (training forward)
  make_cache(cfg, batch, max_len) -> decode cache pytree
  serve_step(params, cfg, tokens, cache, index) -> (logits, new_cache)

Layer stacks are scanned (stacked params, leading "layers" axis) with
optional per-layer remat — compile time and HLO size stay O(1) in depth.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import layers as L
from . import mamba as M
from . import moe as MOE
from .config import ModelConfig

ACT_DTYPE = jnp.bfloat16


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _stack_init(key, n, init_one):
    keys = jax.random.split(key, n)
    params = jax.vmap(lambda k: init_one(k)[0])(keys)
    _, spec = init_one(key)  # spec tree (leaves = tuples of logical axes)
    # prepend the (scanned, unsharded) layers axis to every leaf spec
    spec = jax.tree.map(lambda s: (None,) + tuple(s), spec,
                        is_leaf=lambda s: isinstance(s, tuple))
    return params, spec


def _init_layer(key, cfg: ModelConfig):
    """One decoder layer of the cfg's family (params, specs)."""
    p, s = {}, {}
    k1, k2, k3, k4 = jax.random.split(key, 4)
    if cfg.family in ("dense", "moe", "encoder", "vlm"):
        p["ln1"], s["ln1"] = L.init_norm(cfg.norm_kind, cfg.d_model)
        p["attn"], s["attn"] = L.init_attention(k1, cfg)
        p["ln2"], s["ln2"] = L.init_norm(cfg.norm_kind, cfg.d_model)
        if cfg.family == "moe":
            p["moe"], s["moe"] = MOE.init_moe(k2, cfg)
        else:
            p["mlp"], s["mlp"] = L.init_mlp(k2, cfg)
    elif cfg.family == "ssm":
        p["ln1"], s["ln1"] = L.init_norm(cfg.norm_kind, cfg.d_model)
        p["mamba"], s["mamba"] = M.init_mamba1(k1, cfg)
    elif cfg.family == "hybrid":
        p["ln1"], s["ln1"] = L.init_norm(cfg.norm_kind, cfg.d_model)
        p["mamba"], s["mamba"] = M.init_mamba2(k1, cfg)
    else:
        raise ValueError(cfg.family)
    return p, s


def init_model(key, cfg: ModelConfig):
    kemb, klay, kshared, khead = jax.random.split(key, 4)
    p, s = {}, {}
    p["emb"], s["emb"] = L.init_embedding(kemb, cfg)
    p["layers"], s["layers"] = _stack_init(klay, cfg.num_layers,
                                           partial(_init_layer, cfg=cfg))
    p["ln_f"], s["ln_f"] = L.init_norm(cfg.norm_kind, cfg.d_model)
    if cfg.family == "hybrid":
        sp, ss = {}, {}
        sp["ln1"], ss["ln1"] = L.init_norm(cfg.norm_kind, cfg.d_model)
        sp["attn"], ss["attn"] = L.init_attention(kshared, cfg)
        sp["ln2"], ss["ln2"] = L.init_norm(cfg.norm_kind, cfg.d_model)
        sp["mlp"], ss["mlp"] = L.init_mlp(khead, cfg)
        p["shared"], s["shared"] = sp, ss
    return p, s


# ---------------------------------------------------------------------------
# forward (training) — full-sequence
# ---------------------------------------------------------------------------

def _transformer_layer(lp, x, cfg, positions):
    x = L.shard_tokens(x, cfg.constrain_acts)
    h = L.apply_norm(lp["ln1"], x, cfg.norm_kind, cfg.norm_eps)
    a, _ = L.apply_attention(lp["attn"], h, cfg, positions)
    x = L.shard_tokens(x + a, cfg.constrain_acts)
    h = L.apply_norm(lp["ln2"], x, cfg.norm_kind, cfg.norm_eps)
    if "moe" in lp:
        m, aux = MOE.apply_moe(lp["moe"], h, cfg)
    else:
        m, aux = L.apply_mlp(lp["mlp"], h, cfg), {}
    return L.shard_tokens(x + m, cfg.constrain_acts), aux


def _ssm_layer(lp, x, cfg):
    h = L.apply_norm(lp["ln1"], x, cfg.norm_kind, cfg.norm_eps)
    kind = cfg.ssm_kind
    if kind == "mamba1":
        y, _ = M.apply_mamba1(lp["mamba"], h, cfg)
    else:
        y, _ = M.apply_mamba2(lp["mamba"], h, cfg)
    return x + y


def _run_stack(params, cfg, x, positions):
    """Scan layers; returns (hidden, aux_losses)."""
    zero_aux = {"moe_aux": jnp.float32(0), "moe_z": jnp.float32(0)}

    if cfg.family in ("dense", "moe", "encoder", "vlm"):
        def body(carry, lp):
            x, aux = carry
            x2, a = _transformer_layer(lp, x, cfg, positions)
            aux = {k: aux[k] + a.get(k, 0.0) for k in aux}
            return (x2, aux), None
        if cfg.remat:
            body = jax.checkpoint(body)
        (x, aux), _ = jax.lax.scan(body, (x, zero_aux), params["layers"])
        return x, aux

    if cfg.family == "ssm":
        def body(x, lp):
            return _ssm_layer(lp, x, cfg), None
        if cfg.remat:
            body = jax.checkpoint(body)
        x, _ = jax.lax.scan(body, x, params["layers"])
        return x, zero_aux

    # hybrid: groups of attn_every mamba2 layers + shared attn/mlp block
    n_groups = cfg.num_layers // cfg.attn_every
    assert n_groups * cfg.attn_every == cfg.num_layers
    grouped = jax.tree.map(
        lambda t: t.reshape(n_groups, cfg.attn_every, *t.shape[1:]),
        params["layers"])
    shared = params["shared"]

    def inner(x, lp):
        return _ssm_layer(lp, x, cfg), None

    def group_body(x, gp):
        x, _ = jax.lax.scan(inner, x, gp)
        h = L.apply_norm(shared["ln1"], x, cfg.norm_kind, cfg.norm_eps)
        a, _ = L.apply_attention(shared["attn"], h, cfg, positions)
        x = x + a
        h = L.apply_norm(shared["ln2"], x, cfg.norm_kind, cfg.norm_eps)
        x = x + L.apply_mlp(shared["mlp"], h, cfg)
        return x, None

    if cfg.remat:
        group_body = jax.checkpoint(group_body)
    x, _ = jax.lax.scan(group_body, x, grouped)
    return x, zero_aux


def _inputs_to_hidden(params, cfg, batch):
    """Embed per-family inputs -> (hidden [B,S,D], positions, labels, mask)."""
    if cfg.family == "encoder":
        x = batch["frames"].astype(ACT_DTYPE)           # [B,S,D] stub frontend
        B, S, _ = x.shape
        positions = jnp.broadcast_to(jnp.arange(S), (B, S))
        return x, positions, batch["labels"], jnp.ones((B, S), bool)
    tokens = batch["tokens"]
    B, S_tok = tokens.shape
    x = L.embed_tokens(params["emb"], tokens, ACT_DTYPE)
    if cfg.family == "vlm":
        patches = batch["patches"].astype(ACT_DTYPE)    # [B,P,D] stub frontend
        x = jnp.concatenate([patches, x], axis=1)
        P = patches.shape[1]
        S = S_tok + P
        text_mask = jnp.concatenate(
            [jnp.zeros((B, P), bool), jnp.ones((B, S_tok), bool)], axis=1)
    else:
        S = S_tok
        text_mask = jnp.ones((B, S), bool)
    positions = jnp.broadcast_to(jnp.arange(S), (B, S))
    # next-token labels over the combined sequence
    pad = jnp.zeros((B, 1), tokens.dtype)
    full_tokens = (jnp.concatenate([jnp.zeros((B, S - S_tok), tokens.dtype),
                                    tokens], axis=1)
                   if S != S_tok else tokens)
    labels = jnp.concatenate([full_tokens[:, 1:], pad], axis=1)
    mask = text_mask & (jnp.arange(S) < S - 1)[None, :]
    if "loss_mask" in batch and cfg.family != "vlm":
        mask = mask & batch["loss_mask"].astype(bool)
    return x, positions, labels, mask


def forward_logits(params, cfg: ModelConfig, batch):
    """Full-sequence logits [B, S, V] — small models / tests only."""
    x, positions, _, _ = _inputs_to_hidden(params, cfg, batch)
    x, _ = _run_stack(params, cfg, x, positions)
    x = L.apply_norm(params["ln_f"], x, cfg.norm_kind, cfg.norm_eps)
    W = L.unembed_matrix(params["emb"])
    logits = jnp.einsum("bsd,vd->bsv", x.astype(jnp.float32),
                        W.astype(jnp.float32))
    if cfg.vocab_padded > cfg.vocab_size:
        logits = logits + (jnp.arange(cfg.vocab_padded)
                           >= cfg.vocab_size) * -1e30
    return logits


def loss_fn(params, cfg: ModelConfig, batch):
    x, positions, labels, mask = _inputs_to_hidden(params, cfg, batch)
    x, aux = _run_stack(params, cfg, x, positions)
    x = L.apply_norm(params["ln_f"], x, cfg.norm_kind, cfg.norm_eps)
    ce = L.chunked_ce_loss(params["emb"], x, labels, mask, cfg.loss_chunk,
                           vocab_size=cfg.vocab_size)
    loss = ce + 0.01 * aux["moe_aux"] + 0.001 * aux["moe_z"]
    return loss, {"ce": ce, **aux}


# ---------------------------------------------------------------------------
# decode (serve_step)
# ---------------------------------------------------------------------------

def make_cache(cfg: ModelConfig, batch: int, max_len: int, dtype=ACT_DTYPE):
    Lr = cfg.num_layers
    if cfg.family in ("dense", "moe", "vlm"):
        kv = lambda: jnp.zeros((Lr, batch, max_len, cfg.num_kv_heads,
                                cfg.head_dim), dtype)
        return {"k": kv(), "v": kv()}
    if cfg.family == "ssm":
        st = M.mamba1_state(cfg, batch, dtype)
        return jax.tree.map(
            lambda t: jnp.zeros((Lr, *t.shape), t.dtype), st)
    if cfg.family == "hybrid":
        st = M.mamba2_state(cfg, batch, dtype)
        n_groups = cfg.num_layers // cfg.attn_every
        return {
            "mamba": jax.tree.map(
                lambda t: jnp.zeros((Lr, *t.shape), t.dtype), st),
            "k": jnp.zeros((n_groups, batch, max_len, cfg.num_kv_heads,
                            cfg.head_dim), dtype),
            "v": jnp.zeros((n_groups, batch, max_len, cfg.num_kv_heads,
                            cfg.head_dim), dtype),
        }
    raise ValueError(f"{cfg.family} has no decode step")


def serve_step(params, cfg: ModelConfig, tokens, cache, index):
    """One decode step. tokens: [B] int32; index: current length (scalar).

    Returns (logits [B, vocab], new_cache).
    """
    B = tokens.shape[0]
    x = L.embed_tokens(params["emb"], tokens[:, None], ACT_DTYPE)  # [B,1,D]
    positions = jnp.full((B, 1), index, jnp.int32)

    if cfg.family in ("dense", "moe", "vlm"):
        def body(x, xs):
            lp, ck, cv = xs
            h = L.apply_norm(lp["ln1"], x, cfg.norm_kind, cfg.norm_eps)
            a, nc = L.apply_attention(lp["attn"], h, cfg, positions,
                                      cache={"k": ck, "v": cv},
                                      cache_index=index)
            x = x + a
            h = L.apply_norm(lp["ln2"], x, cfg.norm_kind, cfg.norm_eps)
            if "moe" in lp:
                m, _ = MOE.apply_moe(lp["moe"], h, cfg)
            else:
                m = L.apply_mlp(lp["mlp"], h, cfg)
            return x + m, (nc["k"], nc["v"])

        x, (nk, nv) = jax.lax.scan(body, x,
                                   (params["layers"], cache["k"], cache["v"]))
        new_cache = {"k": nk, "v": nv}

    elif cfg.family == "ssm":
        def body(x, xs):
            lp, st = xs
            h = L.apply_norm(lp["ln1"], x, cfg.norm_kind, cfg.norm_eps)
            y, ns = M.apply_mamba1(lp["mamba"], h, cfg, state=st)
            return x + y, ns

        x, new_cache = jax.lax.scan(body, x, (params["layers"], cache))

    else:  # hybrid
        n_groups = cfg.num_layers // cfg.attn_every
        grouped = jax.tree.map(
            lambda t: t.reshape(n_groups, cfg.attn_every, *t.shape[1:]),
            params["layers"])
        gstates = jax.tree.map(
            lambda t: t.reshape(n_groups, cfg.attn_every, *t.shape[1:]),
            cache["mamba"])
        shared = params["shared"]

        def inner(x, xs):
            lp, st = xs
            h = L.apply_norm(lp["ln1"], x, cfg.norm_kind, cfg.norm_eps)
            y, ns = M.apply_mamba2(lp["mamba"], h, cfg, state=st)
            return x + y, ns

        def group_body(x, xs):
            gp, gst, ck, cv = xs
            x, nst = jax.lax.scan(inner, x, (gp, gst))
            h = L.apply_norm(shared["ln1"], x, cfg.norm_kind, cfg.norm_eps)
            a, nc = L.apply_attention(shared["attn"], h, cfg, positions,
                                      cache={"k": ck, "v": cv},
                                      cache_index=index)
            x = x + a
            h = L.apply_norm(shared["ln2"], x, cfg.norm_kind, cfg.norm_eps)
            x = x + L.apply_mlp(shared["mlp"], h, cfg)
            return x, (nst, nc["k"], nc["v"])

        x, (nmamba, nk, nv) = jax.lax.scan(
            group_body, x, (grouped, gstates, cache["k"], cache["v"]))
        new_cache = {
            "mamba": jax.tree.map(
                lambda t: t.reshape(cfg.num_layers, *t.shape[2:]), nmamba),
            "k": nk, "v": nv}

    x = L.apply_norm(params["ln_f"], x, cfg.norm_kind, cfg.norm_eps)
    logits = L.logits_last(params["emb"], x[:, 0], cfg.vocab_size)
    return logits, new_cache


# ---------------------------------------------------------------------------
# prefill: full-sequence forward that also emits the decode cache
# ---------------------------------------------------------------------------

def prefill(params, cfg: ModelConfig, batch):
    """Forward the prompt and build the decode cache (inference prefill).

    Returns (logits [B, Vp] for the last position, cache compatible with
    serve_step at max_len = S).
    """
    x, positions, _, _ = _inputs_to_hidden(params, cfg, batch)
    B, S, _ = x.shape

    if cfg.family in ("dense", "moe", "vlm", "encoder"):
        def body(x, lp):
            x = L.shard_tokens(x, cfg.constrain_acts)
            h = L.apply_norm(lp["ln1"], x, cfg.norm_kind, cfg.norm_eps)
            dt = x.dtype
            H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
            q = (h @ lp["attn"]["wq"].astype(dt)).reshape(B, S, H, hd)
            k = (h @ lp["attn"]["wk"].astype(dt)).reshape(B, S, K, hd)
            v = (h @ lp["attn"]["wv"].astype(dt)).reshape(B, S, K, hd)
            if cfg.qkv_bias:
                q = q + lp["attn"]["bq"].astype(dt).reshape(1, 1, H, hd)
                k = k + lp["attn"]["bk"].astype(dt).reshape(1, 1, K, hd)
                v = v + lp["attn"]["bv"].astype(dt).reshape(1, 1, K, hd)
            q = L.shard_heads(L.rope(q, positions, cfg.rope_theta),
                              cfg.constrain_acts)
            k = L.shard_heads(L.rope(k, positions, cfg.rope_theta),
                              cfg.constrain_acts)
            v = L.shard_heads(v, cfg.constrain_acts)
            a = L.chunked_attention(q, k, v, causal=cfg.causal,
                                    chunk=cfg.attn_chunk)
            x = L.shard_tokens(
                x + a.reshape(B, S, H * hd) @ lp["attn"]["wo"].astype(dt),
                cfg.constrain_acts)
            h = L.apply_norm(lp["ln2"], x, cfg.norm_kind, cfg.norm_eps)
            if "moe" in lp:
                m, _ = MOE.apply_moe(lp["moe"], h, cfg)
            else:
                m = L.apply_mlp(lp["mlp"], h, cfg)
            return (L.shard_tokens(x + m, cfg.constrain_acts),
                    (k.astype(ACT_DTYPE), v.astype(ACT_DTYPE)))

        if cfg.remat:
            body = jax.checkpoint(body)
        x, (ks, vs) = jax.lax.scan(body, x, params["layers"])
        # encoders have no decode step: the "prefill" cell is the plain
        # inference forward; no cache is produced
        cache = {} if cfg.family == "encoder" else {"k": ks, "v": vs}

    elif cfg.family == "ssm":
        def body(x, lp):
            h = L.apply_norm(lp["ln1"], x, cfg.norm_kind, cfg.norm_eps)
            y, st = M.apply_mamba1(lp["mamba"], h, cfg, return_state=True)
            return x + y, st
        if cfg.remat:
            body = jax.checkpoint(body)
        x, states = jax.lax.scan(body, x, params["layers"])
        cache = states

    else:  # hybrid
        n_groups = cfg.num_layers // cfg.attn_every
        grouped = jax.tree.map(
            lambda t: t.reshape(n_groups, cfg.attn_every, *t.shape[1:]),
            params["layers"])
        shared = params["shared"]

        def inner(x, lp):
            h = L.apply_norm(lp["ln1"], x, cfg.norm_kind, cfg.norm_eps)
            y, st = M.apply_mamba2(lp["mamba"], h, cfg, return_state=True)
            return x + y, st

        def group_body(x, gp):
            x, sts = jax.lax.scan(inner, x, gp)
            h = L.apply_norm(shared["ln1"], x, cfg.norm_kind, cfg.norm_eps)
            dt = x.dtype
            H, K, hd = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
            q = (h @ shared["attn"]["wq"].astype(dt)).reshape(B, S, H, hd)
            k = (h @ shared["attn"]["wk"].astype(dt)).reshape(B, S, K, hd)
            v = (h @ shared["attn"]["wv"].astype(dt)).reshape(B, S, K, hd)
            q = L.rope(q, positions, cfg.rope_theta)
            k = L.rope(k, positions, cfg.rope_theta)
            a = L.chunked_attention(q, k, v, causal=True, chunk=cfg.attn_chunk)
            x = x + a.reshape(B, S, H * hd) @ shared["attn"]["wo"].astype(dt)
            h = L.apply_norm(shared["ln2"], x, cfg.norm_kind, cfg.norm_eps)
            x = x + L.apply_mlp(shared["mlp"], h, cfg)
            return x, (sts, k.astype(ACT_DTYPE), v.astype(ACT_DTYPE))

        if cfg.remat:
            group_body = jax.checkpoint(group_body)
        x, (sts, ks, vs) = jax.lax.scan(group_body, x, grouped)
        cache = {
            "mamba": jax.tree.map(
                lambda t: t.reshape(cfg.num_layers, *t.shape[2:]), sts),
            "k": ks, "v": vs}

    x = L.apply_norm(params["ln_f"], x, cfg.norm_kind, cfg.norm_eps)
    logits = L.logits_last(params["emb"], x[:, -1], cfg.vocab_size)
    return logits, cache
