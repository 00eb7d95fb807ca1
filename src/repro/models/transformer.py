"""Generic decoder-only transformer family.

One implementation, flag-driven, covers five assigned architectures:
  * gemma2-2b / gemma2-27b — sandwich norms, GeGLU, logit soft-caps,
    alternating local(4096)/global attention, tied + scaled embeddings;
  * deepseek-67b / yi-6b — llama arch (pre-RMSNorm, SwiGLU, RoPE GQA);
  * internvl2-1b — Qwen2 backbone (QKV bias) + stub ViT prefix tokens;
  * olmoe-1b-7b — QK-norm + 64-expert top-8 MoE;
  * arctic-480b — 128-expert top-2 MoE + parallel dense residual MLP.

Layers are stacked and scanned (``lax.scan`` over layer parameters) so
HLO size is depth-independent; gemma2's alternating pattern scans
(local, global) *pairs*.  Activation remat wraps the scan body.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.distributed.logical import constrain
from repro.models import common as cm
from repro.models.base import ArchConfig, register_family
from repro.models import moe as moe_lib


# ---------------------------------------------------------------------------
# One block.
# ---------------------------------------------------------------------------

def block_init(cfg: ArchConfig, key):
    ks = jax.random.split(key, 4)
    p = {
        "attn": cm.attn_init(cfg, ks[0]),
        "ln_attn": jnp.zeros((cfg.d_model,), cfg.dtype),
        "ln_mlp": jnp.zeros((cfg.d_model,), cfg.dtype),
    }
    if not cfg.rmsnorm_unit_offset:
        p["ln_attn"] = jnp.ones((cfg.d_model,), cfg.dtype)
        p["ln_mlp"] = jnp.ones((cfg.d_model,), cfg.dtype)
    if cfg.sandwich_norms:
        zero = jnp.zeros if cfg.rmsnorm_unit_offset else jnp.ones
        p["ln_attn_post"] = zero((cfg.d_model,), cfg.dtype)
        p["ln_mlp_post"] = zero((cfg.d_model,), cfg.dtype)
    if cfg.moe is not None:
        p["moe"] = moe_lib.moe_init(cfg, ks[1])
    else:
        p["mlp"] = cm.mlp_init(cfg, ks[1])
    return p


def _norm(cfg, x, w):
    return cm.rmsnorm(x, w, cfg.rms_eps, cfg.rmsnorm_unit_offset)


def block_apply(cfg: ArchConfig, p, x, *, positions, window: int,
                kv_cache=None, cache_pos=None):
    """x: (B, S, d).  Returns (x, new_kv) — new_kv None outside decode."""
    h = _norm(cfg, x, p["ln_attn"])
    q, k, v = cm.qkv_project(cfg, p["attn"], h, positions)

    new_kv = None
    if kv_cache is not None:
        k_cache, v_cache = kv_cache
        k_cache, v_cache = cm.cache_update(k_cache, v_cache, k, v, cache_pos)
        new_kv = (k_cache, v_cache)
        if q.shape[2] == 1:                      # decode: one new token
            from repro.kernels.attention.ops import decode_attention
            with jax.named_scope("attention"):
                ctx = decode_attention(
                    q, k_cache, v_cache, cache_pos + 1,
                    sm_scale=cfg.sm_scale, window=window,
                    softcap=cfg.attn_softcap)
        else:                                    # prefill writes + attends
            ctx = cm.prefill_attention(cfg, q, k, v, window=window)
    else:
        ctx = cm.attention(cfg, q, k, v, causal=True, window=window)

    attn_out = cm.attn_out(cfg, p["attn"], ctx)
    if cfg.sandwich_norms:
        attn_out = _norm(cfg, attn_out, p["ln_attn_post"])
    x = x + attn_out
    x = constrain(x, ("batch", "seq", "embed"))

    h = _norm(cfg, x, p["ln_mlp"])
    if cfg.moe is not None:
        mlp_out = moe_lib.moe_apply(cfg, p["moe"], h)
    else:
        mlp_out = cm.mlp_apply(cfg, p["mlp"], h)
    if cfg.sandwich_norms:
        mlp_out = _norm(cfg, mlp_out, p["ln_mlp_post"])
    x = x + mlp_out
    return constrain(x, ("batch", "seq", "embed")), new_kv


# ---------------------------------------------------------------------------
# Layer stacking: uniform scan or gemma2 (local, global) pairs.
# ---------------------------------------------------------------------------

def _stack_init(cfg: ArchConfig, key, n: int):
    keys = jax.random.split(key, n)
    return jax.vmap(lambda k: block_init(cfg, k))(keys)


def _windows(cfg: ArchConfig):
    if cfg.layer_pattern == "gemma2_alt":
        return (cfg.window, 0)                   # local then global
    return (cfg.window,)


def init(cfg: ArchConfig, key):
    ks = jax.random.split(key, 4)
    v = cfg.padded_vocab
    params = {
        "embedding": cm.embed_init(ks[0], (v, cfg.d_model), cfg.dtype),
        "ln_final": (jnp.zeros if cfg.rmsnorm_unit_offset else jnp.ones)(
            (cfg.d_model,), cfg.dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = cm.dense_init(ks[1], (cfg.d_model, v), cfg.dtype)
    wins = _windows(cfg)
    group = len(wins)
    assert cfg.n_layers % group == 0, (cfg.n_layers, group)
    layer_keys = jax.random.split(ks[2], group)
    params["layers"] = tuple(
        _stack_init(cfg, layer_keys[i], cfg.n_layers // group)
        for i in range(group))
    return params


def _scan_blocks(cfg: ArchConfig, params, x, *, positions, caches=None,
                 cache_pos=None):
    """One scan over layer *groups*; each step applies the whole group in
    order (so gemma2's (local, global) pairs stay interleaved).  KV caches
    are threaded through the scan as per-group ys."""
    wins = _windows(cfg)
    policy = cm.remat_policy(cfg)

    def body(carry, layer):
        x = carry
        lps, kvs = layer if caches is not None else (layer, None)
        new_kvs = [] if caches is not None else None
        for i, window in enumerate(wins):
            kv = kvs[i] if kvs is not None else None
            x, new_kv = block_apply(cfg, lps[i], x, positions=positions,
                                    window=window, kv_cache=kv,
                                    cache_pos=cache_pos)
            if new_kvs is not None:
                new_kvs.append(new_kv)
        return x, (tuple(new_kvs) if new_kvs is not None else None)

    if cfg.remat != "none":
        body = jax.checkpoint(body, policy=policy, prevent_cse=False)

    xs = (params["layers"], caches) if caches is not None else params["layers"]
    x, ys = jax.lax.scan(body, x, xs)
    return x, ys


# ---------------------------------------------------------------------------
# Public protocol.
# ---------------------------------------------------------------------------

def _embed_inputs(cfg: ArchConfig, params, batch):
    tokens = batch["tokens"]
    x = cm.embed_tokens(cfg, params["embedding"], tokens)
    if cfg.vision_prefix:
        # Stub ViT frontend: precomputed patch embeddings replace the
        # first ``vision_prefix`` positions (assignment: frontend is a
        # stub; ``input_specs()`` supplies the embeddings).
        vis = batch["vision_embeds"].astype(x.dtype)
        x = jnp.concatenate([vis, x[:, cfg.vision_prefix:]], axis=1)
    return x


def forward(cfg: ArchConfig, params, batch, return_hidden: bool = False):
    """Full-sequence forward (training / evaluation)."""
    x = _embed_inputs(cfg, params, batch)
    positions = jnp.arange(x.shape[1])
    x, _ = _scan_blocks(cfg, params, x, positions=positions)
    x = _norm(cfg, x, params["ln_final"])
    if return_hidden:
        return x
    return cm.logits_out(cfg, params, x)


def init_cache(cfg: ArchConfig, batch_size: int, max_len: int,
               dtype=None):
    dtype = dtype or cfg.kv_cache_dtype
    wins = _windows(cfg)
    group = len(wins)
    n = cfg.n_layers // group
    shape = (n, batch_size, cfg.n_kv_heads, max_len, cfg.head_dim)
    return tuple((jnp.zeros(shape, dtype), jnp.zeros(shape, dtype))
                 for _ in range(group))


def prefill(cfg: ArchConfig, params, batch, cache):
    """Process the prompt, fill the cache, return last-position logits."""
    x = _embed_inputs(cfg, params, batch)
    positions = jnp.arange(x.shape[1])
    x, cache = _scan_blocks(cfg, params, x, positions=positions,
                            caches=cache, cache_pos=0)
    x = _norm(cfg, x, params["ln_final"])
    return cm.logits_out(cfg, params, x[:, -1]), cache


def decode_step(cfg: ArchConfig, params, tokens, cache, pos):
    """tokens: (B, 1); pos: scalar current length.  One decode step."""
    x = cm.embed_tokens(cfg, params["embedding"], tokens)
    positions = jnp.full((tokens.shape[0], 1), pos, jnp.int32)
    x, cache = _scan_blocks(cfg, params, x, positions=positions,
                            caches=cache, cache_pos=pos)
    x = _norm(cfg, x, params["ln_final"])
    return cm.logits_out(cfg, params, x[:, -1]), cache


import sys as _sys  # noqa: E402

register_family("transformer")(_sys.modules[__name__])
