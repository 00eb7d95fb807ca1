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
    """x: (B, S, d).  Returns (x, new_kv) — new_kv None without a cache.

    A prompt (S > 1) takes this layer's ``(k_cache, v_cache)`` and writes
    its rows at ``cache_pos``.  One decode token (S == 1) takes
    ``((k_stack, v_stack), i)``: every layer's stacked caches and this
    layer's index.  It writes its row into layer i in place and attends
    to layer i where it lies in the stack."""
    h = _norm(cfg, x, p["ln_attn"])
    q, k, v = cm.qkv_project(cfg, p["attn"], h, positions)

    new_kv = None
    if kv_cache is None:
        ctx = cm.attention(cfg, q, k, v, causal=True, window=window)
    elif q.shape[2] == 1:                        # decode: one new token
        from repro.kernels.attention.ops import decode_attention
        (k_stack, v_stack), i = kv_cache
        new_kv = cm.cache_write_row(k_stack, v_stack, k, v, i, cache_pos)
        with jax.named_scope("attention"):
            k_cache, v_cache = (jax.lax.dynamic_index_in_dim(
                c, i, keepdims=False) for c in new_kv)
            ctx = decode_attention(
                q, k_cache, v_cache, cache_pos + 1,
                sm_scale=cfg.sm_scale, window=window,
                softcap=cfg.attn_softcap)
    else:                                        # prefill writes + attends
        new_kv = cm.cache_update(*kv_cache, k, v, cache_pos)
        ctx = cm.prefill_attention(cfg, q, k, v, window=window)

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
    order (so gemma2's (local, global) pairs stay interleaved).  A
    prompt's KV caches are threaded through the scan as per-group xs and
    ys.  One decode token's stacked caches ride in the carry with the
    layer index instead, and each layer writes its row into them in
    place: no step copies a whole cache."""
    wins = _windows(cfg)

    def blocks(x, lps, kvs):
        new_kvs = []
        for lp, kv, window in zip(lps, kvs, wins):
            x, new_kv = block_apply(cfg, lp, x, positions=positions,
                                    window=window, kv_cache=kv,
                                    cache_pos=cache_pos)
            new_kvs.append(new_kv)
        return x, tuple(new_kvs)

    in_place = caches is not None and x.shape[1] == 1
    if in_place:
        from repro.obs import default_registry
        default_registry().counter("kv_cache_inplace_steps_total").inc()

        def body(carry, lps):
            x, stacks, i = carry
            x, stacks = blocks(x, lps, [(kv, i) for kv in stacks])
            return (x, stacks, i + 1), None

        init, xs = (x, caches, jnp.int32(0)), params["layers"]
    elif caches is not None:
        def body(x, layer):
            return blocks(x, *layer)

        init, xs = x, (params["layers"], caches)
    else:
        def body(x, lps):
            return blocks(x, lps, (None,) * len(wins))[0], None

        init, xs = x, params["layers"]

    if cfg.remat != "none":
        body = jax.checkpoint(body, policy=cm.remat_policy(cfg),
                              prevent_cse=False)
    out, ys = jax.lax.scan(body, init, xs)
    return out[:2] if in_place else (out, ys)


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
