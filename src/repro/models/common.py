"""Shared layers for the model zoo.

Every matmul in this file goes through ``core.fusion`` (``cute_matmul`` /
``linear``) so the paper's fused-epilogue contract applies framework-wide;
it takes fusion's process-wide route, as every family's matmuls do.
Attention offers three implementations:

* ``xla``    — chunked online-softmax in pure jnp (lax.scan over KV
  blocks).  This is the distributed/dry-run path: HLO stays compact at
  32k+ context, FLOPs are visible to ``cost_analysis``, GSPMD shards it.
* ``pallas`` — the ``kernels/attention`` flash kernel (interpret-mode on
  CPU; the on-chip path on real TPUs).
* ``dense``  — the reference oracle, for tiny smoke tests only.

A cached prefill takes ``prefill_attention`` instead, which picks its
route by platform alone.

The layer helpers run under ``jax.named_scope``s (``embed``, ``norm``,
``qkv``, ``cache_update``, ``attention``, ``attn_out``, ``mlp``,
``head``), so every family that calls them gets its ops named in the
compiled program's ``op_name`` metadata; scopes change nothing else.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core.fusion import linear
from repro.distributed.logical import constrain
from repro.models.base import ArchConfig


# ---------------------------------------------------------------------------
# Initializers.
# ---------------------------------------------------------------------------

# Both draw directly in the parameter dtype: a float32 draw cast to bf16
# would briefly hold twice the model in device memory.

def dense_init(key, shape, dtype, in_axis: int = 0):
    fan_in = shape[in_axis]
    return jax.random.normal(key, shape, dtype) * jnp.asarray(
        fan_in ** -0.5, dtype)


def embed_init(key, shape, dtype):
    return jax.random.normal(key, shape, dtype) * jnp.asarray(0.02, dtype)


# ---------------------------------------------------------------------------
# Norms.
# ---------------------------------------------------------------------------

@jax.named_scope("norm")
def rmsnorm(x, w, eps: float = 1e-6, unit_offset: bool = False):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    scale = (1.0 + w.astype(jnp.float32)) if unit_offset else w.astype(jnp.float32)
    return (y * scale).astype(dt)


@jax.named_scope("norm")
def layernorm(x, w, b, eps: float = 1e-5):
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


def groupnorm_heads(x, w, b, n_heads: int, eps: float = 64e-5):
    """RWKV ln_x: GroupNorm over head groups of the flattened channel dim."""
    dt = x.dtype
    *lead, c = x.shape
    xf = x.astype(jnp.float32).reshape(*lead, n_heads, c // n_heads)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    y = ((xf - mu) * jax.lax.rsqrt(var + eps)).reshape(*lead, c)
    return (y * w.astype(jnp.float32) + b.astype(jnp.float32)).astype(dt)


# ---------------------------------------------------------------------------
# RoPE.
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                            / head_dim))


def apply_rope(x, positions, theta: float = 1e4):
    """x: (B, H, S, D); positions: (S,) or (B, S)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    pos = positions.astype(jnp.float32)
    angles = pos[..., None] * freqs                    # (..., S, D/2)
    if angles.ndim == 2:                               # (S, D/2) -> broadcast
        angles = angles[None, None]
    else:                                              # (B, S, D/2)
        angles = angles[:, None]
    sin, cos = jnp.sin(angles), jnp.cos(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Attention.
# ---------------------------------------------------------------------------

def attention_xla_chunked(q, k, v, *, sm_scale, causal=True, window=0,
                          softcap=0.0, q_start=0, chunk=1024,
                          pv_bf16=False):
    """Online-softmax attention, lax.scan over KV chunks (flash-in-XLA).

    q: (B, H, Sq, D); k/v: (B, Hkv, Sk, D).  Peak live memory is one
    (B, H, Sq, chunk) score block instead of (B, H, Sq, Sk).
    ``pv_bf16`` keeps the probability block in bf16 for the P·V product
    (fp32 accumulation) — halves the dominant transient buffer (§Perf).
    """
    b, h, sq, d = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    group = h // hkv
    chunk = min(chunk, sk)
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
    n_chunks = (sk + pad) // chunk
    qf = q.astype(jnp.float32) * sm_scale
    qf = qf.reshape(b, hkv, group * sq, d)             # fold GQA into rows
    kc = jnp.moveaxis(k.reshape(b, hkv, n_chunks, chunk, d), 2, 0)
    vc = jnp.moveaxis(v.reshape(b, hkv, n_chunks, chunk, d), 2, 0)

    qpos = q_start + jnp.tile(jnp.arange(sq), group)   # (group*Sq,)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def body(carry, inp):
        # Remat per KV chunk: the backward pass recomputes scores instead
        # of saving (B, H, Sq, chunk) residuals for every chunk step —
        # this is what makes 32k-context backward fit (§Perf memory term).
        m, l, acc, j = carry
        kj, vj = inp
        s = jnp.einsum("bnqd,bnkd->bnqk", qf, kj.astype(jnp.float32))
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        kpos = j * chunk + jnp.arange(chunk)
        mask = kpos[None, :] < sk
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            mask &= (qpos[:, None] - kpos[None, :]) < window
        s = jnp.where(mask[None, None], s, -1e30)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(mask[None, None], jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
        if pv_bf16:
            pv = jnp.einsum("bnqk,bnkd->bnqd", p.astype(jnp.bfloat16),
                            vj.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32)
        else:
            pv = jnp.einsum("bnqk,bnkd->bnqd", p, vj.astype(jnp.float32))
        acc = alpha * acc + pv
        return (m_new, l, acc, j + 1), None

    init = (jnp.full((b, hkv, group * sq, 1), -1e30, jnp.float32),
            jnp.zeros((b, hkv, group * sq, 1), jnp.float32),
            jnp.zeros((b, hkv, group * sq, d), jnp.float32),
            jnp.int32(0))
    (m, l, acc, _), _ = jax.lax.scan(body, init, (kc, vc))
    l = jnp.where(l == 0.0, 1.0, l)
    out = (acc / l).reshape(b, h, sq, d)
    return out.astype(q.dtype)


@jax.named_scope("attention")
def attention(cfg: ArchConfig, q, k, v, *, causal=True, window=0,
              softcap=None, q_start=0, sm_scale=None):
    """Backend-dispatching attention. q: (B, H, S, D), k/v: (B, Hkv, S, D)."""
    sm_scale = cfg.sm_scale if sm_scale is None else sm_scale
    softcap = cfg.attn_softcap if softcap is None else softcap
    if cfg.backend == "pallas":
        from repro.kernels.attention.ops import flash_attention
        return flash_attention(q, k, v, sm_scale=sm_scale, causal=causal,
                               window=window, softcap=softcap,
                               q_start=q_start)
    if cfg.backend == "dense":
        from repro.kernels.attention.ref import attention_ref
        return attention_ref(q, k, v, sm_scale=sm_scale, causal=causal,
                             window=window, softcap=softcap, q_start=q_start)
    return attention_xla_chunked(q, k, v, sm_scale=sm_scale, causal=causal,
                                 window=window, softcap=softcap,
                                 q_start=q_start, chunk=cfg.attn_chunk,
                                 pv_bf16=cfg.attn_pv_bf16)


@jax.named_scope("attention")
def prefill_attention(cfg: ArchConfig, q, k, v, *, window=0):
    """Causal self-attention of a cached prefill, which no gradient
    passes through.

    Where Pallas kernels compile (``resolve_interpret`` is false: a
    TPU), the flash kernel, which fetches and computes only the causal
    (and window) band of KV blocks; elsewhere the chunked scan, since a
    whole model under the kernel interpreter is too slow.  ``cfg.backend``
    is not read.  Each traced call adds its blocks to
    ``attention_prefill_blocks_total{route, kind}`` (``flash`` or
    ``chunked``; ``computed`` or ``skipped``): the flash grid's steps
    inside and outside the band, or the chunked scan's (B·Hkv, chunk)
    blocks, all computed."""
    from repro.kernels import resolve_interpret
    from repro.kernels.attention.ops import band_blocks, flash_attention
    from repro.obs import default_registry
    flash = not resolve_interpret()
    if flash:
        blocks = band_blocks(q.shape, k.shape, window=window)
    else:
        b, hkv, sk = k.shape[:3]
        blocks = (b * hkv * -(-sk // min(cfg.attn_chunk, sk)), 0)
    for kind, n in zip(("computed", "skipped"), blocks):
        default_registry().counter(
            "attention_prefill_blocks_total",
            route="flash" if flash else "chunked", kind=kind).inc(n)
    if flash:
        return flash_attention(q, k, v, sm_scale=cfg.sm_scale, causal=True,
                               window=window, softcap=cfg.attn_softcap)
    return attention_xla_chunked(
        q, k, v, sm_scale=cfg.sm_scale, causal=True, window=window,
        softcap=cfg.attn_softcap, chunk=cfg.attn_chunk,
        pv_bf16=cfg.attn_pv_bf16)


# ---------------------------------------------------------------------------
# Attention block parameters + apply (GQA, optional bias / qk-norm / RoPE).
# ---------------------------------------------------------------------------

def attn_init(cfg: ArchConfig, key, *, d_in: Optional[int] = None):
    d = d_in if d_in is not None else cfg.d_model
    ks = jax.random.split(key, 5)
    p = {
        "wq": dense_init(ks[0], (d, cfg.q_dim), cfg.dtype),
        "wk": dense_init(ks[1], (d, cfg.kv_dim), cfg.dtype),
        "wv": dense_init(ks[2], (d, cfg.kv_dim), cfg.dtype),
        "wo": dense_init(ks[3], (cfg.q_dim, d), cfg.dtype, in_axis=1),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((cfg.q_dim,), cfg.dtype)
        p["bk"] = jnp.zeros((cfg.kv_dim,), cfg.dtype)
        p["bv"] = jnp.zeros((cfg.kv_dim,), cfg.dtype)
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((cfg.head_dim,), cfg.dtype)
        p["k_norm"] = jnp.ones((cfg.head_dim,), cfg.dtype)
    return p


@jax.named_scope("qkv")
def qkv_project(cfg: ArchConfig, p, x, positions):
    """x: (B, S, d) -> q (B, H, S, hd), k/v (B, Hkv, S, hd) with RoPE."""
    b, s, _ = x.shape
    q = linear(x, p["wq"], p.get("bq"))
    k = linear(x, p["wk"], p.get("bk"))
    v = linear(x, p["wv"], p.get("bv"))
    q = q.reshape(b, s, cfg.n_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    k = k.reshape(b, s, cfg.n_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    v = v.reshape(b, s, cfg.n_kv_heads, cfg.head_dim).transpose(0, 2, 1, 3)
    if cfg.qk_norm:
        q = rmsnorm(q, p["q_norm"], cfg.rms_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rms_eps)
    if positions is not None:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    q = constrain(q, ("batch", "heads", "seq", None))
    k = constrain(k, ("batch", "kv_heads", "seq", None))
    v = constrain(v, ("batch", "kv_heads", "seq", None))
    return q, k, v


@jax.named_scope("attn_out")
def attn_out(cfg: ArchConfig, p, ctx):
    """ctx: (B, H, S, hd) -> (B, S, d)."""
    b, h, s, hd = ctx.shape
    ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    return linear(ctx, p["wo"])


# ---------------------------------------------------------------------------
# MLP.
# ---------------------------------------------------------------------------

def mlp_init(cfg: ArchConfig, key, d_ff: Optional[int] = None):
    d, ff = cfg.d_model, d_ff or cfg.d_ff
    ks = jax.random.split(key, 2)
    mult = 2 if cfg.mlp_glu else 1
    return {
        "wi": dense_init(ks[0], (d, mult * ff), cfg.dtype),
        "wo": dense_init(ks[1], (ff, d), cfg.dtype, in_axis=1),
    }


@jax.named_scope("mlp")
def mlp_apply(cfg: ArchConfig, p, x):
    h = linear(x, p["wi"], activation=cfg.mlp_activation, glu=cfg.mlp_glu)
    h = constrain(h, ("batch", "seq", "mlp"))
    return linear(h, p["wo"])


# ---------------------------------------------------------------------------
# Embedding / logits.
# ---------------------------------------------------------------------------

@jax.named_scope("embed")
def embed_tokens(cfg: ArchConfig, embedding, tokens):
    x = embedding[tokens]
    if cfg.embed_scale:
        x = x * jnp.asarray(jnp.sqrt(cfg.d_model), x.dtype)
    return x


@jax.named_scope("head")
def logits_out(cfg: ArchConfig, params, x):
    w = (params["embedding"].T if cfg.tie_embeddings
         else params["lm_head"])
    y = linear(x, w, softcap=cfg.final_softcap, out_dtype=jnp.float32)
    return constrain(y, ("batch", "seq", "vocab") if y.ndim == 3
                     else ("batch", "vocab"))


# ---------------------------------------------------------------------------
# KV cache helpers (dense ring buffer, optionally quantized dtype).
# ---------------------------------------------------------------------------

@jax.named_scope("cache_update")
def cache_update(k_cache, v_cache, k_new, v_new, pos):
    """Write (B, Hkv, S_new, D) at position ``pos`` along the S axis."""
    k_cache = jax.lax.dynamic_update_slice_in_dim(
        k_cache, k_new.astype(k_cache.dtype), pos, axis=2)
    v_cache = jax.lax.dynamic_update_slice_in_dim(
        v_cache, v_new.astype(v_cache.dtype), pos, axis=2)
    return k_cache, v_cache


@jax.named_scope("cache_update")
def cache_write_row(k_stack, v_stack, k_new, v_new, layer, pos):
    """Write one token's (B, Hkv, 1, D) into layer ``layer`` of the
    stacked (L, B, Hkv, S, D) caches at position ``pos``: one
    dynamic-update-slice per stack, which the compiler does in place."""
    def put(stack, new):
        return jax.lax.dynamic_update_slice(
            stack, new.astype(stack.dtype)[None], (layer, 0, 0, pos, 0))
    return put(k_stack, k_new), put(v_stack, v_new)


def remat_policy(cfg: ArchConfig):
    if cfg.remat == "none":
        return None
    if cfg.remat == "dots":
        return jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
    return jax.checkpoint_policies.nothing_saveable
