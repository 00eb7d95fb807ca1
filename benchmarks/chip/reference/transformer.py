"""Weights and the plain float32 reference of a Llama-style decoder.

Covers the configurations whose ``family`` is ``transformer``
(yi-6b, deepseek-llm-67b): pre-RMSNorm blocks, rotary positions (the
rotate-half form of the published code), grouped-query causal attention,
a SwiGLU MLP, a final RMSNorm and an untied output head.  It imports
nothing of the program under test.

Weights are drawn here, from a key, leaf by leaf, each normal with a
spread of one over the square root of its fan-in; the configuration's
``init_residual_scale`` (default 1) scales the two projections that
write into the residual stream (``wo``, ``wd``).  Leaf by leaf: ``layer_weights`` for
layer ``i``, ``top_weights`` for the embedding, the final norm and the
head.  ``program_params`` lays the same draws out as the program's
parameter tree in one jitted call; the reference draws them again, one
layer at a time, and computes in float32 at the highest matmul precision
so that it fits in device memory beside nothing else.

The control (``lower=True``) is the same computation with both operands
of every matmul rounded to float8 (e4m3, scaled by the operand's largest
magnitude): the precision step below the bfloat16 the configurations
state.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
WEIGHT_DTYPE = jnp.bfloat16


class Dims(NamedTuple):
    d: int
    h: int
    hkv: int
    hd: int
    ff: int
    vocab: int
    layers: int
    eps: float
    theta: float
    residual_scale: float   # extra scale of the residual branches' outputs


def dims(conf: dict) -> Dims:
    h = conf["num_attention_heads"]
    return Dims(conf["hidden_size"], h, conf["num_key_value_heads"],
                conf.get("head_dim") or conf["hidden_size"] // h,
                conf["intermediate_size"], conf["vocab_size"],
                conf["num_hidden_layers"], float(conf["rms_norm_eps"]),
                float(conf["rope_theta"]),
                float(conf.get("init_residual_scale", 1.0)))


# ---------------------------------------------------------------------------
# Weights.
# ---------------------------------------------------------------------------

def _normal(key, shape, scale):
    return jax.random.normal(key, shape, WEIGHT_DTYPE) * jnp.asarray(
        scale, WEIGHT_DTYPE)


def _norm_weight(key, n):
    # Around 1, not 1: a norm weight that is read wrongly shows.
    return (1.0 + 0.1 * jax.random.normal(key, (n,), jnp.float32)).astype(
        WEIGHT_DTYPE)


def layer_weights(t: Dims, key, i):
    """Layer ``i``'s weights: ``wi`` is [gate | up] along its columns."""
    k = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1), i), 8)
    q, kv = t.h * t.hd, t.hkv * t.hd
    return {"wq": _normal(k[0], (t.d, q), t.d ** -0.5),
            "wk": _normal(k[1], (t.d, kv), t.d ** -0.5),
            "wv": _normal(k[2], (t.d, kv), t.d ** -0.5),
            "wo": _normal(k[3], (q, t.d), t.residual_scale * q ** -0.5),
            "wi": _normal(k[4], (t.d, 2 * t.ff), t.d ** -0.5),
            "wd": _normal(k[5], (t.ff, t.d), t.residual_scale * t.ff ** -0.5),
            "ln_attn": _norm_weight(k[6], t.d),
            "ln_mlp": _norm_weight(k[7], t.d)}


def top_weights(t: Dims, key):
    k = jax.random.split(jax.random.fold_in(key, 2), 3)
    return {"embed": _normal(k[0], (t.vocab, t.d), 1.0),
            "ln_final": _norm_weight(k[1], t.d),
            "head": _normal(k[2], (t.d, t.vocab), t.d ** -0.5)}


def program_params(conf: dict, key):
    """The weights as the program's ``transformer`` parameter tree, drawn
    on the device in one jitted call."""
    t = dims(conf)

    @jax.jit
    def make(key):
        w = jax.vmap(lambda i: layer_weights(t, key, i))(
            jnp.arange(t.layers))
        top = top_weights(t, key)
        layer = {"attn": {n: w[n] for n in ("wq", "wk", "wv", "wo")},
                 "ln_attn": w["ln_attn"], "ln_mlp": w["ln_mlp"],
                 "mlp": {"wi": w["wi"], "wo": w["wd"]}}
        return {"embedding": top["embed"], "ln_final": top["ln_final"],
                "lm_head": top["head"], "layers": (layer,)}
    return make(key)


# ---------------------------------------------------------------------------
# The reference forward.
# ---------------------------------------------------------------------------

def _lower(x):
    """Round to float8 e4m3 scaled by the largest magnitude, and back."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _dot(spec, a, b, lower):
    if lower:
        a, b = _lower(a), _lower(b)
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x: (heads, S, hd), positions 0..S-1, rotate-half form."""
    s, hd = x.shape[1], x.shape[2]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, -1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, -1)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def _sequence_layer(t: Dims, lower: bool, w, x):
    """One block over one sequence x: (S, d), float32."""
    s = x.shape[0]
    h = _rmsnorm(x, w["ln_attn"], t.eps)
    q = _dot("sd,dn->sn", h, w["wq"], lower).reshape(s, t.h, t.hd)
    k = _dot("sd,dn->sn", h, w["wk"], lower).reshape(s, t.hkv, t.hd)
    v = _dot("sd,dn->sn", h, w["wv"], lower).reshape(s, t.hkv, t.hd)
    q = _rope(q.transpose(1, 0, 2), t.theta)        # (h, S, hd)
    k = _rope(k.transpose(1, 0, 2), t.theta)        # (hkv, S, hd)
    v = v.transpose(1, 0, 2)
    g = t.h // t.hkv
    causal = jnp.tril(jnp.ones((s, s), bool))

    def one_kv_head(args):                          # g query heads share it
        qg, kh, vh = args                           # (g, S, hd), (S, hd)
        sc = _dot("gqd,kd->gqk", qg, kh, lower) * t.hd ** -0.5
        sc = jnp.where(causal[None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return _dot("gqk,kd->gqd", p, vh, lower)

    ctx = jax.lax.map(one_kv_head, (q.reshape(t.hkv, g, s, t.hd), k, v))
    ctx = ctx.reshape(t.h, s, t.hd).transpose(1, 0, 2).reshape(s, -1)
    x = x + _dot("sn,nd->sd", ctx, w["wo"], lower)
    h = _rmsnorm(x, w["ln_mlp"], t.eps)
    gu = _dot("sd,dn->sn", h, w["wi"], lower)
    a = jax.nn.silu(gu[:, : t.ff]) * gu[:, t.ff:]
    return x + _dot("sf,fd->sd", a, w["wd"], lower)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(t: Dims, lower: bool, w, x):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), w)
    return jax.lax.map(lambda xs: _sequence_layer(t, lower, w, xs), x)


@functools.partial(jax.jit, static_argnums=0)
def _layer_weights(t: Dims, key, i):
    return layer_weights(t, key, i)


@functools.partial(jax.jit, static_argnums=0)
def _embed(t: Dims, key, tokens):
    return top_weights(t, key)["embed"][tokens].astype(jnp.float32)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _head(t: Dims, lower: bool, first: int, key, x):
    w = jax.tree.map(lambda a: a.astype(jnp.float32), top_weights(t, key))
    h = _rmsnorm(x[:, first:], w["ln_final"], t.eps)
    return _dot("nsd,dv->nsv", h, w["head"], lower)


def logits(conf: dict, key, tokens, first: int, lower: bool = False):
    """Reference logits (N, S - first, V) at positions first..S-1 of
    ``tokens`` (N, S), computed layer by layer in float32."""
    t = dims(conf)
    x = _embed(t, key, jnp.asarray(tokens, jnp.int32))
    for i in range(t.layers):
        x = _layer(t, lower, _layer_weights(t, key, i), x)
    return _head(t, lower, first, key, x)


def _gaps(conf, key, prompts, served, lower):
    prompts, served = np.asarray(prompts), np.asarray(served)
    p = prompts.shape[1]
    seq = np.concatenate([prompts, served[:, :-1]], axis=1)
    ref = logits(conf, key, seq, p - 1)
    chosen = jnp.asarray(served)
    if lower:
        chosen = logits(conf, key, seq, p - 1, lower=True).argmax(-1)
    pick = jnp.take_along_axis(ref, chosen[..., None], -1)[..., 0]
    return np.asarray(ref.max(-1) - pick)


def served_gaps(conf: dict, key, prompts, served):
    """How far below the reference's best logit each served token lies.

    ``prompts`` (N, P) and ``served`` (N, n) are what the program was
    given and returned.  The reference runs once over each prompt with
    its served tokens; position P - 1 + j is where served token j was
    chosen.  Returns the gaps, (N, n).
    """
    return _gaps(conf, key, prompts, served, lower=False)


def control_gaps(conf: dict, key, prompts, served):
    """The control's gaps at the same positions: those of the tokens that
    the float8 computation puts first."""
    return _gaps(conf, key, prompts, served, lower=True)
