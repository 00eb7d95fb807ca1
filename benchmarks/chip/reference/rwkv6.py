"""Weights and the plain float32 reference of RWKV-6 "Finch".

Covers the configurations whose ``family`` is ``rwkv6`` (rwkv6-7b):
``ln0`` after the embedding; blocks of a time mix (token shift, DDLerp
through a shared low-rank mix and five rank-specific ones, the r, k, v,
g projections, a data-dependent decay through its own low-rank
projection, the WKV recurrence with its bonus ``u``, a per-head
GroupNorm, a SiLU gate and the output projection) and a channel mix
(token shift, squared ReLU gated by a sigmoid receptance), each behind a
LayerNorm; a final LayerNorm and an untied head (arXiv:2404.05892 and
the published ``RWKV_Tmix_x060`` / ``RWKV_CMix_x060`` code).  The WKV is
the per-token recurrence, with no chunking:

    o_t = r_t (S + diag(u) k_t^T v_t),   S <- diag(w_t) S + k_t^T v_t,
    w_t = exp(-exp(w0 + lora(x_w)))

It imports nothing of the program under test.

Weights are drawn here, from a key, leaf by leaf (``layer_weights`` for
layer ``l`` of ``L``, ``top_weights`` for the embedding, the final norm
and the head), in the program's parameter layout and dtypes.  As
published: the decay base ``w0`` of channel ``n`` of ``d`` is
``-6 + 5 (n / (d - 1)) ** (0.7 + 1.3 l / (L - 1))``, the bonus ``u`` is
``l / (L - 1) (1 - n / (d - 1))`` plus a zigzag of ±0.1, and the token
mixes follow the published ``time_maa_*`` formulas.  Departures, so that
a wrongly wired leaf shows: the mixes get a seeded jitter of 0.05; the
low-rank projections' first factors are normal (published: zeros) and
their second ones uniform within ±0.05 (published: ±0.01), so each
moves its mix or decay by about 0.2; projections are normal with a
spread of one over the square root of their fan-in (the published
output projections start at zero); norm weights are drawn around 1 and
their biases around 0.  The program clips the decay exponent to
[-8, 6]; the reference does not (the drawn exponents stay within
[-7, 0]).  ``program_params`` lays the draws out as the program's tree
in one jitted call; the reference draws them again, one layer at a
time, and computes in float32 at the highest matmul precision.

The control (``lower=True``) is the same computation with both operands
of every matmul rounded to float8 (e4m3, scaled by the operand's largest
magnitude): the precision step below the bfloat16 the configurations
state.  The recurrence is element-wise and stays float32.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
WEIGHT_DTYPE = jnp.bfloat16
N_MIX = 5                    # r, k, v, g, w: the program's order


class Dims(NamedTuple):
    d: int
    hs: int                  # head size
    ff: int
    vocab: int
    layers: int
    mix_rank: int
    decay_rank: int
    eps: float               # LayerNorm
    gn_eps: float            # GroupNorm: eps * head_size_divisor ** 2

    @property
    def h(self) -> int:
        return self.d // self.hs


def dims(conf: dict) -> Dims:
    eps = float(conf["layer_norm_epsilon"])
    return Dims(conf["hidden_size"], conf["head_size"],
                conf["intermediate_size"], conf["vocab_size"],
                conf["num_hidden_layers"], conf["time_mix_extra_dim"],
                conf["time_decay_extra_dim"], eps,
                eps * conf["head_size_divisor"] ** 2)


# ---------------------------------------------------------------------------
# Weights.
# ---------------------------------------------------------------------------

def _normal(key, shape, scale):
    return jax.random.normal(key, shape, WEIGHT_DTYPE) * jnp.asarray(
        scale, WEIGHT_DTYPE)


def _uniform(key, shape, bound):
    return jax.random.uniform(key, shape, jnp.float32, -bound,
                              bound).astype(WEIGHT_DTYPE)


def _around(key, n, centre):
    # Around 1 (weights) or 0 (biases), not at it: a norm read wrongly
    # shows.
    return (centre + 0.1 * jax.random.normal(key, (n,), jnp.float32)
            ).astype(WEIGHT_DTYPE)


def layer_weights(t: Dims, key, l):
    """Layer ``l``'s weights, named and laid out as the program's."""
    k = jax.random.split(jax.random.fold_in(jax.random.fold_in(key, 1), l),
                         24)
    d = t.d
    r01 = l / max(t.layers - 1, 1)              # 0 at the first layer, 1 last
    r10 = 1.0 - l / t.layers                    # 1 at the first layer
    ddd = jnp.arange(d, dtype=jnp.float32) / d
    n = jnp.arange(d, dtype=jnp.float32) / (d - 1)

    def mix(key, value):
        return (value + 0.05 * jax.random.normal(key, (d,), jnp.float32)
                ).astype(WEIGHT_DTYPE)

    maa = 1.0 - ddd ** r10
    maa_rg = 1.0 - ddd ** (0.5 * r10)
    zigzag = ((jnp.arange(d) + 1) % 3 - 1) * 0.1
    return {
        "ln1": _around(k[0], d, 1.0), "ln1_b": _around(k[1], d, 0.0),
        "ln2": _around(k[2], d, 1.0), "ln2_b": _around(k[3], d, 0.0),
        "mu_x": mix(k[4], maa),
        "mu_rkvgw": jnp.stack([mix(k[5], maa_rg), mix(k[6], maa),
                               mix(k[7], 1.0 - (ddd ** r10 + 0.3 * r01)),
                               mix(k[8], maa_rg), mix(k[9], maa)]),
        "mix_w1": _normal(k[10], (d, N_MIX * t.mix_rank), d ** -0.5),
        "mix_w2": _uniform(k[11], (N_MIX, t.mix_rank, d), 0.05),
        "w_r": _normal(k[12], (d, d), d ** -0.5),
        "w_k": _normal(k[13], (d, d), d ** -0.5),
        "w_v": _normal(k[14], (d, d), d ** -0.5),
        "w_g": _normal(k[15], (d, d), d ** -0.5),
        "w_o": _normal(k[16], (d, d), d ** -0.5),
        "w0": -6.0 + 5.0 * n ** (0.7 + 1.3 * r01),
        "decay_w1": _normal(k[17], (d, t.decay_rank), d ** -0.5),
        "decay_w2": _uniform(k[18], (t.decay_rank, d), 0.05),
        "u": (r01 * (1.0 - n) + zigzag).reshape(t.h, t.hs),
        "ln_x": _around(k[19], d, 1.0), "ln_x_b": _around(k[20], d, 0.0),
        "mu_cm_k": mix(k[21], maa),
        "mu_cm_r": mix(k[22], maa),
        "w_cm_k": _normal(k[23], (d, t.ff), d ** -0.5),
        "w_cm_v": _normal(jax.random.fold_in(k[23], 1), (t.ff, d),
                          t.ff ** -0.5),
        "w_cm_r": _normal(jax.random.fold_in(k[23], 2), (d, d), d ** -0.5),
    }


def top_weights(t: Dims, key):
    k = jax.random.split(jax.random.fold_in(key, 2), 6)
    return {"embedding": _normal(k[0], (t.vocab, t.d), 1.0),
            "ln_in": _around(k[1], t.d, 1.0),
            "ln_in_b": _around(k[2], t.d, 0.0),
            "ln_final": _around(k[3], t.d, 1.0),
            "ln_final_b": _around(k[4], t.d, 0.0),
            "lm_head": _normal(k[5], (t.d, t.vocab), t.d ** -0.5)}


def program_params(conf: dict, key):
    """The weights as the program's ``rwkv6`` parameter tree, drawn on
    the device in one jitted call."""
    t = dims(conf)

    @jax.jit
    def make(key):
        layers = jax.vmap(lambda l: layer_weights(t, key, l))(
            jnp.arange(t.layers))
        return {**top_weights(t, key), "layers": layers}
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


def _mm(a, w, lower):
    return _dot("nsd,de->nse", a, w, lower)


def _layernorm(x, w, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def _shifted(x):
    """x_{t-1} along the sequence, zeros before the first token."""
    return jnp.pad(x, ((0, 0), (1, 0), (0, 0)))[:, :-1]


def wkv(r, k, v, w, u):
    """The recurrence token by token.  r, k, v, w: (N, S, H, C), w the
    decay in (0, 1); u: (H, C).  Returns o: (N, S, H, C)."""
    n, _, h, c = r.shape

    def step(s, inp):                           # s: (N, H, C_k, C_v)
        r_t, k_t, v_t, w_t = inp
        kv = k_t[..., :, None] * v_t[..., None, :]
        o = jnp.sum(r_t[..., :, None] * (s + u[None, :, :, None] * kv),
                    axis=-2)
        return w_t[..., :, None] * s + kv, o

    _, o = jax.lax.scan(step, jnp.zeros((n, h, c, c), jnp.float32),
                        tuple(jnp.moveaxis(a, 1, 0) for a in (r, k, v, w)))
    return jnp.moveaxis(o, 0, 1)


def _block(t: Dims, lower: bool, w, x):
    """One block over x: (N, S, d), float32."""
    n, s, d = x.shape
    h = _layernorm(x, w["ln1"], w["ln1_b"], t.eps)
    xx = _shifted(h) - h
    m = jnp.tanh(_mm(h + xx * w["mu_x"], w["mix_w1"], lower))
    dyn = _dot("nsfr,frd->nsfd", m.reshape(n, s, N_MIX, t.mix_rank),
               w["mix_w2"], lower)
    mixed = h[:, :, None] + xx[:, :, None] * (w["mu_rkvgw"] + dyn)
    x_r, x_k, x_v, x_g, x_w = (mixed[:, :, i] for i in range(N_MIX))
    r = _mm(x_r, w["w_r"], lower)
    k = _mm(x_k, w["w_k"], lower)
    v = _mm(x_v, w["w_v"], lower)
    g = jax.nn.silu(_mm(x_g, w["w_g"], lower))
    decay = jnp.exp(-jnp.exp(w["w0"] + _mm(
        jnp.tanh(_mm(x_w, w["decay_w1"], lower)), w["decay_w2"], lower)))

    def heads(a):
        return a.reshape(n, s, t.h, t.hs)

    o = wkv(heads(r), heads(k), heads(v), heads(decay), w["u"])
    mu = jnp.mean(o, -1, keepdims=True)
    var = jnp.mean(jnp.square(o - mu), -1, keepdims=True)
    o = ((o - mu) * jax.lax.rsqrt(var + t.gn_eps)).reshape(n, s, d)
    o = o * w["ln_x"] + w["ln_x_b"]
    x = x + _mm(o * g, w["w_o"], lower)

    h = _layernorm(x, w["ln2"], w["ln2_b"], t.eps)
    xx = _shifted(h) - h
    kk = jnp.square(jax.nn.relu(_mm(h + xx * w["mu_cm_k"], w["w_cm_k"],
                                    lower)))
    rr = jax.nn.sigmoid(_mm(h + xx * w["mu_cm_r"], w["w_cm_r"], lower))
    return x + rr * _mm(kk, w["w_cm_v"], lower)


def _f32(w):
    return jax.tree.map(lambda a: a.astype(jnp.float32), w)


# The draws are jitted on their own, apart from any computation: XLA
# would otherwise fuse them into it, and a bfloat16 draw can round
# otherwise inside a fusion than out of it.
_layer_weights = jax.jit(layer_weights, static_argnums=0)
_top_weights = jax.jit(top_weights, static_argnums=0)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(t: Dims, lower: bool, w, x):
    return _block(t, lower, _f32(w), x)


@functools.partial(jax.jit, static_argnums=0)
def _embed(t: Dims, w, tokens):
    w = _f32(w)
    return _layernorm(w["embedding"][tokens], w["ln_in"], w["ln_in_b"],
                      t.eps)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _head(t: Dims, lower: bool, first: int, w, x):
    w = _f32(w)
    h = _layernorm(x[:, first:], w["ln_final"], w["ln_final_b"], t.eps)
    return _mm(h, w["lm_head"], lower)


def logits(conf: dict, key, tokens, first: int, lower: bool = False):
    """Reference logits (N, S - first, V) at positions first..S-1 of
    ``tokens`` (N, S), computed layer by layer in float32."""
    t = dims(conf)
    top = _top_weights(t, key)
    x = _embed(t, top, jnp.asarray(tokens, jnp.int32))
    for l in range(t.layers):
        x = _layer(t, lower, _layer_weights(t, key, l), x)
    return _head(t, lower, first, top, x)


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
