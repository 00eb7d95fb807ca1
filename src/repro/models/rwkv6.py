"""RWKV-6 (Finch) — attention-free SSM family.

Faithful block structure (arXiv:2404.05892):
  * Time-mix: token-shift DDLerp (shared low-rank W1 + per-target W2)
    produces r/k/v/g/w mixes; data-dependent decay via a decay LoRA;
    the WKV recurrence (kernels/rwkv6); per-head GroupNorm; SiLU gate;
    output projection.
  * Channel-mix: token-shift lerp, squared-ReLU FFN with a sigmoid
    receptance gate (the activation is ``cfg.mlp_activation``).

LayerNorms take their epsilon from ``cfg.rms_eps``; the per-head
GroupNorm takes it times ``head_size_divisor`` (8) squared, as the
published code does.  The one departure from the paper: the decay exponent is
clipped to [-8, 6] before ``w = exp(-exp(.))``.

Paper applicability (DESIGN.md §4): the recurrence is vector work — all
projections still flow through ``cute_matmul``; the chunked WKV turns
the state update into MXU-sized outer products.

Training's ``forward`` picks the WKV by ``cfg.backend``: the per-token
oracle for ``dense``, else ``rwkv6_chunked_jnp`` (the chunked math in
jnp under ``lax.scan``, so cost_analysis sees its FLOPs; on the chip it
beat a Pallas scan of the same math at every chunk size, PERF.md).
Serving does not read ``cfg.backend``: prefill streams the prompt
through the state in slices of ``PREFILL_SLICE`` tokens through the
chunked form, and decode takes the token through ``kernels/rwkv6``'s
step kernel (``rwkv6_decode_step``), which reads a layer's WKV state
once and writes it in place in the stacked cache.  The per-token oracle
``_recurrent`` serves the ``dense`` backend and the kernel's tests.

Layer scopes, for the device trace: ``embed``, ``head``; ``norm`` (the
LayerNorms); ``qkv`` (token shift, DDLerp, the r/k/v/g projections and
the decay LoRA); ``attention`` (the WKV recurrence with its state's read
and write: prefill's slice and in-place update, decode's kernel call);
``attn_out`` (GroupNorm, gate, ``w_o``); ``mlp`` (the channel mix);
``cache_update`` (writing the shift states back).  Each traced serving
call adds to ``rwkv_wkv_calls_total{route, step}``: the prefill's
slices, or one decode step; the decode kernel's route adds one to
``rwkv_wkv_inplace_steps_total`` each time it is traced.
"""

from __future__ import annotations

import functools
import sys

import jax
import jax.numpy as jnp

from repro.core.fusion import linear
from repro.distributed.logical import constrain
from repro.models import common as cm
from repro.models.base import ArchConfig, register_family

_N_MIX = 5     # r, k, v, g, w
#: a layer's token-shift states in the serving cache, with the layer
#: scopes that read and write them.  The WKV state is not among them:
#: the serving WKV routes take the whole stack and the layer's index, so
#: its read and write lie in ``attention`` (``_sliced``, ``_in_place``).
_STATES = (("tm_shift", "qkv", "cache_update"),
           ("cm_shift", "mlp", "cache_update"))
HEAD_SIZE_DIVISOR = 8          # the published GroupNorm eps is eps * 8 ** 2
#: serving prefill's tokens per slice: the longest whose program fits a
#: v5e at the chip cell's batch of 128 (memory_analysis, PERF.md)
PREFILL_SLICE = 128
#: the chunked WKV's chunk in serving prefill (chip sweep, PERF.md)
PREFILL_WKV_CHUNK = 16


# ---------------------------------------------------------------------------
# Chunked WKV in pure jnp.
# ---------------------------------------------------------------------------

def rwkv6_chunked_jnp(r, k, v, lw, u, *, chunk: int = 64,
                      initial_state=None):
    """r/k/v/lw: (B, H, T, C); u: (H, C) -> (o, final_state)."""
    b, h, t, c = r.shape
    pad = (-t) % chunk
    if pad:
        widths = ((0, 0), (0, 0), (0, pad), (0, 0))
        r, k, v, lw = (jnp.pad(x, widths) for x in (r, k, v, lw))
    tp = t + pad
    n = tp // chunk

    def to_chunks(x):
        return jnp.moveaxis(
            x.astype(jnp.float32).reshape(b, h, n, chunk, c), 2, 0)

    rc, kc, vc, lwc = map(to_chunks, (r, k, v, lw))
    mask = (jnp.arange(chunk)[:, None] > jnp.arange(chunk)[None, :])

    def body(state, inp):
        rr, kk, vv, ww = inp                      # (B, H, L, C)
        la = jnp.cumsum(ww, axis=2)
        la_prev = la - ww
        q_t = rr * jnp.exp(la_prev)
        o = jnp.einsum("bhlc,bhcd->bhld", q_t, state)
        diff = la_prev[:, :, :, None, :] - la[:, :, None, :, :]
        pair = (rr[:, :, :, None, :] * kk[:, :, None, :, :]
                * jnp.exp(jnp.where(mask[None, None, :, :, None],
                                    diff, -1e30)))
        p = jnp.sum(pair, axis=-1)                # (B, H, L, L)
        o = o + jnp.einsum("bhls,bhsd->bhld", p, vv)
        o = o + jnp.sum(rr * u[None, :, None, :] * kk, axis=-1,
                        keepdims=True) * vv
        la_last = la[:, :, -1:, :]
        k_scaled = kk * jnp.exp(la_last - la)
        state = (jnp.exp(la_last[:, :, 0, :])[..., None] * state
                 + jnp.einsum("bhlc,bhld->bhcd", k_scaled, vv))
        return state, o

    if initial_state is None:
        initial_state = jnp.zeros((b, h, c, c), jnp.float32)
    state, o = jax.lax.scan(body, initial_state, (rc, kc, vc, lwc))
    o = jnp.moveaxis(o, 0, 2).reshape(b, h, tp, c)[:, :, :t]
    return o.astype(r.dtype), state


# The serving cache holds a layer's WKV state lane-dense, as (B, C, d):
# S[b, c, h * C + e] is head h's entry (key channel c, value channel e).
# A (B, H, C, C) array of float32 with C = 64 would fill half of each
# (8, 128) tile of the TPU's memory and so take twice its size.

def _state_heads(s, h):
    """(B, C, d) -> (B, H, C, C)."""
    b, c, _ = s.shape
    return s.reshape(b, c, h, c).transpose(0, 2, 1, 3)


def _state_flat(s):
    """(B, H, C, C) -> (B, C, d)."""
    b, h, c, _ = s.shape
    return s.transpose(0, 2, 1, 3).reshape(b, c, h * c)


def _heads(z, h):
    """(B, T, d) -> (B, H, T, C)."""
    b, t, d = z.shape
    return z.reshape(b, t, h, d // h).transpose(0, 2, 1, 3)


def _unheads(o):
    """(B, H, T, C) -> (B, T, d)."""
    b, h, t, c = o.shape
    return o.transpose(0, 2, 1, 3).reshape(b, t, h * c)


# A WKV route: (r, k, v, lw: (B, T, d); u: (H, C); the state (B, C, d) or
# None for zeros) -> (o: (B, T, d), the state after the last token).

def _chunked(r, k, v, lw, u, state, chunk: int = 64):
    h = u.shape[0]
    o, s = rwkv6_chunked_jnp(
        *(_heads(z, h) for z in (r, k, v, lw)), u, chunk=chunk,
        initial_state=None if state is None else _state_heads(state, h))
    return _unheads(o), _state_flat(s)


def _recurrent(r, k, v, lw, u, state):
    """The exact per-token recurrence on the lane-dense state:
    o_t = r_t (S + diag(u) k_t^T v_t), then S = diag(w_t) S + k_t^T v_t."""
    b, t, d = r.shape
    h, c = u.shape

    def per_key(z):
        """z[..., h * C + c] -> (..., C, d), along every value channel."""
        lead = z.shape[:-1]
        z = jnp.moveaxis(z.reshape(*lead, h, c, 1), -2, -3)
        return jnp.broadcast_to(z, (*lead, c, h, c)).reshape(*lead, c, d)

    uu = per_key(u.reshape(d).astype(jnp.float32))

    def step(s, inp):
        r_t, k_t, v_t, lw_t = (z.astype(jnp.float32) for z in inp)
        kv = per_key(k_t) * v_t[:, None, :]
        o = jnp.sum(per_key(r_t) * (s + uu * kv), axis=1)
        return per_key(jnp.exp(lw_t)) * s + kv, o

    if state is None:
        state = jnp.zeros((b, c, d), jnp.float32)
    state, o = jax.lax.scan(step, state, tuple(
        jnp.moveaxis(z, 1, 0) for z in (r, k, v, lw)))
    return jnp.moveaxis(o, 0, 1).astype(r.dtype), state


# A serving WKV route takes, in place of the state, the stacked states of
# every layer and this layer's index (stack, i), and gives back the
# stack with layer i advanced.

def _sliced(route):
    """``route`` on layer i's state, sliced out of the stack and written
    back in place (the compiler fuses the update into that write)."""
    def run(r, k, v, lw, u, state):
        stack, i = state
        o, s = route(r, k, v, lw, u,
                     jax.lax.dynamic_index_in_dim(stack, i, keepdims=False))
        return o, jax.lax.dynamic_update_index_in_dim(stack, s, i, 0)
    return run


def _in_place(r, k, v, lw, u, state):
    """One token through the decode kernel, which reads layer i's state
    once and writes it in place in the stack.  The layer scan traces
    this once per decode step, which counts it once."""
    from repro.kernels.rwkv6.ops import rwkv6_decode_step
    _count("rwkv_wkv_inplace_steps_total", 1)
    stack, i = state
    o, stack = rwkv6_decode_step(stack, i, r[:, 0], k[:, 0], v[:, 0],
                                 lw[:, 0], u)
    return o[:, None], stack


def _train_wkv(cfg: ArchConfig):
    """Training's WKV route, by ``cfg.backend``; starts from no state."""
    if cfg.backend == "dense":
        return _recurrent
    return _chunked


# ---------------------------------------------------------------------------
# Parameters.
# ---------------------------------------------------------------------------

def _layer_init(cfg: ArchConfig, key):
    d, rw = cfg.d_model, cfg.rwkv
    ks = jax.random.split(key, 16)
    dt = cfg.dtype
    p = {
        "ln1": jnp.ones((d,), dt), "ln1_b": jnp.zeros((d,), dt),
        "ln2": jnp.ones((d,), dt), "ln2_b": jnp.zeros((d,), dt),
        # DDLerp token-shift mixes.
        "mu_x": (jax.random.uniform(ks[0], (d,)) * 0.5).astype(dt),
        "mu_rkvgw": (jax.random.uniform(ks[1], (_N_MIX, d)) * 0.5).astype(dt),
        "mix_w1": cm.dense_init(ks[2], (d, _N_MIX * rw.lora_mix), dt),
        "mix_w2": (jax.random.normal(ks[3], (_N_MIX, rw.lora_mix, d))
                   * 0.01).astype(dt),
        # Time-mix projections.
        "w_r": cm.dense_init(ks[4], (d, d), dt),
        "w_k": cm.dense_init(ks[5], (d, d), dt),
        "w_v": cm.dense_init(ks[6], (d, d), dt),
        "w_g": cm.dense_init(ks[7], (d, d), dt),
        "w_o": cm.dense_init(ks[8], (d, d), dt),
        # Data-dependent decay LoRA + per-channel bases.
        "w0": (jax.random.uniform(ks[9], (d,)) * 2.0 - 2.0).astype(jnp.float32),
        "decay_w1": cm.dense_init(ks[10], (d, rw.lora_decay), dt),
        "decay_w2": (jax.random.normal(ks[11], (rw.lora_decay, d))
                     * 0.01).astype(dt),
        "u": (jax.random.normal(ks[12], (d // rw.head_size, rw.head_size))
              * 0.3).astype(jnp.float32),
        "ln_x": jnp.ones((d,), dt), "ln_x_b": jnp.zeros((d,), dt),
        # Channel mix.
        "mu_cm_k": (jax.random.uniform(ks[13], (d,)) * 0.5).astype(dt),
        "mu_cm_r": (jax.random.uniform(ks[13], (d,)) * 0.5).astype(dt),
        "w_cm_k": cm.dense_init(ks[14], (d, cfg.d_ff), dt),
        "w_cm_v": cm.dense_init(ks[15], (cfg.d_ff, d), dt, in_axis=1),
        "w_cm_r": cm.dense_init(ks[9], (d, d), dt),
    }
    return p


def init(cfg: ArchConfig, key):
    ks = jax.random.split(key, 4)
    v = cfg.padded_vocab
    layer_keys = jax.random.split(ks[2], cfg.n_layers)
    return {
        "embedding": cm.embed_init(ks[0], (v, cfg.d_model), cfg.dtype),
        "lm_head": cm.dense_init(ks[1], (cfg.d_model, v), cfg.dtype),
        "ln_in": jnp.ones((cfg.d_model,), cfg.dtype),
        "ln_in_b": jnp.zeros((cfg.d_model,), cfg.dtype),
        "ln_final": jnp.ones((cfg.d_model,), cfg.dtype),
        "ln_final_b": jnp.zeros((cfg.d_model,), cfg.dtype),
        "layers": jax.vmap(lambda k: _layer_init(cfg, k))(layer_keys),
    }


# ---------------------------------------------------------------------------
# Block application.
# ---------------------------------------------------------------------------

def _shift(x, last=None):
    """Token shift: x_{t-1} (zeros or carried state at t=0)."""
    pad = jnp.zeros_like(x[:, :1]) if last is None else last[:, None]
    return jnp.concatenate([pad, x[:, :-1]], axis=1)


def time_mix(cfg: ArchConfig, p, x, wkv, shift_state=None, wkv_state=None):
    """x: (B, T, d), normed.  ``wkv(r, k, v, lw, u, state)`` is the
    recurrence's route.  Returns (out, last input, WKV state)."""
    b, t, d = x.shape
    rw = cfg.rwkv
    h = d // rw.head_size
    with jax.named_scope("qkv"):
        xx = _shift(x, shift_state) - x
        xxx = x + xx * p["mu_x"]
        mix = jnp.tanh(linear(xxx, p["mix_w1"]))            # (B, T, 5*r)
        mix = mix.reshape(b, t, _N_MIX, rw.lora_mix)
        dyn = jnp.einsum("btnr,nrd->btnd", mix, p["mix_w2"])
        mixed = x[:, :, None, :] + xx[:, :, None, :] * (
            p["mu_rkvgw"][None, None] + dyn)                # (B, T, 5, d)
        x_r, x_k, x_v, x_g, x_w = (mixed[:, :, i] for i in range(_N_MIX))
        r = linear(x_r, p["w_r"])
        k = linear(x_k, p["w_k"])
        v = linear(x_v, p["w_v"])
        g = linear(x_g, p["w_g"], activation="silu")
        w_dyn = linear(jnp.tanh(linear(x_w, p["decay_w1"])), p["decay_w2"],
                       out_dtype=jnp.float32)
        lw = -jnp.exp(jnp.clip(p["w0"][None, None].astype(jnp.float32)
                               + w_dyn, -8.0, 6.0))

    with jax.named_scope("attention"):
        o, wkv_state = wkv(r, k, v, lw, p["u"], wkv_state)
    with jax.named_scope("attn_out"):
        o = cm.groupnorm_heads(o, p["ln_x"], p["ln_x_b"], h,
                               cfg.rms_eps * HEAD_SIZE_DIVISOR ** 2)
        out = linear(o * g, p["w_o"])
    return constrain(out, ("batch", "seq", "embed")), x[:, -1], wkv_state


@jax.named_scope("mlp")
def channel_mix(cfg: ArchConfig, p, x, shift_state=None):
    xx = _shift(x, shift_state) - x
    x_k = x + xx * p["mu_cm_k"]
    x_r = x + xx * p["mu_cm_r"]
    k = linear(x_k, p["w_cm_k"], activation=cfg.mlp_activation)
    kv = linear(k, p["w_cm_v"])
    return jax.nn.sigmoid(linear(x_r, p["w_cm_r"], out_dtype=jnp.float32)
                          ).astype(x.dtype) * kv, x[:, -1]


def block_apply(cfg: ArchConfig, p, x, wkv=None, state=(None, None, None)):
    """One block over x: (B, T, d).  ``state`` is the layer's
    (tm_shift, cm_shift, wkv) carried in, or none, the WKV state in the
    form ``wkv`` takes; returns (x, the states carried out)."""
    tm_s, cm_s, wkv_s = state
    h = cm.layernorm(x, p["ln1"], p["ln1_b"], cfg.rms_eps)
    tm, tm_new, wkv_new = time_mix(cfg, p, h, wkv or _train_wkv(cfg),
                                   tm_s, wkv_s)
    x = x + tm
    h = cm.layernorm(x, p["ln2"], p["ln2_b"], cfg.rms_eps)
    cmix, cm_new = channel_mix(cfg, p, h, cm_s)
    return x + cmix, (tm_new, cm_new, wkv_new)


def _embed(cfg: ArchConfig, params, tokens):
    x = cm.embed_tokens(cfg, params["embedding"], tokens)
    return cm.layernorm(x, params["ln_in"], params["ln_in_b"], cfg.rms_eps)


def _final_norm(cfg: ArchConfig, params, x):
    return cm.layernorm(x, params["ln_final"], params["ln_final_b"],
                        cfg.rms_eps)


def forward(cfg: ArchConfig, params, batch, return_hidden: bool = False):
    x = _embed(cfg, params, batch["tokens"])

    def body(carry, lp):
        return block_apply(cfg, lp, carry)[0], None

    if cfg.remat != "none":
        body = jax.checkpoint(body, policy=cm.remat_policy(cfg),
                              prevent_cse=False)
    x = _final_norm(cfg, params, jax.lax.scan(body, x, params["layers"])[0])
    return x if return_hidden else cm.logits_out(cfg, params, x)


# ---------------------------------------------------------------------------
# Serving: state = per-layer (tm_shift, cm_shift, wkv_state).
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, batch_size: int, max_len: int, dtype=None):
    del max_len                                   # state is O(1) in context
    d, rw = cfg.d_model, cfg.rwkv
    dt = dtype or cfg.dtype
    n = cfg.n_layers
    return {
        "tm_shift": jnp.zeros((n, batch_size, d), dt),
        "cm_shift": jnp.zeros((n, batch_size, d), dt),
        "wkv": jnp.zeros((n, batch_size, rw.head_size, d), jnp.float32),
    }


def _layers(cfg: ArchConfig, params, x, cache, wkv):
    """Every layer over x: (B, T, d), reading and writing layer i's
    states in the stacked cache in place (the loop carries the cache);
    ``wkv`` is a serving route, on the stacked WKV state."""
    def body(carry, lp):
        x, cache, i = carry
        state = []
        for name, scope, _ in _STATES:
            with jax.named_scope(scope):
                state.append(jax.lax.dynamic_index_in_dim(
                    cache[name], i, keepdims=False))
        x, new = block_apply(cfg, lp, x, wkv,
                             (*state, (cache["wkv"], i)))
        cache = dict(cache, wkv=new[-1])
        for (name, _, scope), s in zip(_STATES, new):
            with jax.named_scope(scope):
                cache[name] = jax.lax.dynamic_update_index_in_dim(
                    cache[name], s.astype(cache[name].dtype), i, 0)
        return (x, cache, i + 1), None

    (x, cache, _), _ = jax.lax.scan(body, (x, cache, jnp.int32(0)),
                                    params["layers"])
    return x, cache


def _count(name: str, n: int, **labels):
    from repro.obs import default_registry
    default_registry().counter(name, **labels).inc(n)


def prefill(cfg: ArchConfig, params, batch, cache):
    """The prompt in slices of ``PREFILL_SLICE`` tokens (a
    shorter remainder first), each through every layer, the states
    carried from slice to slice: activations stay those of one slice."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    size = PREFILL_SLICE
    n, rem = divmod(s, size)
    _count("rwkv_wkv_calls_total", n + (rem > 0), route="chunked",
           step="prefill")

    def run(toks, cache):
        x, cache = _layers(cfg, params, _embed(cfg, params, toks), cache,
                           _sliced(functools.partial(
                               _chunked, chunk=PREFILL_WKV_CHUNK)))
        return x[:, -1], cache

    if rem:
        last, cache = run(tokens[:, :rem], cache)
    if n:
        slices = tokens[:, rem:].reshape(b, n, size).swapaxes(0, 1)

        def body(cache, toks):
            last, cache = run(toks, cache)
            return cache, last

        cache, lasts = jax.lax.scan(body, cache, slices)
        last = lasts[-1]
    return cm.logits_out(cfg, params, _final_norm(cfg, params, last)), cache


def decode_step(cfg: ArchConfig, params, tokens, cache, pos):
    del pos                                        # state carries position
    _count("rwkv_wkv_calls_total", 1, route="recurrent", step="decode")
    x, cache = _layers(cfg, params, _embed(cfg, params, tokens), cache,
                       _in_place)
    return cm.logits_out(cfg, params, _final_norm(cfg, params, x[:, -1])), \
        cache


register_family("rwkv6")(sys.modules[__name__])
