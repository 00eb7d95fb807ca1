"""RWKV-6 (Finch) WKV kernel — data-dependent per-channel decay, one
token of serving decode.

The recurrence (per head, state S ∈ R^{C×C}):

    o_t = r_t · (S_{t-1} + diag(u) k_tᵀ v_t)
    S_t = diag(w_t) · S_{t-1} + k_tᵀ v_t,     w_t = exp(lw_t), lw_t ≤ 0

One token is vector work on a state that is far larger than anything
else the step touches, so the kernel (``rwkv6_decode_kernel``) is one
pass over it: each block of the state is read once, read out, updated
and written back in place.  Grid: (batch blocks, lane blocks).  A
sequence's WKV takes the chunked jnp form
(``models.rwkv6.rwkv6_chunked_jnp``), which turns the state update into
MXU-sized products.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _bf16_pieces(z):
    """float32 ``z`` as three bfloat16 parts whose sum is exactly ``z``
    (8 significant bits each); bfloat16 ``z`` is its own one part."""
    if z.dtype == jnp.bfloat16:
        return (z,)
    hi = z.astype(jnp.bfloat16)
    rest = z - hi.astype(jnp.float32)
    mid = rest.astype(jnp.bfloat16)
    return hi, mid, (rest - mid.astype(jnp.float32)).astype(jnp.bfloat16)


def rwkv6_decode_kernel(layer_ref, s_ref, r_ref, k_ref, lw_ref, u_ref,
                        v_ref, o_ref, s_out_ref):
    """One token through the recurrence, on a block of the lane-dense
    state: ``s_ref`` (Bt, C, lanes) float32, S[b, c, h·C + e] head h's
    entry (key channel c, value channel e), the lanes whole heads.

    ``r``/``k``/``lw`` (Bt, Hb, C) and ``u`` (Hb, C) are keyed by key
    channel, ``v`` (1, Bt, lanes) by value channel.  The readout
    ``o = Σ_c r (S + u k v)`` takes the state as it was read, and the
    state goes back as ``diag(w) S + kᵀ v`` (``s_out_ref`` is the same
    block of the same buffer).  A key-channel row becomes its heads'
    lanes on the MXU: a 0/1 matrix times each bfloat16 part of it, one
    nonzero product per output, so the expansion is exact.
    """
    del layer_ref                                 # it picked the blocks
    bt, c, lanes = s_ref.shape
    hb = u_ref.shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (hb, lanes), 1)
    first = jax.lax.broadcasted_iota(jnp.int32, (hb, lanes), 0) * c
    spread = ((lane >= first) & (lane < first + c)).astype(jnp.bfloat16)

    def per_key(z):
        """(Hb, C) -> (C, lanes): z[h, c] along head h's lanes."""
        parts = [jax.lax.dot_general(p, spread, (((0,), (0,)), ((), ())),
                                     preferred_element_type=jnp.float32)
                 for p in _bf16_pieces(z)]
        out = parts.pop()
        while parts:                  # smallest first: each sum is exact
            out = parts.pop() + out
        return out

    uu = per_key(u_ref[...].astype(jnp.float32))
    for b in range(bt):
        s = s_ref[b]
        row = pl.ds(b, 1)
        kv = per_key(k_ref[b]) * v_ref[0, row, :].astype(jnp.float32)
        o_ref[0, row, :] = jnp.sum(per_key(r_ref[b]) * (s + uu * kv),
                                   axis=0, keepdims=True).astype(o_ref.dtype)
        s_out_ref[b] = per_key(jnp.exp(lw_ref[b].astype(jnp.float32))) * s \
            + kv
