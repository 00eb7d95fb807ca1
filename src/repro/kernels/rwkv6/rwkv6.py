"""RWKV-6 (Finch) WKV kernels — data-dependent per-channel decay: the
chunked scan over a sequence, and serving decode's one-token step.

The recurrence (per head, state S ∈ R^{C×C}):

    o_t = r_t · (S_{t-1} + diag(u) k_tᵀ v_t)
    S_t = diag(w_t) · S_{t-1} + k_tᵀ v_t,     w_t = exp(lw_t), lw_t ≤ 0

TPU adaptation (DESIGN.md §4): the element-wise recurrence itself has no
matmul for the paper's PE array — but the *chunked* reformulation turns
it into small dense products (inter-chunk state contribution ``q̃ @ S``
and the state update ``K̃ᵀ @ V`` hit the MXU), with the remaining
intra-chunk pairwise-decay term on the VPU.  That is the paper's
matrix/vector split applied inside a single operator.

Numerics: everything is kept in log space with non-positive exponents —
``exp(la_{t-1} - la_s)`` for s < t and ``exp(la_L - la_s)`` are both ≤ 1
because cumulative log-decay is non-increasing.  The intra-chunk term is
computed one query row at a time over an (L, C) pairwise block, which
is exact and overflow-free (a production kernel would use the GLA
two-level split).

Grid: (B·H, T/L) — chunk axis sequential, state carried in VMEM scratch.

Decode (``rwkv6_decode_kernel``): one token is vector work on a state
that is far larger than anything else the step touches, so the kernel
is one pass over it: each block of the state is read once, read out,
updated and written back in place.  Grid: (batch blocks, lane blocks).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def rwkv6_kernel(r_ref, k_ref, v_ref, lw_ref, u_ref, o_ref, s_ref,
                 r_st, lap_st, intra_st, *, n_chunks: int):
    """Scratch: ``s_ref`` (C, C) state carry; ``r_st``/``lap_st``/
    ``intra_st`` (L, C) float32 staging for the per-step rows of the
    intra-chunk term."""
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        s_ref[...] = jnp.zeros_like(s_ref)

    r = r_ref[0].astype(jnp.float32)      # (L, C)
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    lw = lw_ref[0].astype(jnp.float32)    # (L, C), log decay <= 0
    u = u_ref[0].astype(jnp.float32)      # (1, C)
    L = r.shape[0]

    # Inclusive prefix log-decay, as a lower-triangular matmul (the TPU
    # kernel compiler has no cumsum; HIGHEST keeps the f32 sum exact).
    tril = (jax.lax.broadcasted_iota(jnp.int32, (L, L), 0)
            >= jax.lax.broadcasted_iota(jnp.int32, (L, L), 1))
    la = jnp.dot(tril.astype(jnp.float32), lw,
                 precision=jax.lax.Precision.HIGHEST,
                 preferred_element_type=jnp.float32)
    la_prev = la - lw                     # la_{t-1} (la_0 = 0)

    # Inter-chunk: r_t ⊙ exp(la_{t-1}) @ S_0          (MXU)
    q_t = r * jnp.exp(la_prev)
    o = jnp.dot(q_t, s_ref[...], preferred_element_type=jnp.float32)

    # Intra-chunk, one query row t at a time (VPU):
    #   o_t += Σ_{s<t} (Σ_c r_tc k_sc exp(la_{t-1,c} - la_{s,c})) v_s
    # Every exponent is <= 0 for s < t, so the sum is overflow-free.
    r_st[...] = r
    lap_st[...] = la_prev
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (L, 1), 0)

    def intra_row(t, carry):
        row = pl.ds(t, 1)
        e = jnp.where(s_idx < t, lap_st[row, :] - la, -1e30)     # (L, C)
        w = jnp.sum(r_st[row, :] * k * jnp.exp(e), axis=1,
                    keepdims=True)                              # (L, 1)
        intra_st[row, :] = jnp.sum(w * v, axis=0, keepdims=True)
        return carry

    jax.lax.fori_loop(0, L, intra_row, 0)
    o += intra_st[...]

    # Bonus diagonal: ((r_t ⊙ u) · k_t) v_t            (VPU)
    o += jnp.sum(r * u * k, axis=-1, keepdims=True) * v
    o_ref[0] = o.astype(o_ref.dtype)

    # State update: S_L = diag(exp(la_L)) S_0 + (K ⊙ exp(la_L - la_s))ᵀ V.
    la_last = la[L - 1:L]                              # (1, C)
    k_scaled = k * jnp.exp(la_last - la)               # <= 1 factors
    # diag(exp(la_L)) as a (C, C) matrix: the row scaling stays a matmul
    # instead of a lane-to-sublane relayout of the (1, C) decay row.
    n = s_ref.shape[0]
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1))
    decay = jnp.where(eye, jnp.exp(la_last), 0.0)
    s_ref[...] = (jnp.dot(decay, s_ref[...],
                          precision=jax.lax.Precision.HIGHEST,
                          preferred_element_type=jnp.float32)
                  + jax.lax.dot_general(
                      k_scaled, v, (((0,), (0,)), ((), ())),
                      preferred_element_type=jnp.float32))


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
