"""Top-k MoE block (OLMoE 64e/top-8, Arctic 128e/top-2 + dense residual).

Distribution strategy (DESIGN.md §3, EP): activations are replicated
across the ``model`` axis (standard Megatron TP layout), experts are
sharded across it.  Each model shard sort-dispatches its *local* tokens
to the experts it owns, runs the grouped GEMM, combines with the gate
weights, and a single ``psum`` over ``model`` adds the partial outputs —
the same collective cost class as a Megatron row-parallel all-reduce,
with no global sort and no (T, E, C) one-hot.

Token overflow beyond ``capacity = ceil(T·k/E · cf)`` is dropped
(GShard-style); the property tests check conservation under capacity.
The single-device path is the same function with ``e_start=0`` and all
experts local.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.core.fusion import Epilogue, linear
from repro.models.base import ArchConfig
from repro.models.common import dense_init


def moe_init(cfg: ArchConfig, key):
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 5)
    mult = 2 if cfg.mlp_glu else 1
    p = {
        "w_router": dense_init(ks[0], (d, m.n_experts), jnp.float32),
        "experts_wi": dense_init(
            ks[1], (m.n_experts, d, mult * m.d_ff_expert), cfg.dtype),
        "experts_wo": dense_init(
            ks[2], (m.n_experts, m.d_ff_expert, d), cfg.dtype, in_axis=2),
    }
    if m.dense_parallel:
        p["dense_wi"] = dense_init(ks[3], (d, mult * cfg.d_ff), cfg.dtype)
        p["dense_wo"] = dense_init(ks[4], (cfg.d_ff, d), cfg.dtype, in_axis=1)
    return p


def _expert_ffn(cfg: ArchConfig, wi, wo, x):
    """x: (E_l, C, d) -> (E_l, C, d) through the per-expert GLU MLP."""
    if cfg.backend == "pallas":
        from repro.kernels.moe.ops import grouped_matmul
        h = grouped_matmul(x, wi, epilogue=Epilogue(
            activation=cfg.mlp_activation, glu=cfg.mlp_glu,
            out_dtype=x.dtype))
        return grouped_matmul(h, wo)
    h = jnp.einsum("ecd,edf->ecf", x, wi,
                   preferred_element_type=jnp.float32)
    if cfg.mlp_glu:
        half = h.shape[-1] // 2
        from repro.core.fusion import ACTIVATIONS
        h = ACTIVATIONS[cfg.mlp_activation](h[..., :half]) * h[..., half:]
    else:
        from repro.core.fusion import ACTIVATIONS
        h = ACTIVATIONS[cfg.mlp_activation](h)
    h = h.astype(x.dtype)
    return jnp.einsum("ecf,efd->ecd", h, wo,
                      preferred_element_type=jnp.float32).astype(x.dtype)


def moe_apply_local(cfg: ArchConfig, x2d, w_router, wi_local, wo_local,
                    e_start, capacity: int):
    """Partial MoE output of the locally-held experts.

    x2d: (T, d); wi_local: (E_l, d, mult·ff); e_start: first owned expert
    (traced OK).  Returns (T, d) — sum over model shards = full output.
    """
    m = cfg.moe
    t, d = x2d.shape
    e_local = wi_local.shape[0]

    logits = (x2d.astype(jnp.float32) @ w_router)          # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    gate, idx = jax.lax.top_k(probs, m.top_k)              # (T, k)
    if m.renormalize:
        gate = gate / jnp.sum(gate, axis=-1, keepdims=True)

    flat_idx = idx.reshape(-1)                             # (T·k,)
    flat_gate = gate.reshape(-1)
    local_e = jnp.where(
        (flat_idx >= e_start) & (flat_idx < e_start + e_local),
        flat_idx - e_start, e_local)                       # e_local = trash

    order = jnp.argsort(local_e)                           # stable
    sorted_e = local_e[order]
    counts = jnp.bincount(local_e, length=e_local + 1)
    starts = jnp.concatenate([jnp.zeros((1,), counts.dtype),
                              jnp.cumsum(counts)[:-1]])
    rank = jnp.arange(t * m.top_k) - starts[sorted_e]
    keep = (sorted_e < e_local) & (rank < capacity)
    slot = jnp.where(keep, sorted_e * capacity + rank, e_local * capacity)
    token = order // m.top_k

    disp = jnp.zeros((e_local * capacity + 1, d), x2d.dtype)
    disp = disp.at[slot].set(
        jnp.where(keep[:, None], x2d[token], 0.0).astype(x2d.dtype))
    disp = disp[:-1].reshape(e_local, capacity, d)

    y = _expert_ffn(cfg, wi_local, wo_local, disp)         # (E_l, C, d)
    y_flat = y.reshape(e_local * capacity, d)

    contrib = jnp.where(keep[:, None],
                        flat_gate[order][:, None].astype(x2d.dtype)
                        * y_flat[jnp.minimum(slot, e_local * capacity - 1)],
                        0.0)
    out = jnp.zeros((t, d), x2d.dtype).at[token].add(contrib.astype(x2d.dtype))
    return out


def moe_capacity(cfg: ArchConfig, tokens_local: int) -> int:
    m = cfg.moe
    cap = int(tokens_local * m.top_k * m.capacity_factor / m.n_experts) + 1
    return max(8, cap + (-cap) % 8)


def moe_apply(cfg: ArchConfig, p, x, mesh: Optional[Mesh] = None):
    """x: (B, S, d) -> (B, S, d).  Uses shard_map(EP over 'model') when a
    mesh with a 'model' axis is active; single-shard math otherwise."""
    b, s, d = x.shape
    m = cfg.moe
    if mesh is None:
        from repro.distributed import logical
        mesh = logical.active_mesh()

    if cfg.moe_shard_map and mesh is not None and "model" in mesh.shape \
            and m.n_experts % mesh.shape["model"] == 0:
        n_shards = mesh.shape["model"]
        e_local = m.n_experts // n_shards
        data_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
        t_local = (b // _size(mesh, data_axes)) * s
        capacity = moe_capacity(cfg, t_local)

        @functools.partial(
            jax.shard_map, mesh=mesh,
            in_specs=(P(data_axes, None, None), P(), P("model", None, None),
                      P("model", None, None)),
            out_specs=P(data_axes, None, None),
            check_vma=False)
        def sharded(x_l, w_router, wi_l, wo_l):
            shard = jax.lax.axis_index("model")
            x2d = x_l.reshape(-1, d)
            out = moe_apply_local(cfg, x2d, w_router, wi_l, wo_l,
                                  shard * e_local, capacity)
            out = jax.lax.psum(out, "model")
            return out.reshape(x_l.shape)

        y = sharded(x, p["w_router"], p["experts_wi"], p["experts_wo"])
    else:
        capacity = moe_capacity(cfg, b * s)
        y = moe_apply_local(cfg, x.reshape(-1, d), p["w_router"],
                            p["experts_wi"], p["experts_wo"], 0,
                            capacity).reshape(b, s, d)

    if m.dense_parallel:
        # Arctic: dense residual MLP in parallel with the MoE branch.
        h = linear(x, p["dense_wi"], activation=cfg.mlp_activation,
                   glu=cfg.mlp_glu)
        y = y + linear(h, p["dense_wo"])
    return y


def _size(mesh: Mesh, axes) -> int:
    out = 1
    for a in axes:
        out *= mesh.shape[a]
    return out
