"""Collective matmul: overlap an all-gather with the matmul that consumes it.

The cluster-scale mirror of the paper's matrix–vector overlap: instead of
``all_gather(x) @ w`` (link idle during compute, MXU idle during
gather), walk the ring with ``ppermute`` and multiply each arriving shard
immediately — compute and communication pipeline at shard granularity
(Wang et al., "Overlap communication with dependent computation", the
pattern XLA's async collectives approximate automatically).

In HLO this replaces one ``all-gather`` of X with N-1 ``collective-
permute``s of X/N each — same total bytes, but every chunk overlaps a
chunk matmul (§Perf collective-term iterations use this on the logits
GEMM).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def _ring_matmul(x_shard, w_shard, axis_name: str, n_dev: int):
    """x_shard: (m_local, k); w_shard: (k, n_local) — X sharded on rows
    over the ring, W sharded on cols.  Output: (m_local, n) — i.e. the
    all-gather of W happens implicitly by rotating X? No: we rotate X
    shards around the ring and accumulate into the *full-M* output block
    owned by this device's W columns: out = all_gather(x) @ w_shard.
    ``n_dev`` is the ring size, passed statically."""
    idx = jax.lax.axis_index(axis_name)
    m_local = x_shard.shape[0]
    out = jnp.zeros((m_local * n_dev, w_shard.shape[1]), x_shard.dtype)
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def body(i, carry):
        out, x = carry
        src = (idx - i) % n_dev                   # whose shard we hold now
        out = jax.lax.dynamic_update_slice_in_dim(
            out, jnp.dot(x, w_shard, preferred_element_type=out.dtype),
            src * m_local, axis=0)
        x = jax.lax.ppermute(x, axis_name, perm)  # overlaps next dot
        return out, x

    out, _ = jax.lax.fori_loop(0, n_dev, body, (out, x_shard))
    return out


def collective_matmul(x, w, mesh: Mesh, axis: str = "model"):
    """x: (M, K) sharded on M over ``axis``; w: (K, N) sharded on N.
    Returns (M, N) sharded on N (X implicitly all-gathered, overlapped)."""
    fn = jax.shard_map(
        functools.partial(_ring_matmul, axis_name=axis,
                          n_dev=mesh.shape[axis]), mesh=mesh,
        in_specs=(P(axis, None), P(None, axis)),
        out_specs=P(None, axis), check_vma=False)
    return fn(x, w)


def allgather_matmul_reference(x, w):
    """The unoverlapped equivalent (numerical oracle)."""
    return jnp.dot(x, w, preferred_element_type=x.dtype)
