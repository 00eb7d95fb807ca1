"""Parameter / batch / cache sharding rules (divisibility-aware).

Maps every parameter leaf to logical axes by its name, then through the
active ``logical`` rules to a ``NamedSharding``.  Megatron-style TP falls
out of the name map: QKV and MLP-in shard their *output* column (column
parallel), attention-out and MLP-out shard their *input* row (row
parallel), so each transformer block costs one all-reduce in forward.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.distributed import logical
from repro.models.base import ArchConfig

#: leaf name -> logical axes (matched on the last path component).
_NAME_RULES: "dict[str, tuple]" = {
    "embedding": ("vocab", "embed"),
    "lm_head": ("embed", "vocab"),
    "wq": ("embed", "heads"),        # column parallel
    "wk": ("embed", "kv_heads"),
    "wv": ("embed", "kv_heads"),
    "wo": ("heads", "embed"),        # row parallel
    "wi": ("embed", "mlp"),          # column parallel (GLU keeps 2x cols)
    "w_router": ("embed", None),     # replicated router
    "experts_wi": ("experts", "embed", "mlp_expert"),
    "experts_wo": ("experts", "mlp_expert", "embed"),
    # Griffin recurrent block.
    "w_rnn_in": ("embed", "mlp"),
    "w_gate_in": ("embed", "mlp"),
    "w_rnn_out": ("mlp", "embed"),
    # RWKV time-mix projections.
    "w_r": ("embed", "heads"),
    "w_k": ("embed", "heads"),
    "w_v": ("embed", "heads"),
    "w_g": ("embed", "heads"),
    "w_o": ("heads", "embed"),
    "w_cm_k": ("embed", "mlp"),
    "w_cm_v": ("mlp", "embed"),
    "w_cm_r": ("embed", "mlp"),
}
# mlp wo: name collision with attention wo is fine — both are row parallel
# with the sharded dim first.


def _leaf_logical_axes(path, leaf) -> "tuple | None":
    name = None
    for part in reversed(path):
        key = getattr(part, "key", getattr(part, "name", None))
        if isinstance(key, str):
            name = key
            break
    if name in _NAME_RULES:
        axes = _NAME_RULES[name]
        if len(axes) == leaf.ndim:
            return axes
        # Stacked-over-layers leaves get a leading (replicated) layer dim.
        if len(axes) == leaf.ndim - 1:
            return (None,) + axes
        if len(axes) == leaf.ndim - 2:
            return (None, None) + axes
    return None


def param_shardings(params, mesh: Optional[Mesh], rules: Optional[dict] = None):
    """NamedSharding pytree for a (possibly abstract) param pytree."""
    if mesh is None:
        return jax.tree.map(lambda _: None, params)
    with logical.use_rules(mesh, rules):
        def one(path, leaf):
            axes = _leaf_logical_axes(path, leaf)
            if axes is None:
                return NamedSharding(mesh, P())      # replicate
            s = logical.sharding_for(leaf.shape, axes)
            return s if s is not None else NamedSharding(mesh, P())
        return jax.tree_util.tree_map_with_path(one, params)


def batch_shardings(batch, mesh: Optional[Mesh], rules: Optional[dict] = None):
    """Shard the leading (batch) dim of every input leaf over (pod, data)."""
    if mesh is None:
        return jax.tree.map(lambda _: None, batch)
    with logical.use_rules(mesh, rules):
        def one(leaf):
            axes = ("batch",) + (None,) * (leaf.ndim - 1)
            s = logical.sharding_for(leaf.shape, axes)
            return s if s is not None else NamedSharding(mesh, P())
        return jax.tree.map(one, batch)


def cache_shardings(cache, mesh: Optional[Mesh], cfg: ArchConfig,
                    rules: Optional[dict] = None):
    """KV caches: batch over (pod, data); the model axis takes the KV-head
    dim when it divides, else the cache *sequence* dim (sequence-parallel
    decode attention: scores/softmax/PV reduce over the sharded S with a
    single all-reduce — how a 2 TB 32k cache fits 16 GB chips when
    n_kv_heads < model size, e.g. deepseek-67b kv=8 on model=16)."""
    if mesh is None:
        return jax.tree.map(lambda _: None, cache)
    model = mesh.shape.get("model", 1)
    with logical.use_rules(mesh, rules):
        def one(leaf):
            if leaf.ndim == 5:
                # (L, B, Hkv, S, D) KV cache or (L, B, H, C, C) rwkv state.
                heads, seq = leaf.shape[2], leaf.shape[3]
                if heads % model == 0:
                    axes = (None, "batch", "kv_heads", None, None)
                elif seq % model == 0:
                    axes = (None, "batch", None, "heads", None)
                else:
                    axes = (None, "batch", None, None, None)
            elif leaf.ndim >= 2:
                axes = (None, "batch") + (None,) * (leaf.ndim - 2)
            else:
                axes = (None,) * leaf.ndim
            s = logical.sharding_for(leaf.shape, axes)
            return s if s is not None else NamedSharding(mesh, P())
        return jax.tree.map(one, cache)


# ---------------------------------------------------------------------------
# Cluster-partitioned GEMM: the execution mirror of sim.partition.
# ---------------------------------------------------------------------------

def shard_map_gemm(a, b, n_units: int, dim: str = "m",
                   axis: str = "units", accum_dtype=None, precision=None,
                   bounds=None):
    """Accumulator-precision GEMM sharded over ``n_units``.

    ``dim="m"`` shards A's rows (row-panel partition: each unit owns
    full output rows), ``dim="n"`` shards B's columns (output-tile
    partition: each unit owns full output columns).  ``bounds`` is the
    per-unit ``(lo, hi)`` extent list of a ``sim.partition.Partition``
    (``None`` entries for idle units), so execution reproduces the
    *exact* unit-to-data mapping the DES timed; omitted, an even split
    is assumed.  An even split runs under a real ``shard_map`` over a
    ``(units,)`` mesh of ``n_units`` devices, and fewer devices is an
    error; partition-shaped (unbalanced) spans run as :func:`sliced_gemm`
    (integer dots are bit-exact either way, which is what the parity
    suite pins).

    ``accum_dtype``/``precision`` mirror ``cute_matmul``'s dot so the
    shards accumulate exactly like the single-device kernel path.
    Returns the full (M, N) accumulator (int32 for int8 inputs).
    """
    size = a.shape[0] if dim == "m" else b.shape[1]
    if n_units == 1 or size % n_units or (
            bounds is not None and list(bounds) != _even_spans(size, n_units)):
        return sliced_gemm(a, b, n_units, dim, accum_dtype, precision, bounds)
    if jax.device_count() < n_units:
        raise ValueError(
            f"shard_map_gemm over {n_units} units needs {n_units} devices, "
            f"found {jax.device_count()}; sliced_gemm runs the same spans "
            "as a loop on one device")
    dot = _accumulating_dot(a, dim, accum_dtype, precision)

    from repro.launch.mesh import make_mesh
    mesh = make_mesh((n_units,), (axis,))
    in_specs = (P(axis, None), P(None, None)) if dim == "m" \
        else (P(None, None), P(None, axis))
    out_specs = P(axis, None) if dim == "m" else P(None, axis)
    fn = jax.shard_map(dot, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(a, b)


def sliced_gemm(a, b, n_units: int, dim: str = "m", accum_dtype=None,
                precision=None, bounds=None):
    """``shard_map_gemm``'s result computed on one device: each unit's
    span (``bounds``, else an even split) is one dot, and the blocks are
    concatenated.  For modelled units that are not devices."""
    dot = _accumulating_dot(a, dim, accum_dtype, precision)
    if bounds is None:
        bounds = _even_spans(a.shape[0] if dim == "m" else b.shape[1],
                             n_units)
    parts = []
    for span in bounds:
        if span is None:
            continue
        lo, hi = span
        if hi <= lo:
            continue
        parts.append(dot(a[lo:hi], b) if dim == "m"
                     else dot(a, b[:, lo:hi]))
    return jnp.concatenate(parts, axis=0 if dim == "m" else 1)


def _even_spans(size: int, n_units: int):
    return [(size * u // n_units, size * (u + 1) // n_units)
            for u in range(n_units)]


def _accumulating_dot(a, dim, accum_dtype, precision):
    if dim not in ("m", "n"):
        raise ValueError(f"dim must be 'm' or 'n', got {dim!r}")
    if accum_dtype is None:
        accum_dtype = jnp.int32 if a.dtype in (jnp.int8.dtype, jnp.uint8.dtype) \
            else jnp.float32

    def dot(a_s, b_s):
        return jnp.matmul(a_s, b_s, preferred_element_type=accum_dtype,
                          precision=precision)
    return dot
