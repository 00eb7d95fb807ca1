"""Logical-axis sharding rules (MaxText-style) + activation constraints.

Models annotate activations with *logical* axes ("batch", "seq", "heads",
"mlp", "vocab", ...).  A ``ShardingRules`` context maps logical axes to
mesh axes; ``constrain`` applies ``with_sharding_constraint`` only when a
mesh is active **and** the dimension is divisible by the mapped mesh-axis
size (gemma2-2b's 8 heads on a 16-way model axis silently fall back to
GSPMD's choice — the divisibility-aware fallback of DESIGN.md §6).

Changing the rules dict is the primary lever of the §Perf hillclimb:
re-lower with a different mapping.
"""

from __future__ import annotations

import contextlib
import math
from typing import Optional, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

AxisSpec = Union[str, "tuple[str, ...]", None]

#: default mapping; pod is folded into the data dimension of the batch.
#: "embed" -> "data" is FSDP/ZeRO-3: parameters (and optimizer moments)
#: shard their non-TP dimension over the data axis; GSPMD all-gathers
#: them per layer inside the scan and reduce-scatters gradients.
DEFAULT_RULES: "dict[str, AxisSpec]" = {
    "batch": ("pod", "data"),
    "seq": None,
    "seq_shard": "data",          # sequence parallelism (long-context)
    "heads": "model",
    "kv_heads": "model",
    "embed": "data",              # FSDP axis
    "mlp": "model",
    "mlp_expert": None,
    "vocab": "model",
    "experts": "model",
    "audio_ctx": None,
}

_ACTIVE: "list[tuple[Mesh, dict]]" = []


@contextlib.contextmanager
def use_rules(mesh: Optional[Mesh], rules: Optional[dict] = None):
    if mesh is None:
        yield
        return
    merged = dict(DEFAULT_RULES)
    if rules:
        merged.update(rules)
    merged = {k: v for k, v in merged.items() if v is not None}
    _ACTIVE.append((mesh, merged))
    try:
        yield
    finally:
        _ACTIVE.pop()


def active_mesh() -> Optional[Mesh]:
    return _ACTIVE[-1][0] if _ACTIVE else None


def _axis_size(mesh: Mesh, ax: AxisSpec) -> int:
    if ax is None:
        return 1
    if isinstance(ax, str):
        ax = (ax,)
    return math.prod(mesh.shape[a] for a in ax)


def spec_for(shape, logical_axes) -> Optional[P]:
    """PartitionSpec for ``shape`` under the active rules (None = inactive)."""
    if not _ACTIVE:
        return None
    mesh, rules = _ACTIVE[-1]
    used: set = set()
    parts = []
    for dim, lax_name in zip(shape, logical_axes):
        ax = rules.get(lax_name) if lax_name else None
        if ax is not None:
            names = (ax,) if isinstance(ax, str) else tuple(ax)
            # Keep only axes present in this mesh (e.g. "pod" is absent on
            # the single-pod mesh) and not already used by another dim.
            names = tuple(n for n in names
                          if n in mesh.shape and n not in used)
            if names and dim % _axis_size(mesh, names) == 0:
                used.update(names)
                parts.append(names if len(names) > 1 else names[0])
                continue
        parts.append(None)
    return P(*parts)


def constrain(x: jax.Array, logical_axes) -> jax.Array:
    spec = spec_for(x.shape, logical_axes)
    if spec is None:
        return x
    mesh, _ = _ACTIVE[-1]
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def sharding_for(shape, logical_axes) -> Optional[NamedSharding]:
    spec = spec_for(shape, logical_axes)
    if spec is None:
        return None
    return NamedSharding(_ACTIVE[-1][0], spec)
