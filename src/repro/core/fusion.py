"""``cute_matmul`` — the unified fused-matmul API (paper Listing 1, §4.3).

Every projection, MLP, logit and expert GEMM in every model in this
framework goes through this one function.  It implements the paper's
matrix–vector fusion contract: the matrix engine produces accumulator
tiles, and the "vector side" (bias, (de)quant scales, activation,
residual, soft-capping, GLU gating) is applied as an *epilogue* without a
round-trip through main memory.

Backends
--------
* ``"xla"``   — einsum + epilogue; XLA fuses the epilogue into the matmul
  consumer.  Used for distributed lowering (GSPMD shards it, and
  ``cost_analysis`` sees real FLOPs).
* ``"pallas"`` — the ``kernels/matmul`` fused kernel (MXU/VPU overlap via
  the Pallas grid pipeline).  Tile sizes default to the Eq.2-style solver
  in ``core.constraint``.
* ``"auto"``  — pallas when the shapes meet the kernel's divisibility
  contract on a real TPU, else xla.  On CPU hosts auto → xla.

A call that names no backend takes the process-wide route,
:func:`default_route` (``"xla"``), which depends on nothing else: no
shape, platform or tuning cache.  Its one setter is
``repro.backend.set_default_matmul_backend``.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import precision as prec
from repro.core.precision import DataType, PrecisionPolicy
from repro.core.task import BiasType


# ---------------------------------------------------------------------------
# Epilogue description — tile-local vector work fused after the matmul.
# ---------------------------------------------------------------------------

def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(0.7978845608028654 * (x + 0.044715 * x * x * x)))


ACTIVATIONS: "dict[str, Callable]" = {
    "none": lambda x: x,
    "relu": jax.nn.relu,
    "relu2": lambda x: jnp.square(jax.nn.relu(x)),
    "gelu": jax.nn.gelu,
    "gelu_tanh": _gelu_tanh,
    "silu": jax.nn.silu,
    "sigmoid": jax.nn.sigmoid,
    "tanh": jnp.tanh,
}


@dataclasses.dataclass(frozen=True)
class Epilogue:
    """Vector-side work fused into the matmul (paper Fig. 5 'epilogue').

    Application order (matches the int8 inference pipeline of §5.1):
      acc -> *scale_a (per-row dequant) -> *scale_b (per-col dequant)
          -> +bias (zero/row/full) -> softcap -> activation
          -> GLU gate (optional; splits N in half: act(left) * right)
          -> +residual -> cast(out_dtype)
    """

    bias_type: BiasType = BiasType.ZERO
    activation: str = "none"
    softcap: float = 0.0            # gemma-style logit soft-capping; 0 = off
    glu: bool = False               # act(y[:, :n/2]) * y[:, n/2:]
    has_scale_a: bool = False       # per-row (M,) dequant scale
    has_scale_b: bool = False       # per-col (N,) dequant scale
    has_residual: bool = False
    out_dtype: object = None

    def __post_init__(self):
        if self.activation not in ACTIVATIONS:
            raise ValueError(f"unknown activation {self.activation!r}")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class EpilogueOperands:
    """Arrays consumed by an Epilogue.  All optional, shapes as noted."""

    bias: Optional[jax.Array] = None       # (N,) for ROW, (M, N) for FULL
    scale_a: Optional[jax.Array] = None    # (M,) or scalar
    scale_b: Optional[jax.Array] = None    # (N,) or scalar
    residual: Optional[jax.Array] = None   # (M, N_out)


NO_EPILOGUE = Epilogue()
NO_OPERANDS = EpilogueOperands()


def apply_epilogue(acc: jax.Array, ep: Epilogue, ops: EpilogueOperands,
                   compute_dtype=jnp.float32,
                   tile_operands: bool = False) -> jax.Array:
    """Pure-jnp epilogue application.  ``acc`` is (..., M, N) accumulator.

    Shared by the XLA backend, the Pallas kernel's reference oracle and —
    on a per-tile basis — the Pallas kernel body itself.  The kernel
    passes ``tile_operands=True``: its per-column operands arrive as
    ``(1, N)`` and per-row ones as ``(M, 1)`` blocks (the TPU kernel
    compiler lays 1-D blocks out differently from XLA), which already
    broadcast against the tile.
    """
    def col(x):          # per-column (N,) operand against (..., M, N)
        return x if tile_operands else x[..., None, :]

    def row(x):          # per-row (M,) operand against (..., M, N)
        return x if tile_operands else x[..., :, None]

    out_dtype_final = ep.out_dtype if ep.out_dtype is not None else acc.dtype
    trivial = (not ep.has_scale_a and not ep.has_scale_b
               and ep.bias_type == BiasType.ZERO and not ep.softcap
               and not ep.glu and ep.activation == "none"
               and not ep.has_residual)
    if trivial:
        # Keep int32 accumulators exact (no float round-trip).
        return acc.astype(out_dtype_final)
    y = acc.astype(compute_dtype)
    if ep.has_scale_a:
        y = y * row(ops.scale_a).astype(compute_dtype)
    if ep.has_scale_b:
        y = y * col(ops.scale_b).astype(compute_dtype)
    if ep.bias_type == BiasType.ROW:
        y = y + col(ops.bias).astype(compute_dtype)
    elif ep.bias_type == BiasType.FULL:
        y = y + ops.bias.astype(compute_dtype)
    if ep.softcap:
        y = jnp.tanh(y / ep.softcap) * ep.softcap
    if ep.glu:
        half = y.shape[-1] // 2
        y = ACTIVATIONS[ep.activation](y[..., :half]) * y[..., half:]
    else:
        y = ACTIVATIONS[ep.activation](y)
    if ep.has_residual:
        y = y + ops.residual.astype(compute_dtype)
    out_dtype = ep.out_dtype if ep.out_dtype is not None else acc.dtype
    return y.astype(out_dtype)


# ---------------------------------------------------------------------------
# The unified entry point.
# ---------------------------------------------------------------------------

#: the route ``cute_matmul`` takes when the call names none.  The
#: default stays on XLA because the Pallas matmul everywhere is too slow
#: under interpret mode on CPU for whole-model tests.  Written only by
#: ``repro.backend.set_default_matmul_backend``, which checks the name
#: against the backend registry first.
_default_route = "xla"


def default_route() -> str:
    """The process-wide ``cute_matmul`` route (``"xla"`` or
    ``"pallas"``).  A jitted program that traces route-less calls keys
    on it, so a new route compiles a new program."""
    return _default_route


def _infer_policy(a: jax.Array) -> PrecisionPolicy:
    table = {
        jnp.int8.dtype: prec.INT8,
        jnp.bfloat16.dtype: prec.policy(DataType.BF16, out_dtype=jnp.bfloat16),
        jnp.float16.dtype: prec.policy(DataType.FP16, out_dtype=jnp.float16),
        jnp.float8_e4m3fn.dtype: prec.FP8,
        jnp.float8_e5m2.dtype: prec.policy(DataType.FP8_E5M2),
        jnp.float32.dtype: prec.FP32,
    }
    return table.get(a.dtype, prec.FP32)


def cute_matmul(a: jax.Array, b: jax.Array, *,
                epilogue: Epilogue = NO_EPILOGUE,
                operands: EpilogueOperands = NO_OPERANDS,
                policy: Optional[PrecisionPolicy] = None,
                backend: Optional[str] = None,
                interpret: Optional[bool] = None) -> jax.Array:
    """C = epilogue(A @ B).  A: (..., M, K), B: (K, N) (or (..., K, N)).

    ``backend`` is a ``cute_matmul`` route string (``"xla"``,
    ``"pallas"``, ``"auto"``); ``None`` takes :func:`default_route`.

    ``epilogue.transpose`` equivalent: the paper's result-transpose flag is
    expressed by the caller transposing the (cheap, fused) output — XLA
    folds it into the consuming op's layout.
    """
    if backend is None:
        backend = _default_route
    if policy is None:
        policy = _infer_policy(a)
    if backend == "auto":
        backend = "pallas" if _pallas_supported(a, b, epilogue) else "xla"

    if backend == "pallas":
        from repro.kernels.matmul import ops as mm_ops   # lazy: avoid cycle
        return mm_ops.fused_matmul(a, b, epilogue=epilogue, operands=operands,
                                   policy=policy, interpret=interpret)

    # ----- XLA backend ------------------------------------------------------
    if epilogue.glu and b.ndim == 3:       # (K, 2, N/2) GLU layout
        b = b.reshape(b.shape[0], -1)
    acc = jnp.matmul(a, b, preferred_element_type=policy.accum_dtype,
                     precision=policy.dot_precision)
    ep = epilogue
    if ep.out_dtype is None:
        ep = dataclasses.replace(ep, out_dtype=policy.output_dtype)
    return apply_epilogue(acc, ep, operands)


def _pallas_supported(a, b, epilogue: Epilogue) -> bool:
    from repro.kernels.matmul import ops as mm_ops
    return mm_ops.supports(a.shape, b.shape, epilogue)


def linear(x: jax.Array, w: jax.Array, bias: Optional[jax.Array] = None, *,
           activation: str = "none", glu: bool = False, softcap: float = 0.0,
           out_dtype=None, backend: Optional[str] = None) -> jax.Array:
    """Convenience wrapper used by every model layer in this framework."""
    ep = Epilogue(
        bias_type=BiasType.ROW if bias is not None else BiasType.ZERO,
        activation=activation, glu=glu, softcap=softcap,
        out_dtype=out_dtype if out_dtype is not None else x.dtype)
    return cute_matmul(x, w, epilogue=ep,
                       operands=EpilogueOperands(bias=bias), backend=backend)
