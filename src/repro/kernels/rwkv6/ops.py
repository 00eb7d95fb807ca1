"""jit'd wrapper for the RWKV-6 WKV kernel: serving decode's in-place
step."""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret
from repro.kernels.rwkv6.rwkv6 import rwkv6_decode_kernel


#: a decode block holds whole heads, this many where they divide d, so
#: the keyed operands' (heads, C) blocks keep the (8, 128) tiling
DECODE_BLOCK_HEADS = 8
#: the most state a decode block holds: double-buffered in and out it
#: stays well inside the 16 MiB of a v5e's scoped VMEM
DECODE_BLOCK_BYTES = 1 << 20


def _decode_blocks(b: int, c: int, d: int) -> tuple[int, int]:
    """(batch rows, lanes) of a decode block of the (B, C, d) state."""
    lanes = DECODE_BLOCK_HEADS * c
    if d % lanes:
        lanes = d
    rows = max(1, DECODE_BLOCK_BYTES // (c * lanes * 4))
    return max(n for n in range(1, min(rows, b) + 1) if b % n == 0), lanes


@functools.partial(jax.jit, static_argnames=("interpret",))
def rwkv6_decode_step(stack, layer, r, k, v, lw, u, *,
                      interpret: Optional[bool] = None):
    """One decode token through layer ``layer``'s WKV state, in place.

    ``stack``: every layer's state, (L, B, C, d) float32 with
    S[b, c, h·C + e] head h's entry (key channel c, value channel e);
    r/k/v/lw: (B, d); u: (H, C).  Returns (o: (B, d) in r's dtype, the
    stack with layer ``layer`` advanced one token).  The stack is the
    kernel's output buffer too, so where the caller's buffer may be
    reused (a loop's carry, a donated argument) only that layer's blocks
    are read and written.  A block is ``DECODE_BLOCK_HEADS`` heads (all
    of d where they do not divide it) by the most batch rows, a divisor
    of B, that keep it within ``DECODE_BLOCK_BYTES``: 8 × 64 × 512 at
    the chip cell's batch of 128 and heads of 64, where a call takes
    about 0.4 ms on a TPU v5e (PERF.md).
    """
    _, b, c, d = stack.shape
    h = d // c
    bb, bd = _decode_blocks(b, c, d)
    hb = bd // c
    # Keyed by key channel, r/k/lw are (B, H, C): a relayout of a
    # megabyte each.  The barrier keeps the compiler from folding that
    # reshape into the projection that made r or k, where it turned the
    # projection's 32 MB weight around on every call.
    r, k, lw = jax.lax.optimization_barrier((r, k, lw))
    # v and o hold a batch block's rows in their two minor dimensions,
    # which the (8, 128) tiling then allows for any divisor of B.
    state = pl.BlockSpec((pl.Squeezed(), bb, c, bd),
                         lambda i, j, layer: (layer[0], i, 0, j))
    keyed = pl.BlockSpec((bb, hb, c), lambda i, j, layer: (i, j, 0))
    valued = pl.BlockSpec((1, bb, bd), lambda i, j, layer: (i, 0, j))
    o, stack = pl.pallas_call(
        rwkv6_decode_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(b // bb, d // bd),
            in_specs=[state, keyed, keyed, keyed,
                      pl.BlockSpec((hb, c), lambda i, j, layer: (j, 0)),
                      valued],
            out_specs=[valued, state]),
        out_shape=[jax.ShapeDtypeStruct((b // bb, bb, d), r.dtype),
                   jax.ShapeDtypeStruct(stack.shape, stack.dtype)],
        input_output_aliases={1: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=resolve_interpret(interpret),
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), stack,
      *(z.reshape(b, h, c) for z in (r, k, lw)), u,
      v.reshape(b // bb, bb, d))
    return o.reshape(b, d), stack
