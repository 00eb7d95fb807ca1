"""Time RWKV-6's decode step for the WKV state alone, on a TPU.

The state of ``rwkv6-16l.decode``: a stack of 16 layers, batch 128, 64
heads of 64 (d 4096), float32, lane-dense.  Each program advances every
layer of the stack by one token, in a loop over the layers as the
model's decode step runs them, with the stack donated so that it is
updated in place.  Three routes:

* ``xla``: the exact per-token step of ``models/rwkv6._recurrent`` on
  layer i's state, sliced out of the stack and written back;
* ``elementwise``: layer i's state scaled in place, the plainest pass
  that reads and writes the same bytes, for the rate HBM gives;
* ``kernel``: ``rwkv6_decode_step`` (Pallas).

Each prints one JSON line: milliseconds per layer call (the median of
``--reps`` timed programs of 16 calls, after two untimed), the state
bytes' least time at the chip's HBM bandwidth over it, the rate at which
the call moves them, and, for the kernel, the widest distance of its
output and state from the XLA step's.  Run on the chip, from the
repository's root:

    PYTHONPATH=src python scripts/time_rwkv6_decode.py
"""

from __future__ import annotations

import argparse
import functools
import json
import statistics
import time

import jax
import jax.numpy as jnp

from repro.kernels.rwkv6.ops import rwkv6_decode_step
from repro.models.rwkv6 import _recurrent

HBM_BYTES_PER_S = 819e9                     # TPU v5e (peaks.json)
#: the cell's state: layers, batch, d, head size
SHAPE = (16, 128, 4096, 64)


def inputs(key, layers, b, d, c):
    ks = jax.random.split(key, 6)
    r, k, v = (jax.random.normal(x, (b, d), jnp.bfloat16) for x in ks[:3])
    lw = -jnp.exp(jax.random.normal(ks[3], (b, d)))
    u = jax.random.normal(ks[4], (d // c, c)) * 0.3
    stack = jax.random.normal(ks[5], (layers, b, c, d)) * 0.1
    return stack, (r, k, v, lw, u)


def xla_step(stack, i, r, k, v, lw, u):
    s = jax.lax.dynamic_index_in_dim(stack, i, keepdims=False)
    o, s = _recurrent(r[:, None], k[:, None], v[:, None], lw[:, None], u, s)
    return o[:, 0], jax.lax.dynamic_update_index_in_dim(stack, s, i, 0)


def elementwise(stack, i, r, *_):
    s = jax.lax.dynamic_index_in_dim(stack, i, keepdims=False)
    return r, jax.lax.dynamic_update_index_in_dim(stack, s * 0.5, i, 0)


def every_layer(step):
    """A program that runs ``step`` on each layer of the stack in turn."""
    @functools.partial(jax.jit, donate_argnums=0)
    def run(stack, args):
        def body(i, carry):
            stack, acc = carry
            o, stack = step(stack, i, *args)
            return stack, acc + jnp.sum(o.astype(jnp.float32))
        return jax.lax.fori_loop(0, stack.shape[0], body,
                                 (stack, jnp.float32(0)))
    return run


def time_route(step, stack, args, reps):
    run = every_layer(step)
    stack = jnp.copy(stack)
    for _ in range(2):
        stack, acc = run(stack, args)
    acc.block_until_ready()
    times = []
    for _ in range(reps):
        t = time.perf_counter()
        stack, acc = run(stack, args)
        acc.block_until_ready()
        times.append(time.perf_counter() - t)
    return 1e3 * statistics.median(times) / stack.shape[0]


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reps", type=int, default=10)
    a = ap.parse_args()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"needs a TPU, found {dev.platform}")
    stack, args = inputs(jax.random.PRNGKey(0), *SHAPE)
    state_bytes = 2 * stack[0].nbytes
    least_ms = 1e3 * state_bytes / HBM_BYTES_PER_S

    def report(route, ms, **extra):
        print(json.dumps({"route": route, "ms_per_call": ms,
                          "state_roofline_pct": 100 * least_ms / ms,
                          "state_gb_per_s": state_bytes / ms / 1e6,
                          "device": dev.device_kind, **extra}), flush=True)

    report("xla", time_route(xla_step, stack, args, a.reps))
    report("elementwise", time_route(elementwise, stack, args, a.reps))
    want_o, want_s = jax.jit(xla_step)(stack, 1, *args)
    o, s = jax.jit(rwkv6_decode_step)(jnp.copy(stack), 1, *args)
    err_o = float(jnp.max(jnp.abs(o.astype(jnp.float32)
                                  - want_o.astype(jnp.float32))))
    err_s = float(jnp.max(jnp.abs(s - want_s)))
    report("kernel", time_route(rwkv6_decode_step, stack, args, a.reps),
           max_abs_diff_o=err_o, max_abs_diff_state=err_s)


if __name__ == "__main__":
    main()
