"""GPipe-style pipeline parallelism (optional ``pp`` mesh axis).

The production mesh maps ``pod`` to data parallelism (DESIGN.md §3); this
module provides the PP alternative for deployments where cross-pod DCN
bandwidth cannot carry gradient all-reduces: stages hold layer slices,
microbatches stream through a ``lax.scan`` schedule, bubbles =
(stages-1)/(microbatches+stages-1).

Implementation: the classic "collective-permute pipeline" — the stage
axis lives in a shard_map; each scan step every stage processes one
microbatch and ppermutes its activation to the next stage.  Layers are
assumed stacked (scan-over-layers pytrees) so a stage slice is a leading-
axis slice of every leaf.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def stage_slice(stacked_params, n_stages: int, stage: int):
    """Slice layer-stacked params into one stage's sub-stack."""
    def one(x):
        per = x.shape[0] // n_stages
        return jax.lax.dynamic_slice_in_dim(x, stage * per, per, axis=0)
    return jax.tree.map(one, stacked_params)


def pipeline_apply(block_fn, stacked_params, x_microbatches, mesh: Mesh,
                   axis: str = "pp"):
    """Run microbatches through pipeline stages.

    block_fn(stage_params, x) -> x applies one stage's layer sub-stack.
    x_microbatches: (n_micro, mb, ...) activations.
    Returns (n_micro, mb, ...) outputs after all stages.
    """
    n_stages = mesh.shape[axis]
    n_micro = x_microbatches.shape[0]
    steps = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P()),               # params sharded by stage
        out_specs=P(), check_vma=False)
    def run(params_stage, xs):
        stage = jax.lax.axis_index(axis)
        params_stage = jax.tree.map(lambda p: p[0], params_stage)

        def body(carry, t):
            buf, outs = carry
            # Stage 0 injects microbatch t; others take the permuted buf.
            inject = jnp.where(t < n_micro, t, 0)
            x_in = jnp.where(stage == 0, xs[inject], buf)
            y = block_fn(params_stage, x_in)
            # Last stage emits a finished microbatch (t - n_stages + 1).
            done_idx = t - (n_stages - 1)
            emit = jnp.logical_and(stage == n_stages - 1, done_idx >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(done_idx, 0), 0),
                lambda o: o, outs)
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        buf0 = jnp.zeros_like(xs[0])
        outs0 = jnp.zeros_like(xs)
        (buf, outs), _ = jax.lax.scan(body, (buf0, outs0),
                                      jnp.arange(steps))
        # Collect the finished outputs from the last stage to all stages.
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    # shard_map wants the stage axis explicit on params' leading dim.
    def add_stage_axis(p):
        per = p.shape[0] // n_stages
        return p.reshape((n_stages, per) + p.shape[1:])

    staged = jax.tree.map(add_stage_axis, stacked_params)
    return run(staged, x_microbatches)


def pipeline_bubble_fraction(n_stages: int, n_micro: int) -> float:
    return (n_stages - 1) / (n_micro + n_stages - 1)
