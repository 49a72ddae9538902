"""Pipeline parallelism: GPipe-style microbatch schedule over a ``pipe`` axis.

Stages are laid out along the mesh's ``pipe`` axis; microbatches flow
stage-to-stage via ``lax.ppermute`` inside ``shard_map``.  The schedule is
the classic (n_micro + n_stages − 1)-tick loop: tick t feeds microbatch t to
stage 0, and stage s processes microbatch (t − s).  Bubble fraction =
(n_stages − 1)/(n_micro + n_stages − 1).

This is an optional axis for deeper-than-memory models; the assigned
production meshes are data×model, so the 40-cell dry-run does not use it —
it is exercised by its own virtual-mesh test (tests/test_parallel.py).
"""
from __future__ import annotations

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,        # (stage_params, x) -> x
    stage_params,              # pytree stacked on leading n_stages dim
    x: jax.Array,              # [n_micro, mb, ...] microbatched input
    mesh: Mesh,
    axis: str = "pipe",
) -> jax.Array:
    """Run x through n_stages pipeline stages; returns [n_micro, mb, ...]
    outputs (as produced by the last stage)."""
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro >= 1

    param_specs = jax.tree.map(lambda _: P(axis), stage_params)

    @partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(param_specs, P(axis)),   # microbatches start on stage 0? —
        out_specs=P(axis),                 # see gather/scatter note below
        check_vma=False,
    )
    def run(my_params, x_shard):
        # Each stage holds an equal slice of the microbatch dim; gather all
        # microbatches so stage 0 can feed them in order (they are small).
        my_params = jax.tree.map(lambda p: p[0], my_params)
        xs = jax.lax.all_gather(x_shard, axis, tiled=True)     # [n_micro, mb, ...]
        stage = jax.lax.axis_index(axis)
        ticks = n_micro + n_stages - 1
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        buf = jnp.zeros_like(xs[0])                            # stage input
        outs = jnp.zeros_like(xs)                              # last-stage outputs

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            feed = jax.lax.dynamic_index_in_dim(xs, mb_idx, 0, keepdims=False)
            inp = jnp.where(stage == 0, feed, buf)
            out = stage_fn(my_params, inp)
            # record finished microbatch (t - n_stages + 1) from the last stage
            done_idx = t - (n_stages - 1)
            write_idx = jnp.clip(done_idx, 0, n_micro - 1)
            is_last = stage == n_stages - 1
            should_write = jnp.logical_and(is_last, done_idx >= 0)
            cur = jax.lax.dynamic_index_in_dim(outs, write_idx, 0, keepdims=False)
            upd = jnp.where(should_write, out, cur)
            outs = jax.lax.dynamic_update_index_in_dim(outs, upd, write_idx, 0)
            # hand off to next stage
            buf = jax.lax.ppermute(out, axis, fwd_perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, ticks, tick, (buf, outs))
        # broadcast the last stage's results to all stages (all_gather +
        # select — ppermute can't fan out one source), then each stage
        # returns its slice so out_specs P(axis) reassembles the batch.
        outs = jax.lax.all_gather(outs, axis)[n_stages - 1]
        k = n_micro // n_stages
        return jax.lax.dynamic_slice_in_dim(outs, stage * k, k, axis=0)

    return run(stage_params, x)


def stage_split(n_layers: int, n_stages: int) -> list[int]:
    """Even layer split with remainder on early stages."""
    base, rem = divmod(n_layers, n_stages)
    return [base + (1 if i < rem else 0) for i in range(n_stages)]
