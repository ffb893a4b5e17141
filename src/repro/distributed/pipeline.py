"""GPipe-style pipeline parallelism via shard_map + ppermute.

Demonstrates the PP capability on a host mesh: layer stages are sharded
over a ``pipe`` mesh axis; microbatches stream through the stages with
``jax.lax.ppermute`` moving activations stage→stage. The schedule is the
classic GPipe fill-drain: with S stages and M microbatches, S+M−1 ticks.

This is exercised by tests on 8 host devices and offered as an optional
execution mode for the dense transformer (config ``pipeline_stages``); it
is intentionally not part of the 40-cell dry-run matrix (see DESIGN.md §5).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    stage_params,          # pytree, leaves with leading axis S (stages)
    x,                     # (M, mb, ...) microbatched input
    layer_fn: Callable,    # layer_fn(stage_params_slice, h) -> h
    mesh,
    axis: str = "pipe",
):
    """Run x through S pipeline stages laid over mesh axis ``axis``."""
    S = mesh.shape[axis]
    M = x.shape[0]

    def stage_program(params_local, x_local):
        # params_local: leaves (1, ...) — this device's stage
        # x_local: (M, mb, ...) — full microbatch stream (stage 0 uses it)
        idx = jax.lax.axis_index(axis)
        params_me = jax.tree.map(lambda a: a[0], params_local)
        mb_shape = x_local.shape[1:]
        # carries differ per stage: mark them device-varying for
        # shard_map's replication checker
        h = jax.lax.pcast(jnp.zeros(mb_shape, x_local.dtype), (axis,),
                          to="varying")
        outs = jax.lax.pcast(jnp.zeros((M,) + mb_shape, x_local.dtype),
                             (axis,), to="varying")
        perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(t, carry):
            h, outs = carry
            # stage 0 injects microbatch t (if still filling)
            inject = jax.lax.dynamic_index_in_dim(
                x_local, jnp.clip(t, 0, M - 1), keepdims=False)
            h = jnp.where(jnp.logical_and(idx == 0, t < M), inject, h)
            h = layer_fn(params_me, h)
            # last stage emits microbatch t-(S-1)
            emit_t = t - (S - 1)
            idx_c = jnp.clip(emit_t, 0, M - 1)
            old = jax.lax.dynamic_index_in_dim(outs, idx_c, 0, keepdims=False)
            emit = jnp.logical_and(idx == S - 1, emit_t >= 0)
            new = jnp.where(emit, h.astype(outs.dtype), old)
            outs = jax.lax.dynamic_update_index_in_dim(outs, new, idx_c, 0)
            h = jax.lax.ppermute(h, axis, perm)
            return h, outs

        h, outs = jax.lax.fori_loop(0, M + S - 1, tick, (h, outs))
        # broadcast results from the last stage to all (psum of one-hot)
        mask = (idx == S - 1).astype(outs.dtype)
        outs = jax.lax.psum(outs * mask, axis)
        return outs

    fn = jax.shard_map(
        stage_program,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )
    return fn(stage_params, x)
