"""Pipeline parallelism over a mesh axis — SNAX Fig. 5 at pod scale.

The SNAX-MLIR asynchronous-scheduling pass unrolls a virtual pipeline of
accelerator stages with double-buffered SPM hand-off.  The pod-scale mirror:
layers are partitioned into S stages along a mesh axis; microbatches flow
through `shard_map` + ``ppermute`` (the tightly-coupled hand-off), each
stage computing one microbatch per tick (the loosely-coupled async launch).
The rotating ``state`` buffer is exactly the odd/even double buffer; the
ppermute is the barrier between dependent stages — inserted only where the
data dependency requires, as in the paper.

This is a *forward* pipeline (serving / pipelined prefill).  The schedule
is GPipe-style with bubble fraction (S-1)/(T+S-1).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


__all__ = ["pipeline_forward", "split_stages"]


def split_stages(stacked_params, n_stages: int):
    """(L, ...) layer-stacked params -> (S, L/S, ...) stage-stacked."""
    def reshape(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape((n_stages, l // n_stages) + x.shape[1:])

    return jax.tree_util.tree_map(reshape, stacked_params)


def pipeline_forward(stage_params, x_micro, block_fn, mesh, *,
                     axis: str = "stage"):
    """Run microbatches through pipeline stages laid out on ``axis``.

    stage_params: pytree, leaves (S, L/S, ...) — dim0 sharded over ``axis``.
    x_micro:      (T, mb, ...) microbatched activations (replicated).
    block_fn:     (layer_params, x) -> x, applied L/S times per stage.
    Returns (T, mb, ...) outputs.
    """
    n_stages = mesh.shape[axis]
    t_micro = x_micro.shape[0]
    total_ticks = t_micro + n_stages - 1

    def stage_apply(local_params, x):
        def body(x, layer_params):
            return block_fn(layer_params, x), None

        x, _ = jax.lax.scan(body, x, local_params)
        return x

    @functools.partial(
        jax.shard_map, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
    )
    def run(params_local, xs):
        params_local = jax.tree_util.tree_map(
            lambda q: q[0], params_local)          # strip stage dim
        sid = jax.lax.axis_index(axis)
        # carries become device-varying through ppermute/axis_index; mark
        # the initial values varying so the scan carry type is stable
        state = jax.lax.pcast(jnp.zeros_like(xs[0]), (axis,), to="varying")
        outs = jax.lax.pcast(jnp.zeros_like(xs), (axis,), to="varying")

        def tick(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t; others consume the hand-off
            feed = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, t_micro - 1), keepdims=False)
            inp = jnp.where(sid == 0, feed, state)
            y = stage_apply(params_local, inp)
            # last stage retires microbatch t - (S-1)
            out_idx = jnp.clip(t - (n_stages - 1), 0, t_micro - 1)
            write = (sid == n_stages - 1) & (t >= n_stages - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, out_idx,
                                               keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(write, y, cur), out_idx, 0)
            # double-buffered hand-off to the next stage (the barrier)
            state = jax.lax.ppermute(
                y, axis,
                [(i, (i + 1) % n_stages) for i in range(n_stages)])
            return (state, outs), None

        (state, outs), _ = jax.lax.scan(
            tick, (state, outs), jnp.arange(total_ticks))
        # only the last stage holds real outputs; share them
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    return run(stage_params, x_micro)
