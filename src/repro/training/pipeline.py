"""GPipe-style pipeline parallelism via shard_map + collective_permute.

Binds a mesh axis (typically the multi-pod 'pod' axis) to pipeline stages:
layer-stacked parameters are sharded over the stage axis, microbatches
rotate through the stages with ``jax.lax.ppermute``, and the classic GPipe
schedule (M microbatches over S stages, M+S-1 ticks) keeps every stage busy
after the fill phase.  Bubble fraction = (S-1)/(M+S-1).

This is the cross-pod alternative to pure data parallelism when a model's
layers do not fit one pod's HBM: inter-pod links carry only the (mb, D)
activation cuts once per tick instead of full gradient all-reduces.

Used by ``tests/test_pipeline.py`` (numerical equality vs the sequential
stack on fake devices) and the dry-run PP demo.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

__all__ = ["pipeline_apply", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_stages - 1 + n_microbatches)


def pipeline_apply(stage_fn, stage_params, x, *, mesh, axis: str):
    """Run ``x`` through ``n_stages`` sequential stages, pipelined over
    ``axis``.

    Args:
      stage_fn: (params_slice, h) -> h, one pipeline stage (may itself scan
        several layers).
      stage_params: pytree with leading dim = n_stages (sharded over
        ``axis``).
      x: (n_microbatches, mb, ...) microbatched input, sharded over ``axis``
        on dim 0 or replicated.
      mesh: the device mesh; ``axis`` must be one of its axes.

    Returns: (n_microbatches, mb, ...) outputs (gathered on every device).
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert jax.tree_util.tree_leaves(stage_params)[0].shape[0] == n_stages

    def local(params, xs):
        # params: (1, ...) this stage's slice; xs: (n_micro, mb, ...) full
        params = jax.tree.map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_micro + n_stages - 1
        mb_shape = xs.shape[1:]

        def tick(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t (while t < n_micro)
            inject = jnp.where(t < n_micro,
                               xs[jnp.minimum(t, n_micro - 1)],
                               jnp.zeros(mb_shape, xs.dtype))
            h = jnp.where(stage == 0, inject, state)
            h = stage_fn(params, h)
            # the last stage emits microbatch t-(S-1)
            out_idx = t - (n_stages - 1)
            outs = jax.lax.cond(
                (stage == n_stages - 1) & (out_idx >= 0),
                lambda o: o.at[jnp.maximum(out_idx, 0)].set(h),
                lambda o: o,
                outs)
            # rotate activations stage s -> s+1
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            state = jax.lax.ppermute(h, axis, perm)
            return (state, outs), None

        state0 = jnp.zeros(mb_shape, xs.dtype)
        outs0 = jnp.zeros_like(xs)
        (_, outs), _ = jax.lax.scan(
            tick, (state0, outs0), jnp.arange(n_ticks))
        # only the last stage holds real outputs: mask + psum broadcasts
        outs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs

    other_axes = [a for a in mesh.axis_names if a != axis]
    pspec_params = P(axis)
    return jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(jax.tree.map(lambda _: pspec_params, stage_params), P()),
        out_specs=P(),
        check_vma=False,
    )(stage_params, x)
