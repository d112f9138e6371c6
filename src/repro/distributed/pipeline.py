"""Pipeline parallelism (GPipe-style) over a 'stage' mesh axis via shard_map
+ collective_permute.

The assigned production meshes use DP(+pod) x TP, which is the right config
for <=512 chips at these model sizes; this module demonstrates the PP
substrate needed beyond that (thousands of chips / very deep models): layers
are split into S stages, microbatches stream through with
collective_permute boundaries, bubble fraction (S-1)/(S-1+M).
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


def make_pipeline_fn(stage_fn: Callable, n_stages: int, n_micro: int,
                     mesh: Mesh, axis: str = "stage"):
    """stage_fn(stage_params, x) -> x, applied S times in sequence.

    Returns pipe(params_stacked, x_micro) where params_stacked has leading
    stage axis (sharded over `axis`) and x_micro is (n_micro, mb, ...)
    (replicated). Output: (n_micro, mb, ...) from the last stage.
    """
    assert n_micro >= n_stages, "need >= S microbatches to fill the pipe"

    def per_device(params, xs):
        # params: stage-local (leading axis 1) ; xs: all microbatches
        params = jax.tree.map(lambda a: a[0], params)
        sid = jax.lax.axis_index(axis)
        n_steps = n_micro + n_stages - 1
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def step(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t (if within range)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            inject = jnp.where(sid == 0,
                               xs[mb_idx].astype(buf.dtype), buf)
            y = stage_fn(params, inject)
            # last stage emits microbatch (t - S + 1)
            out_idx = jnp.clip(t - n_stages + 1, 0, n_micro - 1)
            emit = jnp.logical_and(sid == n_stages - 1, t >= n_stages - 1)
            outs = jax.lax.cond(
                emit, lambda o: o.at[out_idx].set(y.astype(o.dtype)),
                lambda o: o, outs)
            # shift activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            buf = jax.lax.ppermute(y, axis, perm)
            return (buf, outs), None

        (buf, outs), _ = jax.lax.scan(step, (buf, outs),
                                      jnp.arange(n_steps))
        # broadcast final outputs from the last stage to all (psum of one-hot)
        outs = jax.lax.psum(
            jnp.where(sid == n_stages - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    pspec = jax.tree.map(lambda _: P(axis), {"_": 0})["_"]

    def pipe(params_stacked, x_micro):
        in_specs = (jax.tree.map(lambda _: P(axis), params_stacked), P())
        return jax.shard_map(per_device, mesh=mesh, in_specs=in_specs,
                             out_specs=P(), check_vma=False)(params_stacked,
                                                             x_micro)

    return pipe
