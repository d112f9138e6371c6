"""Pallas TPU kernel: WOT throttling projection (paper §4.1, step 2).

Clamps positions 0..6 of every 8-value block of an int8 weight vector to
[-64, 63]; position 7 stays free. Elementwise VPU op, memory-bound; runs
after every QATT optimizer step so it must not add HBM round-trips beyond
one read + one write.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import wot
from . import platform

DEFAULT_BLK_N = 4096


def _kernel(q_ref, out_ref):
    q = q_ref[...]  # (bn, 8) int8
    pos = jax.lax.broadcasted_iota(jnp.int32, q.shape, dimension=1)
    clamped = jnp.clip(q, wot.WOT_LO, wot.WOT_HI)
    out_ref[...] = jnp.where(pos == 7, q, clamped).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("blk_n",))
def throttle(q_blocks: jnp.ndarray, *,
             blk_n: int = DEFAULT_BLK_N) -> jnp.ndarray:
    """(nblk, 8) int8 -> WOT-throttled (nblk, 8) int8."""
    nblk = q_blocks.shape[0]
    blk_n = min(blk_n, nblk)
    assert nblk % blk_n == 0
    return pl.pallas_call(
        _kernel,
        grid=(nblk // blk_n,),
        in_specs=[pl.BlockSpec((blk_n, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((blk_n, 8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblk, 8), jnp.int8),
        interpret=platform.interpret(),
    )(q_blocks)
