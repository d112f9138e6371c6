"""Pallas TPU kernel: fused quantize + WOT-throttle (the QATT inner step).

After every optimizer update, QATT quantizes the fp32 masters and clamps
protected positions. Unfused, that's 3 HBM round-trips (read w, write q,
read q / write clamped); fused it is one read + one write. The scale
(max|w|/127) is computed in a first reduction pass (also a kernel).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from repro.core import quant, wot
from . import platform

DEFAULT_BLK = 4096


def _row_valid(i, blk, nblk, shape):
    """Row mask for the (possibly ragged) edge block: rows past nblk are
    grid padding whose contents are unspecified."""
    rows = i * blk + jax.lax.broadcasted_iota(jnp.int32, shape, dimension=0)
    return rows < nblk


def _absmax_kernel(w_ref, out_ref, *, blk, nblk):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    w = jnp.abs(w_ref[...])
    w = jnp.where(_row_valid(i, blk, nblk, w.shape), w, jnp.zeros_like(w))
    out_ref[0] = jnp.maximum(out_ref[0], jnp.max(w))


def _qt_kernel(w_ref, scale_ref, q_ref, *, blk, nblk):
    i = pl.program_id(0)
    w = w_ref[...]                       # (bn, 8) f32
    w = jnp.where(_row_valid(i, blk, nblk, w.shape), w, jnp.zeros_like(w))
    scale = scale_ref[0]
    q = jnp.clip(jnp.round(w / scale), -quant.QMAX, quant.QMAX)
    pos = jax.lax.broadcasted_iota(jnp.int32, w.shape, dimension=1)
    clamped = jnp.clip(q, wot.WOT_LO, wot.WOT_HI)
    q = jnp.where(pos == 7, q, clamped)
    q_ref[...] = q.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("blk",))
def quantize_throttle(w_blocks: jnp.ndarray, *, blk: int = DEFAULT_BLK):
    """(nblk, 8) f32 -> (int8 q (nblk, 8) WOT-compliant, scale f32 ()).

    nblk need not divide into ``blk`` tiles: the grid is ``pl.cdiv`` and the
    edge block is masked by a row-iota, so arbitrary leaf sizes quantize
    without host-side padding. Deployment-exact: equals quantize() then
    throttle_q()."""
    nblk = w_blocks.shape[0]
    blk = min(blk, nblk)
    grid = (pl.cdiv(nblk, blk),)
    absmax = pl.pallas_call(
        functools.partial(_absmax_kernel, blk=blk, nblk=nblk),
        grid=grid,
        in_specs=[pl.BlockSpec((blk, 8), lambda i: (i, 0))],
        out_specs=pl.BlockSpec((1,), lambda i: (0,)),
        out_shape=jax.ShapeDtypeStruct((1,), jnp.float32),
        interpret=platform.interpret(),
    )(w_blocks.astype(jnp.float32))
    scale = jnp.maximum(absmax, 1e-12) / quant.QMAX
    q = pl.pallas_call(
        functools.partial(_qt_kernel, blk=blk, nblk=nblk),
        grid=grid,
        in_specs=[pl.BlockSpec((blk, 8), lambda i: (i, 0)),
                  pl.BlockSpec((1,), lambda i: (0,))],
        out_specs=pl.BlockSpec((blk, 8), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((nblk, 8), jnp.int8),
        interpret=platform.interpret(),
    )(w_blocks.astype(jnp.float32), scale)
    return q, scale[0]
