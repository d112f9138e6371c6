"""Pallas TPU kernel: in-place SEC-DED (64,57,1) decode.

Streams ECC-encoded int8 weight bytes HBM->VMEM, computes the 7-bit Hsiao
syndrome of every 64-bit block, corrects single-bit errors, restores the
non-informative sign bits, and writes decoded weights back — the software
analogue of the paper's Fig. 2 "swizzle + standard ECC logic" path.

Lane-dense layout. The block codec works on any ``(rows, W)`` byte tile
whose width is a multiple of 8: an ECC block is 8 consecutive lanes, and
byte ``c % 8`` of its block sits in lane ``c``. Every op is 32-bit VPU
work (unsigned bytes widen to int32 on load), so the same helpers run
inside the fused matmul and paged-attention kernels and in this
standalone one:

* each byte XORs the code columns of its set bits (an ``(8, W)`` lane
  table, :func:`code_table`) into a partial syndrome;
* three lane rolls XOR-reduce each aligned 8-lane group, three more
  broadcast the block syndrome back to the group's lanes;
* the syndrome picks the flip mask per lane, and the sign-bit restore is a
  per-lane select.

Packed words. Every step above works on each byte alone: a syndrome is an
XOR of 7-bit code columns, so it never carries into the next byte, the
group reduction runs along lanes, and the sign restore is bit arithmetic
within a byte. So a ``(R, W)`` uint8 tile bitcast to ``(R/4, W)`` int32
words (``pltpu.bitcast``: four rows of one lane per word, every block
position still in its lane) decodes four bytes per 32-bit op.
:func:`syndrome_words` gives each block's syndrome, packed the same way,
on the last lane of its block (the reduction half of the group XOR: a
check for zero needs no broadcast back), and :func:`restore_words` the
packed sign restore. A tile whose syndromes are all zero decodes to
exactly what :func:`decode_lanes` returns for it, with no flags, so only a
tile with a nonzero syndrome needs the per-byte correction. The fused
matmul (``ecc_qmatmul``) and the paged-attention kernels
(``paged_attention``, in-place KV strips) take this path.

The standalone kernels take ``(..., nb, 8)`` blocks but run on the 2-D
byte plane those blocks view (:func:`plane`), so every vreg lane carries
data and no 8-wide minor dim is ever laid out on the device.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import ecc
from . import platform

DEFAULT_BLK_N = 32768  # blocks per grid step (256 KiB tiles)
LANES = 128
MAX_TILE_LANES = 2048
_SIGN_KEEP = 0xFF ^ (1 << ecc.CHECK_BIT)
_WORD = 0x01010101  # bit 0 of each byte of a packed 32-bit word


def _splat(byte: int, unit: int) -> int:
    """``byte`` in every byte that ``unit`` marks (1: one byte per int32
    lane, ``_WORD``: four), as an int32 constant."""
    return int(np.array(byte * unit, np.uint32).view(np.int32))


def code_table(width: int) -> np.ndarray:
    """(8, width) int32: row j, lane c = the 7-bit code column of bit j of
    byte ``c % 8`` of its block."""
    return np.tile(ecc.COLS64_BYBYTE.T.astype(np.int32), (1, width // 8))


def _byte_pos(shape):
    return jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1) & 7


def _group_fold(t):
    """XOR over each aligned 8-lane group onto its last lane, 8g+7; the
    group's other lanes are left holding partial XORs."""
    for s in (1, 2, 4):
        t = t ^ pltpu.roll(t, s, t.ndim - 1)
    return t


def _group_xor(t):
    """XOR over each aligned 8-lane group, broadcast back to its lanes."""
    w = t.shape[-1]
    t = jnp.where(_byte_pos(t.shape) == 7, _group_fold(t), 0)
    for s in (1, 2, 4):          # copy it down to lanes 8g..8g+6
        t = t ^ pltpu.roll(t, w - s, t.ndim - 1)
    return t


def _columns(x, table, unit=1):
    """XOR of the code columns of the set bits of each byte of ``x``: int32
    bytes (``unit`` 1) or packed words (``_WORD``, four bytes per word)."""
    t = jnp.zeros_like(x)
    for j in range(8):
        t = t ^ (((x >> j) & unit) * table[j:j + 1, :])
    return t


def _syndrome(x, table):
    """x (R, W) int32 bytes -> (R, W) syndrome of each lane's block."""
    return _group_xor(_columns(x, table))


def _restore(x, unit=1):
    """Sign-bit restore: bit 6 of bytes 0..6 of each block <- bit 7."""
    restored = ((x & _splat(_SIGN_KEEP, unit)) |
                ((x >> 1) & _splat(1 << ecc.CHECK_BIT, unit)))
    return jnp.where(_byte_pos(x.shape) == 7, x, restored)


def syndrome_words(w, table):
    """w (R/4, W) int32: a ``(R, W)`` byte tile bitcast to words (four
    bytes of one lane per word); table: :func:`code_table` ``(8, W)``.
    Returns each block's syndrome on the last lane of its block (byte 7),
    packed like ``w``: every byte stays below 128, so none carries into the
    next. The block's other lanes hold partial XORs; mask them off."""
    return _group_fold(_columns(w, table, _WORD))


def restore_words(w):
    """Packed sign restore of a word tile whose syndromes are all zero:
    :func:`decode_lanes`' output bytes, packed like ``w``."""
    return _restore(w, _WORD)


def decode_lanes(x, table):
    """Decode a lane-dense tile. x: (R, W) int32 encoded bytes (0..255);
    table: :func:`code_table` ``(8, W)``. Returns (decoded bytes int32,
    single, double) with each block's flags on all 8 of its lanes."""
    syn = _syndrome(x, table)
    single = (jax.lax.population_count(syn) & 1) == 1
    double = jnp.logical_and(syn != 0, jnp.logical_not(single))
    flip = jnp.zeros_like(x)
    for j in range(8):
        flip = flip | jnp.where(syn == table[j:j + 1, :], 1 << j, 0)
    cor = jnp.where(single, x ^ flip, x)
    return _restore(cor), single, double


def encode_lanes(x, table):
    """Encode a lane-dense tile of WOT-compliant bytes (int32 0..255): bit 6
    of bytes 0..6 of every block is overwritten with its check bit."""
    pos = _byte_pos(x.shape)
    zeroed = jnp.where(pos == 7, x, x & _SIGN_KEEP)
    syn = _syndrome(zeroed, table)
    checks = ((syn >> pos) & 1) << ecc.CHECK_BIT
    return zeroed | jnp.where(pos == 7, 0, checks)


def signed(x):
    """int32 byte values 0..255 -> the int8 values they encode (as int32)."""
    return jnp.where(x >= 128, x - 256, x)


def plane(blocks):
    """``(..., nb, 8)`` blocks -> ``(x, unplane, block_flags)``: ``x`` the
    2-D byte plane the blocks view, ``unplane`` maps a plane-shaped result
    back to ``blocks.shape`` and ``block_flags`` picks one per-lane flag per
    block (shape ``blocks.shape[:-1]``).

    Rows are the leading dims and lanes the ``nb * 8`` bytes, so the pair
    of reshapes folds away and no array whose minor dim is 8 (padded to 128
    lanes on TPU) is ever laid out. A flat ``(nblk, 8)`` image, or a width
    that is not whole 128-lane vregs, is repacked as a zero-padded
    ``(rows, 128)`` plane instead."""
    shape = blocks.shape
    if blocks.ndim > 2 and (shape[-2] * 8) % LANES == 0:
        return (blocks.reshape(-1, shape[-2] * 8),
                lambda y: y.reshape(shape),
                lambda f: f[:, 7::8].reshape(shape[:-1]))
    n = blocks.size
    rows = pl.cdiv(n, LANES)
    x = jnp.pad(blocks.reshape(-1), (0, rows * LANES - n)).reshape(rows,
                                                                   LANES)
    return (x, lambda y: y.reshape(-1)[:n].reshape(shape),
            lambda f: f.reshape(-1)[7:n:8].reshape(shape[:-1]))


def plane_call(kernel, x, blk_n: int, n_out: int):
    """Run an elementwise lane-dense kernel over a ``(rows, W)`` uint8
    plane in tiles of about ``blk_n`` blocks: full-width (or 2048-lane)
    column tiles, rows in whole 32-sublane uint8 tiles."""
    r, w = x.shape
    tc = min(w, MAX_TILE_LANES)
    tr = max(1, blk_n * 8 // tc)
    tr = r if tr >= r else min(r, max(32, tr - tr % 32))
    spec = pl.BlockSpec((tr, tc), lambda i, j: (i, j))
    return pl.pallas_call(
        kernel,
        grid=(pl.cdiv(r, tr), pl.cdiv(w, tc)),
        in_specs=[spec, pl.BlockSpec((8, tc), lambda i, j: (0, 0))],
        out_specs=[spec] * n_out,
        out_shape=[jax.ShapeDtypeStruct(x.shape, jnp.uint8)] * n_out,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        interpret=platform.interpret(),
    )(x, jnp.asarray(code_table(tc)))


def _kernel(enc_ref, table_ref, dec_ref, flags_ref):
    dec, single, double = decode_lanes(enc_ref[...].astype(jnp.int32),
                                       table_ref[...])
    dec_ref[...] = dec.astype(jnp.uint8)
    flags_ref[...] = (single.astype(jnp.int32) |
                      (double.astype(jnp.int32) << 1)).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("blk_n",))
def ecc_decode(enc: jnp.ndarray, *, blk_n: int = DEFAULT_BLK_N):
    """(..., 8) uint8 blocks -> (decoded blocks, flags (...,) uint8) with
    flags bit 0 = single corrected, bit 1 = double detected."""
    x, unplane, block_flags = plane(enc)
    dec, flags = plane_call(_kernel, x, blk_n, 2)
    return unplane(dec), block_flags(flags)
