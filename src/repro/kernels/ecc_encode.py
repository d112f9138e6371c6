"""Pallas TPU kernel: in-place SEC-DED (64,57,1) encode.

Runs once at deployment (and inside the protected-checkpoint writer): takes
WOT-compliant int8 weights, computes the 7 check bits per 64-bit block and
writes them into the non-informative bits. Memory-bound one-pass kernel,
mirror image of `ecc_decode` and built on the same lane-dense block codec
(:func:`ecc_decode.encode_lanes`).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from . import ecc_decode

DEFAULT_BLK_N = ecc_decode.DEFAULT_BLK_N


def _kernel(w_ref, table_ref, out_ref):
    out_ref[...] = ecc_decode.encode_lanes(
        w_ref[...].astype(jnp.int32), table_ref[...]).astype(jnp.uint8)


@functools.partial(jax.jit, static_argnames=("blk_n",))
def ecc_encode(blocks: jnp.ndarray, *,
               blk_n: int = DEFAULT_BLK_N) -> jnp.ndarray:
    """(..., 8) uint8 blocks (WOT-compliant int8 bytes) -> encoded, same
    shape."""
    x, unplane, _ = ecc_decode.plane(blocks.astype(jnp.uint8))
    (out,) = ecc_decode.plane_call(_kernel, x, blk_n, 1)
    return unplane(out)
