"""Pallas TPU kernel: causal flash attention (online softmax).

This is the kernel that closes the prefill-32k memory gap identified in
EXPERIMENTS.md §Perf cell C: the XLA-level chunked attention materializes
f32 score chunks in HBM; this kernel keeps the running (o, m, l) state in
VMEM and never writes scores out.

Grid (B*H, Sq/BQ, Sk/BK) with the KV dimension innermost; the causal
triangle is honoured per-tile: fully-masked tiles still iterate (Pallas
grids are dense) but exit without compute via @pl.when. Tiles are
MXU-aligned (BQ, BK multiples of 128, head_dim typically 64..256).

VMEM per step: BQ*D (q) + BK*D (k,v) + BQ*BK (scores) + BQ*D (o acc)
= for 128x128xD=128 fp32: ~0.4 MB.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from . import platform

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, *, bq, bk, scale):
    i = pl.program_id(1)  # q tile
    j = pl.program_id(2)  # kv tile

    @pl.when(j == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(j * bk <= i * bq + bq - 1)  # tile intersects the causal triangle
    def _compute():
        q = q_ref[0]                       # (BQ, D)
        k = k_ref[0]                       # (BK, D)
        v = v_ref[0]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # (BQ, BK)
        qpos = i * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        s = jnp.where(qpos >= kpos, s, NEG_INF)

        m_prev = m_ref[0]                  # (BQ,)
        m_cur = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m_prev - m_new)
        l_ref[0] = l_ref[0] * alpha + jnp.sum(p, axis=-1)
        pv = jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        o_ref[0] = o_ref[0] * alpha[:, None] + pv
        m_ref[0] = m_new


def _norm_kernel(o_ref, l_ref, out_ref):
    out_ref[...] = (o_ref[...] /
                    jnp.maximum(l_ref[...], 1e-30)[..., None]).astype(
        out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bq", "bk"))
def flash_attention(q, k, v, *, bq: int = 128, bk: int = 128):
    """Causal flash attention. q,k,v: (B, H, S, D) -> (B, H, S, D).

    GQA callers broadcast KV heads beforehand (or reshape to grouped form).
    """
    b, h, s, d = q.shape
    dtype = q.dtype
    bq = min(bq, s)
    bk = min(bk, s)
    assert s % bq == 0 and s % bk == 0, (s, bq, bk)
    scale = 1.0 / np.sqrt(d)
    qf = q.reshape(b * h, s, d)
    kf = k.reshape(b * h, s, d)
    vf = v.reshape(b * h, s, d)
    grid = (b * h, s // bq, s // bk)

    o, m, l = pl.pallas_call(
        functools.partial(_kernel, bq=bq, bk=bk, scale=scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
            pl.BlockSpec((1, bk, d), lambda g, i, j: (g, j, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda g, i, j: (g, i, 0)),
            pl.BlockSpec((1, bq), lambda g, i, j: (g, i)),
            pl.BlockSpec((1, bq), lambda g, i, j: (g, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s, d), jnp.float32),
            jax.ShapeDtypeStruct((b * h, s), jnp.float32),
            jax.ShapeDtypeStruct((b * h, s), jnp.float32),
        ],
        interpret=platform.interpret(),
    )(qf, kf, vf)
    out = pl.pallas_call(
        _norm_kernel,
        grid=(b * h, s // bq),
        in_specs=[pl.BlockSpec((1, bq, d), lambda g, i: (g, i, 0)),
                  pl.BlockSpec((1, bq), lambda g, i: (g, i))],
        out_specs=pl.BlockSpec((1, bq, d), lambda g, i: (g, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, d), dtype),
        interpret=platform.interpret(),
    )(o, l)
    return out.reshape(b, h, s, d)
