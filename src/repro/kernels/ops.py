"""Public jit'd wrappers around the Pallas kernels (compiled on a TPU,
interpreted elsewhere — see :mod:`repro.kernels.platform`)."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core import ecc
from . import ecc_decode as _dec
from . import ecc_qmatmul as _qmm
from . import throttle as _thr


def decode_weights(enc_flat: jnp.ndarray):
    """Flat uint8 ECC-encoded image (n % 8 == 0) -> (int8 weights, flags)."""
    blocks = enc_flat.reshape(-1, ecc.BLOCK_BYTES)
    dec, flags = _dec.ecc_decode(blocks)
    w = jax.lax.bitcast_convert_type(dec.reshape(-1), jnp.int8)
    return w, flags


def qmatmul_protected(a_q: jnp.ndarray, w_enc: jnp.ndarray, a_scale,
                      w_scale) -> jnp.ndarray:
    """float output = (a_q @ decode(w_enc)) * a_scale * w_scale."""
    acc = _qmm.ecc_qmatmul(a_q, w_enc)
    return acc.astype(jnp.float32) * (a_scale * w_scale)


def throttle_flat(q_flat: jnp.ndarray) -> jnp.ndarray:
    """WOT projection on a flat int8 vector (n % 8 == 0)."""
    out = _thr.throttle(q_flat.reshape(-1, 8))
    return out.reshape(-1)


def encode_weights(q_flat: jnp.ndarray):
    """Flat int8 WOT-compliant weights (n % 8 == 0) -> encoded uint8 image."""
    from . import ecc_encode as _enc
    blocks = jax.lax.bitcast_convert_type(q_flat, jnp.uint8).reshape(-1, 8)
    return _enc.ecc_encode(blocks).reshape(-1)


def attention(q, k, v, *, bq: int = 128, bk: int = 128):
    """Causal flash attention (B, H, S, D) -> (B, H, S, D)."""
    from . import flash_attention as _fa
    return _fa.flash_attention(q, k, v, bq=bq, bk=bk)


def deploy_quantize(w):
    """fp32 weight tensor -> (WOT-compliant int8 (same shape), scale).
    Fused quantize+throttle; requires last dim % 8 == 0."""
    from . import quant_throttle as _qt
    q, scale = _qt.quantize_throttle(w.reshape(-1, 8))
    return q.reshape(w.shape), scale
