"""Backend dispatch: route codec compute through XLA or Pallas.

Every scheme op that touches 64-bit ECC blocks goes through a ``Backend``
object, selected by a single ``backend=`` switch anywhere in the public API:

* ``"xla"``    — the pure-jnp reference path (``core.ecc`` / ``kernels.ref``).
  Works everywhere, fuses into the surrounding XLA program; this is what the
  decode-on-read serving path compiles today.
* ``"pallas"`` — the fused TPU kernels (``kernels/ops.py``): tiled VMEM
  decode/encode and the decode+matmul ``ecc_qmatmul``. Compiled on a TPU
  backend and interpreted on CPU (``kernels.platform``), so the same switch
  validates on CPU and runs natively on the chip.

Backends only differ for the in-place (64,57,1) code — parity/secded72 have
no Pallas kernels and always take the jnp path inside their schemes.
"""
from __future__ import annotations

import jax.numpy as jnp

from repro.core import ecc

__all__ = ["Backend", "XlaBackend", "PallasBackend", "get_backend",
           "BACKENDS"]


class Backend:
    """Interface: in-place-code block ops + the fused protected matmul."""

    name = "abstract"

    def encode64(self, blocks: jnp.ndarray) -> jnp.ndarray:
        """(..., 8) uint8 WOT-compliant bytes -> encoded (..., 8)."""
        raise NotImplementedError

    def decode64(self, blocks: jnp.ndarray):
        """(..., 8) uint8 encoded -> (decoded (..., 8), single, double)."""
        raise NotImplementedError

    def qmatmul(self, a_q: jnp.ndarray, w_enc: jnp.ndarray, a_scale,
                w_scale) -> jnp.ndarray:
        """a_q (M,K) int8 @ decode(w_enc (K,N) uint8) * scales -> (M,N) f32."""
        raise NotImplementedError


class XlaBackend(Backend):
    name = "xla"

    def encode64(self, blocks):
        return ecc.encode64(blocks)

    def decode64(self, blocks):
        return ecc.decode64(blocks)

    def qmatmul(self, a_q, w_enc, a_scale, w_scale):
        from repro.kernels import ref
        acc = ref.ecc_qmatmul_ref(a_q, w_enc)
        return acc.astype(jnp.float32) * (a_scale * w_scale)


class PallasBackend(Backend):
    """Tiled VMEM kernels over the byte plane a block view comes from
    (``kernels.ecc_decode.plane``), any block shape; ``blk_n`` is the
    blocks per grid step."""

    name = "pallas"

    def __init__(self, *, blk_n: int = 32768):
        self.blk_n = blk_n

    def encode64(self, blocks):
        from repro.kernels import ecc_encode
        return ecc_encode.ecc_encode(blocks, blk_n=self.blk_n)

    def decode64(self, blocks):
        from repro.kernels import ecc_decode
        dec, flags = ecc_decode.ecc_decode(blocks.astype(jnp.uint8),
                                           blk_n=self.blk_n)
        return dec, (flags & 1) == 1, (flags & 2) == 2

    def qmatmul(self, a_q, w_enc, a_scale, w_scale):
        from repro.kernels import ops
        return ops.qmatmul_protected(a_q, w_enc, a_scale, w_scale)


BACKENDS = {"xla": XlaBackend, "pallas": PallasBackend}

def get_backend(backend, **kw) -> Backend:
    """Resolve a backend name or pass an instance through."""
    if isinstance(backend, Backend):
        return backend
    if backend is None:
        backend = "xla"
    try:
        return BACKENDS[backend](**kw)
    except KeyError:
        raise ValueError(
            f"unknown backend {backend!r}; one of {sorted(BACKENDS)}") from None
