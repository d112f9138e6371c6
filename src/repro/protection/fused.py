"""``ProtectedWeight`` — lazy decode-at-use carrier for one protected leaf.

The decode-at-use serving step replaces each (per-layer) ``ProtectedTensor``
with a ``ProtectedWeight`` view instead of decoding the whole tree up front.
The view defers ALL codec work to the weight's point of use inside the
model:

* ``matmul(x)`` — the projection path. Float activations take the fused
  ``kernels.ecc_qmatmul`` float path on the Pallas route (decode in VMEM on
  the way to the MXU — zero decoded bytes ever hit HBM) or a per-leaf inline
  decode + matmul elsewhere. With an activation-quant decision
  (``act_quant`` = "static" calibrated scale | "dynamic" per-token absmax)
  the view quantizes the activations to int8 first and runs the kernel's
  fused requantize epilogue — int8 MXU throughput, int32 accumulation, and
  a bf16 result straight out of VMEM. The non-fused int8 route (XLA backend,
  flat images) is the literal quantize -> decode -> int8-matmul -> rescale
  sequence, bit-identical to the epilogue (both scale one exact int32
  accumulator by ``a_scale * w_scale`` in f32).
* ``astype(dtype)`` — the fallback for non-projection uses (router einsums,
  gate matmuls, 3-D expert weights): decodes just this leaf, with flags.

Both paths report ``(corrected, due)`` int32 counts through the ``record``
callback, which the serving step wires to the per-layer flags sink in
``models.layers`` — the FT-CNN-style fault accounting that used to be
discarded by the kernel. An optional ``observe`` callback receives each
float activation absmax — the calibration pass uses it to derive static
``a_scale`` values from a small batch.

``models.layers._proj`` recognizes the view by its ``decode_at_use`` class
attribute (duck typing — layers never imports this module).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro.core import quant

from .backends import get_backend
from .policy import decode_leaf_with_flags
from .schemes import get_scheme
from .tensor import ProtectedTensor

__all__ = ["ProtectedWeight", "can_fuse"]


def can_fuse(pt: ProtectedTensor, backend) -> bool:
    """True when this leaf can route through the fused decode+matmul kernel:
    Pallas backend, in-place scheme, 2-D same-shape image (ECC blocks along
    the output dim)."""
    name = getattr(backend, "name", backend) or "xla"
    return (name == "pallas" and pt.scheme_id == "in-place"
            and not pt.is_flat and getattr(pt.enc, "ndim", 0) == 2)


def is_matmul_weight(path: str) -> bool:
    """True when the leaf is consumed as the RHS of a matmul/einsum — the
    only uses a lazy view can serve. Depthwise conv kernels (``conv_w``) are
    indexed elementwise by ``layers._causal_conv`` and must decode to real
    arrays instead."""
    last = path.rsplit("/", 1)[-1]
    return not last.startswith("conv")


class ProtectedWeight:
    """One leaf's decode-at-use view (see module docstring).

    pt:         the (already per-layer-sliced) ProtectedTensor.
    backend:    Backend instance or name for this leaf's codec compute.
    record:     ``record(corrected, due)`` flags callback (no-op when None).
    act_quant:  None (float activations) | "dynamic" (per-token absmax) |
                "static" (needs ``a_scale``) — the int8 MXU serve path.
    a_scale:    calibrated static activation scale (float).
    observe:    ``observe(absmax)`` callback fed each float activation's
                absmax (the calibration hook; no-op when None).
    abft:       verify ABFT checksums on every matmul (in-kernel on the
                fused route, the ``kernels.ref.abft_counts`` mirror on the
                XLA route — same math, backend parity).
    clamp:      per-leaf activation absmax: epilogue output clipped to
                ``[-clamp, +clamp]``, hits counted (Geissler-style range
                supervision).
    record_abft: ``record_abft(mismatches, clamp_hits)`` callback; scalars,
                or per-output-row (M,) vectors when ``abft_per_slot`` (the
                column-check count is not row-attributable and then rides
                only the scalar channel).
    """

    decode_at_use = True  # the marker layers._proj dispatches on

    def __init__(self, pt: ProtectedTensor, backend="xla", *,
                 record: Optional[Callable] = None,
                 act_quant: Optional[str] = None,
                 a_scale: Optional[float] = None,
                 observe: Optional[Callable] = None,
                 abft: bool = False,
                 clamp: Optional[float] = None,
                 record_abft: Optional[Callable] = None,
                 abft_per_slot: bool = False):
        if act_quant not in (None, "static", "dynamic"):
            raise ValueError(f"act_quant {act_quant!r}; one of "
                             f"(None, 'static', 'dynamic')")
        if act_quant == "static" and a_scale is None:
            raise ValueError("act_quant='static' needs a calibrated a_scale")
        self.pt = pt
        self.backend = get_backend(backend)
        self.fuse = can_fuse(pt, self.backend)
        self.act_quant = act_quant
        self.a_scale = a_scale
        self.abft = bool(abft)
        self.clamp = None if clamp is None else float(clamp)
        self.abft_per_slot = abft_per_slot
        self._record = record
        self._record_abft = record_abft
        self._observe = observe

    # -- array-protocol surface (enough for every call site in layers.py) ----

    @property
    def shape(self):
        return tuple(self.pt.orig_shape)

    @property
    def ndim(self):
        return len(self.pt.orig_shape)

    def record(self, corrected, due):
        if self._record is not None:
            self._record(corrected, due)

    @property
    def _track(self):
        """ABFT and/or clamp accounting active for this leaf."""
        return self.abft or self.clamp is not None

    def record_abft(self, row_mm, clamp_hits, col_mm):
        """Report (mismatches, clamp hits) — per-row vectors when the serve
        step wants per-slot attribution, else scalars (the scalar mismatch
        total additionally includes the column-check count)."""
        if self._record_abft is None:
            return
        if self.abft_per_slot:
            self._record_abft(row_mm, clamp_hits)
        else:
            self._record_abft(jnp.sum(row_mm) + col_mm, jnp.sum(clamp_hits))

    def astype(self, dtype):
        """Decode just this leaf (recording flags) -> dequantized array."""
        w, corrected, due = decode_leaf_with_flags(self.pt, dtype,
                                                   backend=self.backend)
        self.record(corrected, due)
        return w

    # -- int8 path internals -------------------------------------------------

    def _decode_q(self):
        """Decode to RAW int8 weights (no dequantization), with flags."""
        scheme = get_scheme(self.pt.scheme_id)
        q, corrected, due = scheme.decode_with_flags(self.pt.enc,
                                                     self.pt.checks,
                                                     self.backend)
        if self.pt.is_flat:
            q = q.reshape(-1)[: self.pt.n_weights].reshape(self.pt.orig_shape)
        return q, corrected, due

    def _quantize_x(self, x2):
        """(M, K) float -> (int8 q, f32 a_scale (scalar | (M, 1)))."""
        xf = x2.astype(jnp.float32)
        if self.act_quant == "static":
            a_scale = jnp.asarray(self.a_scale, jnp.float32)
        else:  # dynamic per-token absmax
            a_scale = quant.compute_scale(xf, axis=1)  # (M, 1)
        q, _ = quant.quantize(xf, scale=a_scale)
        return q, a_scale

    def _int8_matmul(self, q_x, a_scale, out_dtype):
        """``q_x (M,K) int8 @ decode(enc)`` with the fused requantize
        epilogue (Pallas route) or the inline quantize->decode->matmul
        reference (every other route) — bit-identical value paths: one
        exact int32 accumulator scaled by ``a_scale * w_scale`` in f32."""
        if self.fuse:
            from repro.kernels.ecc_qmatmul import ecc_qmatmul
            res = ecc_qmatmul(q_x, self.pt.enc, self.pt.scale,
                              a_scale=a_scale, out_dtype=out_dtype,
                              with_flags=True,
                              with_abft=self.abft, clamp=self.clamp)
            if self._track:
                out, flags, (rows, col_mm) = res
                self.record_abft(rows[:, 0], rows[:, 1], col_mm)
            else:
                out, flags = res
            self.record(flags[0], flags[1])
            return out
        q_w, corrected, due = self._decode_q()
        self.record(corrected, due)
        if not self._track:
            # quant.int8_matmul is the single source of the epilogue's value
            # path: exact int32 accumulator * (a_scale * w_scale) in f32
            return quant.int8_matmul(q_x, q_w, a_scale,
                                     self.pt.scale).astype(out_dtype)
        # XLA mirror of the guarded epilogue: the same int32 accumulator
        # (quant.int8_acc IS int8_matmul's accumulator) checked by the
        # same ABFT pair, then the identical rescale.
        from repro.kernels import ref
        acc = quant.int8_acc(q_x, q_w)
        if self.abft:
            row_mm, col_bad = ref.abft_counts(q_x, q_w, acc)
            col_mm = jnp.sum(col_bad)
        else:
            row_mm = jnp.zeros((q_x.shape[0],), jnp.int32)
            col_mm = jnp.int32(0)
        out = acc.astype(jnp.float32) * (a_scale * self.pt.scale)
        if self.clamp is not None:
            out, hits = ref.clamp_counts(out, self.clamp)
        else:
            hits = jnp.zeros_like(row_mm)
        self.record_abft(row_mm, hits, col_mm)
        return out.astype(out_dtype)

    # -- the projection entry point ------------------------------------------

    def matmul(self, x):
        """``x @ decode(self)`` with decode at the point of use.

        Float ``x``: fused float path / inline decode (value path identical
        to decode-then-matmul); with an ``act_quant`` decision, ``x`` is
        quantized here and served over the int8 MXU path instead. int8 ``x``
        is accepted when a static ``a_scale`` says what the integers mean.
        """
        lead = x.shape[:-1]
        a2 = x.reshape(-1, x.shape[-1])
        n_out = self.pt.orig_shape[-1]
        if not jnp.issubdtype(x.dtype, jnp.floating):
            # pre-quantized activations: meaningful only at a known scale
            if self.act_quant != "static":
                raise TypeError(
                    f"ProtectedWeight.matmul got raw {x.dtype} activations "
                    f"without a static a_scale; serve float activations, or "
                    f"plan.with_act_quant('static', scales) so the view "
                    f"knows the quantization scale")
            out = self._int8_matmul(a2, jnp.asarray(self.a_scale, jnp.float32),
                                    jnp.bfloat16)
            return out.reshape(*lead, n_out)
        if self._observe is not None:
            self._observe(jnp.max(jnp.abs(a2.astype(jnp.float32))))
        if self.act_quant is not None:
            q_x, a_scale = self._quantize_x(a2)
            out = self._int8_matmul(q_x, a_scale, x.dtype)
            return out.astype(x.dtype).reshape(*lead, n_out)
        if not self.fuse:
            if not self._track:
                return x @ self.astype(x.dtype)
            from repro.kernels import ref
            w = self.astype(x.dtype)
            # check the f32 accumulator, as the kernel does — a bf16 dot's
            # rounded output would trip the float tolerance spuriously; the
            # value path stays identical (f32 accumulate, one final round)
            acc = jax.lax.dot_general(
                a2, w, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if self.abft:
                row_mm, col_bad = ref.abft_counts(a2, w, acc)
                col_mm = jnp.sum(col_bad)
            else:
                row_mm = jnp.zeros((a2.shape[0],), jnp.int32)
                col_mm = jnp.int32(0)
            if self.clamp is not None:
                acc, hits = ref.clamp_counts(acc, self.clamp)
            else:
                hits = jnp.zeros_like(row_mm)
            self.record_abft(row_mm, hits, col_mm)
            return acc.astype(x.dtype).reshape(*lead, n_out)
        from repro.kernels.ecc_qmatmul import ecc_qmatmul
        # the kernel's default full-K tiles give one f32 dot per output
        # tile, so the accumulation order — and hence every logit — is
        # bit-identical to decode-then-matmul
        res = ecc_qmatmul(a2, self.pt.enc, self.pt.scale, with_flags=True,
                          with_abft=self.abft, clamp=self.clamp)
        if self._track:
            out, flags, (rows, col_mm) = res
            self.record_abft(rows[:, 0], rows[:, 1], col_mm)
        else:
            out, flags = res
        self.record(flags[0], flags[1])
        return out.astype(x.dtype).reshape(*lead, self.pt.enc.shape[1])

    def __repr__(self):
        return (f"ProtectedWeight({self.pt!r}, backend={self.backend.name!r}, "
                f"fuse={self.fuse}, act_quant={self.act_quant!r})")
