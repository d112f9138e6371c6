"""Pallas TPU kernels: fused ECC page decode + single-token attention.

The paged KV cache (``serving.kvcache``) keeps keys/values ECC-encoded at
rest; these kernels decode each sequence's pages in VMEM on their way into
the attention dots — the serving-state twin of ``ecc_qmatmul``'s
decode-at-use weight path. Protection then costs zero HBM space (in-place
scheme) AND zero extra HBM traffic: the encoded strip is what streams in,
and no decoded copy of the cache ever lands in HBM.

Two kernels, one contract each:

**Strip kernel** (:func:`fused_page_attention`). Grid (B, KV): one step
owns the whole gathered (S, hd) K and V strips for one (batch, kv-head)
pair, block-decodes them (per-token flag counts), dequantizes with the
per-token page scales, and computes all rep = H/KV query heads of that
group in full-sequence form. Deliberately NO online softmax: the op/dtype
sequence exactly mirrors ``layers.decode_attention`` (bf16 score dot ->
f32 scale + mask -> ``jax.nn.softmax`` -> dtype cast -> PV dot), which is
what makes the fused path BIT-IDENTICAL to the XLA decode-then-attend
reference *compiled as one program* (the serving paths always jit it;
eager op-by-op execution materializes an intermediate bf16 rounding of
the score dot that fused compilation elides, costing ~1 ulp). VMEM holds
the full strip (see :func:`strip_vmem_bytes`) — fine for decode contexts
to a few k tokens, a hard wall long before 500k-class contexts.

**Chunked kernel** (:func:`chunked_page_attention`). Grid (B, KV,
n_chunks) with the chunk axis innermost and sequential: each step streams
ONE fixed-size page chunk through VMEM (decode ECC block -> int8 dequant
-> f32) and folds it into running online-softmax state (max m, normalizer
l, accumulator acc) held in VMEM scratch, so the VMEM working set is
bounded by the CHUNK size, not the context length
(:func:`chunked_vmem_bytes`). The price is the bit-identity contract:
online softmax reassociates the reduction and the chunked path computes
in f32 rather than replaying the reference's bf16 op sequence, so its
output is only tolerance-close to the reference. It therefore lives
behind an explicit ``KVProtectionPolicy(attention_impl="chunked")`` knob
and is validated against :func:`oracle_page_attention` — an fp64 oracle
over the SAME encoded strips — instead of a bit-equality check. Flag
counts (integer, decode-exact) still match the reference exactly.

The page-table gather itself (pool -> (B, S, ...) strips) stays in XLA
before the ``pallas_call``, as does the transpose to (B, KV, S, hd) that
makes each grid cell's strip a TPU-legal block: gathers are layout
transforms XLA schedules well, while the kernels own everything that must
not leave VMEM decoded.

Both kernels decode a grid step's K and V strips with the lane-dense codec
shared with the fused matmul: the 8-byte ECC blocks run along ``hd``, the
lane axis. Under the in-place scheme, where the strip's row count is a
multiple of 32 (every serving shape), a fast pass bitcasts
(``pltpu.bitcast``) each uint8 ``(s, hd)`` strip to ``(s/4, hd)`` int32
words, four rows of one lane per word, computes every block's syndrome
four bytes per op (``ecc_decode.syndrome_words``), stores the packed sign
restore (``restore_words``) bitcast back to int8 in a VMEM scratch strip,
and ORs the syndromes of valid blocks (rows ``<= pos``, masked by a row
mask packed the way the words are) of both strips into one accumulator.
Only if that accumulator is nonzero (one branch per grid step) does the
correcting pass re-check the strips in 32-row chunks and run the per-byte
``ecc_decode.decode_lanes`` path, with its flag counts, on each chunk that
holds a faulty valid block, overwriting what the fast pass stored there.
A strip whose valid syndromes are all zero decodes to exactly its
sign-restored bytes with no flags, so values and flags are bit-identical
to a per-byte decode of every byte; bytes past ``pos`` may stay
uncorrected, but they are finite and their softmax weight is exactly 0.
Lane ``_SLOW`` of a grid cell's flag row counts the chunks that took the
correcting pass (:func:`_fused_call` and :func:`_chunked_call` return the
raw rows). Other row counts, and the faulty and parity-zero schemes,
decode every byte per byte.

Flags (corrected, DUE) are masked to valid (``<= pos``) tokens inside the
kernel, summed per (batch, kv-head) cell, and reduced outside — per
batch row (``per_slot=True``, for per-request fault attribution) or to
batch-total scalars.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ecc_decode, platform

KV_SCHEMES = ("faulty", "parity-zero", "in-place")

# ~VMEM per TPU core (v4/v5 class) — the budget the strip kernel's whole
# gathered working set must fit inside, and the denominator of the
# structural crossover that strip_vmem_crossover computes.
VMEM_BUDGET_BYTES = 16 * 2 ** 20

# lanes of a grid cell's (1, 128) flag row: corrected and detected blocks
# over valid tokens, row chunks that took the correcting pass
_COR, _DUE, _SLOW = 0, 1, 2
# rows the correcting pass re-checks at a time: one (32, 128) uint8 tile
_CHUNK = 32


def _count(hit, valid_col):
    """(s, W) bool hits over valid (s, 1) tokens -> (1, 1) int32 count."""
    n = jnp.where(valid_col, hit.astype(jnp.int32), 0)
    return jnp.sum(jnp.sum(n, axis=0, keepdims=True), axis=1, keepdims=True)


def _decode_strip(enc, ch, valid_col, table, *, scheme):
    """Decode one (s, hd) uint8 encoded strip in-kernel.

    -> (int8 values as int32 (s, hd), corrected (1, 1), due (1, 1)) — flag
    counts already masked to ``valid_col`` (s, 1) tokens. Shared by the
    strip and chunked kernels so both observe identical per-token fault
    accounting.
    """
    x = enc.astype(jnp.int32)
    zero = jnp.zeros((1, 1), jnp.int32)
    if scheme == "faulty":
        return ecc_decode.signed(x), zero, zero
    s, hd = x.shape
    lane = jax.lax.broadcasted_iota(jnp.int32, (s, hd), 1)
    if scheme == "parity-zero":
        # restatement of ecc.decode_parity8: byte j's stored parity is bit
        # (j % 8) of check byte j // 8. An exact 0/1 matmul spreads each
        # check byte over its 8 lanes.
        nb = ch.shape[-1]
        spread = (jax.lax.broadcasted_iota(jnp.int32, (nb, hd), 1) // 8 ==
                  jax.lax.broadcasted_iota(jnp.int32, (nb, hd), 0))
        stored = jax.lax.dot_general(
            ch.astype(jnp.int32).astype(jnp.float32),
            spread.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
        bad = (jax.lax.population_count(x) & 1) != ((stored >> (lane & 7)) & 1)
        return (ecc_decode.signed(jnp.where(bad, 0, x)),
                _count(bad, valid_col), zero)
    dec, single, double = ecc_decode.decode_lanes(x, table)
    block_lane = (lane & 7) == 7      # count each block once
    return (ecc_decode.signed(dec),
            _count(jnp.logical_and(single, block_lane), valid_col),
            _count(jnp.logical_and(double, block_lane), valid_col))


def _flag_row(cor, due, slow):
    """(1, 1) counts -> the (1, 128) lane row a grid cell writes."""
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 128), 1)
    return jnp.where(lane == _COR, cor, jnp.where(
        lane == _DUE, due, jnp.where(lane == _SLOW, slow, 0)))


def _unpack(refs, has_checks, packed):
    """Positional kernel refs -> dict (the check planes ride only with the
    parity-zero scheme, the decoded-strip scratch only with the packed
    pass, after the kernel's own scratch)."""
    it = iter(refs)
    names = ["q", "ke"] + (["kch"] if has_checks else []) + \
        ["ksc", "ve"] + (["vch"] if has_checks else []) + \
        ["vsc", "pos", "table", "o", "flags"]
    r = {name: next(it) for name in names}
    r["scratch"] = list(it)
    if packed:
        r["kdec"], r["vdec"] = r["scratch"][-2:]
        del r["scratch"][-2:]
    return r


def _packed_strips(r, first, pos, table):
    """In-place decode of this grid step's K and V strips, four bytes per
    int32 op, into the ``kdec``/``vdec`` int8 scratch; the per-byte path
    only on 32-row chunks whose valid blocks have a nonzero syndrome.
    Rows ``first + i`` with ``i`` the strip row are valid ``<= pos``.
    Returns (kq, vq, cor, due, slow)."""
    s, hd = r["kdec"].shape
    strips = (("ke", r["kdec"]), ("ve", r["vdec"]))
    block_lane = (jax.lax.broadcasted_iota(jnp.int32, (1, hd), 1) & 7) == 7

    def valid(r0, n):
        return first + r0 + jax.lax.broadcasted_iota(jnp.int32, (n, 1),
                                                     0) <= pos

    def dirty(syn, r0, n):
        """Does a valid block (last lane, row <= pos) have a syndrome? The
        row mask is packed the way the words are; syndrome bytes are below
        128, so the words are >= 0."""
        mask = jnp.broadcast_to(jnp.where(valid(r0, n), -1, 0), (n, hd))
        syn = syn & pltpu.bitcast(mask.astype(jnp.int8), jnp.int32)
        return jnp.max(jnp.where(block_lane, syn, 0)) > 0

    acc = jnp.zeros((s // 4, hd), jnp.int32)
    for name, dec in strips:
        words = pltpu.bitcast(r[name][0, 0], jnp.int32)
        dec[...] = pltpu.bitcast(ecc_decode.restore_words(words), jnp.int8)
        acc = acc | ecc_decode.syndrome_words(words, table)

    def recheck(c, counts):
        """Redo chunk ``c`` of each strip per byte if a valid block of it
        is faulty."""
        r0 = pl.multiple_of(c * _CHUNK, _CHUNK)
        for name, dec in strips:
            enc = r[name][0, 0, pl.ds(r0, _CHUNK), :]

            def redo(counts, enc=enc, dec=dec):
                q, cor, due = _decode_strip(enc, None, valid(r0, _CHUNK),
                                            table, scheme="in-place")
                dec[pl.ds(r0, _CHUNK), :] = q.astype(jnp.int8)
                return counts[0] + cor, counts[1] + due, counts[2] + 1

            syn = ecc_decode.syndrome_words(
                pltpu.bitcast(enc, jnp.int32), table)
            counts = jax.lax.cond(dirty(syn, r0, _CHUNK), redo,
                                  lambda c: c, counts)
        return counts

    zero = jnp.zeros((1, 1), jnp.int32)
    counts = jax.lax.cond(
        dirty(acc, 0, s),
        lambda c: jax.lax.fori_loop(0, s // _CHUNK, recheck, c),
        lambda c: c, (zero, zero, zero))
    return (r["kdec"][...], r["vdec"][...], *counts)


def _strips(r, first, pos, scheme):
    """Decode this grid step's K and V strips, rows ``first + i`` valid
    ``<= pos`` -> (kq, vq, cor, due, slow): int values as any int dtype,
    (1, 1) counts."""
    table = r["table"][...]
    if "kdec" in r:
        return _packed_strips(r, first, pos, table)
    s = r["ke"].shape[2]
    valid_col = first + jax.lax.broadcasted_iota(jnp.int32, (s, 1), 0) <= pos
    ch = lambda name: r[name][0, 0] if name in r else None
    kq, kcor, kdue = _decode_strip(r["ke"][0, 0], ch("kch"), valid_col,
                                   table, scheme=scheme)
    vq, vcor, vdue = _decode_strip(r["ve"][0, 0], ch("vch"), valid_col,
                                   table, scheme=scheme)
    return kq, vq, kcor + vcor, kdue + vdue, jnp.zeros((1, 1), jnp.int32)


def _kernel(*refs, scheme, s, has_checks, packed):
    r = _unpack(refs, has_checks, packed)
    qb = r["q"][0, 0]                                  # (rep, hd)
    hd = qb.shape[-1]
    pos = r["pos"][pl.program_id(0)]
    # 2-D iotas throughout (Mosaic rejects rank-1 iota outside interpret)
    valid_row = jax.lax.broadcasted_iota(jnp.int32, (1, s), 1) <= pos
    kq, vq, cor, due, slow = _strips(r, 0, pos, scheme)
    cdt = qb.dtype
    kf = (kq.astype(jnp.float32) * r["ksc"][0]).astype(cdt)   # (s, hd)
    vf = (vq.astype(jnp.float32) * r["vsc"][0]).astype(cdt)
    # score path mirrors layers.decode_attention as XLA compiles it (bit-
    # identity): the bf16 score dot feeds an f32 cast, which XLA folds into
    # an f32-accumulated dot; the PV dot rounds its f32 sum once to bf16
    sc = jax.lax.dot_general(qb, kf,
                             dimension_numbers=(((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32)
    sc = sc * (1.0 / np.sqrt(hd))                      # (rep, s)
    sc = jnp.where(valid_row, sc, -1e30)
    pr = jax.nn.softmax(sc, axis=-1).astype(cdt)
    r["o"][0, 0] = jax.lax.dot_general(
        pr, vf, dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(r["o"].dtype)
    r["flags"][0, 0] = _flag_row(cor, due, slow)


def _reduce_flags(flags, per_slot: bool):
    """(b, kv, 1, 128) in-grid flag rows -> (2, b) per-slot rows or (2,)
    batch totals."""
    flags = flags[:, :, 0, _COR:_DUE + 1]
    if per_slot:
        return flags.sum(axis=1).T                     # (2, b)
    return flags.sum(axis=(0, 1))                      # (2,)


def _call(kernel, q, ke, kch, ksc, ve, vch, vsc, pos, *, scheme, sblk,
          seq_block, grid, scratch=(), semantics):
    """Shared pallas_call plumbing of both kernels. Strips are gathered in
    XLA as (B, S, KV, x) and handed over as (B, KV, S, x) so each grid
    cell's (S, hd) strip is a block whose last two dims are whole array
    dims; scales ride as (B, S, 1) columns, positions in SMEM. Returns
    the (B, KV, rep, hd) output and the raw (B, KV, 1, 128) flag rows."""
    b, h, _, hd = q.shape
    kv = ke.shape[2]
    rep = h // kv
    has_checks = scheme == "parity-zero"
    packed = scheme == "in-place" and sblk % _CHUNK == 0
    if packed:   # the decoded K and V strips
        scratch = [*scratch, *[pltpu.VMEM((sblk, hd), jnp.int8)] * 2]
    if has_checks and kch is None:
        kch = jnp.zeros((*ke.shape[:3], hd // 8), jnp.uint8)
        vch = jnp.zeros((*ve.shape[:3], hd // 8), jnp.uint8)
    heads = lambda bi, g, *c: (bi, g, 0, 0)
    strip = lambda bi, g, *c: (bi, g, seq_block(*c), 0)
    column = lambda bi, g, *c: (bi, seq_block(*c), 0)
    to_kv = lambda a: a.transpose(0, 2, 1, 3)
    ops, specs = [q[:, :, 0, :].reshape(b, kv, rep, hd)], \
        [pl.BlockSpec((1, 1, rep, hd), heads)]
    for enc, ch, sc in ((ke, kch, ksc), (ve, vch, vsc)):
        ops.append(to_kv(enc))
        specs.append(pl.BlockSpec((1, 1, sblk, hd), strip))
        if has_checks:
            ops.append(to_kv(ch))
            specs.append(pl.BlockSpec((1, 1, sblk, hd // 8), strip))
        ops.append(sc.reshape(*sc.shape, 1))
        specs.append(pl.BlockSpec((1, sblk, 1), column))
    ops += [pos.reshape(b).astype(jnp.int32),
            jnp.asarray(ecc_decode.code_table(hd))]
    specs += [pl.BlockSpec(memory_space=pltpu.SMEM),
              pl.BlockSpec((8, hd), lambda *_: (0, 0))]
    return pl.pallas_call(
        functools.partial(kernel, scheme=scheme, has_checks=has_checks,
                          packed=packed),
        grid=grid,
        in_specs=specs,
        out_specs=[pl.BlockSpec((1, 1, rep, hd), heads),
                   pl.BlockSpec((1, 1, 1, 128), heads)],
        out_shape=[jax.ShapeDtypeStruct((b, kv, rep, hd), q.dtype),
                   jax.ShapeDtypeStruct((b, kv, 1, 128), jnp.int32)],
        scratch_shapes=list(scratch),
        compiler_params=pltpu.CompilerParams(dimension_semantics=semantics),
        interpret=platform.interpret(),
    )(*ops)


@functools.partial(jax.jit, static_argnames=("scheme", "per_slot"))
def fused_page_attention(q, ke, kch, ksc, ve, vch, vsc, pos, *,
                         scheme: str = "in-place", per_slot: bool = False):
    """Fused decode-at-use attention over gathered encoded KV strips.

    q:        (B, H, 1, hd) float query (hd % 8 == 0).
    ke/ve:    (B, S, KV, hd) uint8 encoded strips (``kvcache._gather_seq``).
    kch/vch:  (B, S, KV, hd // 8) uint8 parity check bytes, or None.
    ksc/vsc:  (B, S) f32 per-token scales.
    pos:      (B,) int32 current positions; tokens > pos are masked.

    Returns ``(o (B, H, 1, hd) q.dtype, flags)`` — o bit-identical to
    decode-then-``layers.decode_attention``; flags are the (corrected,
    DUE) counts over valid tokens of both strips, as per-batch-row
    ``(2, B)`` rows when ``per_slot`` (per-request fault attribution for
    the serving front-end) else batch-total ``(2,)`` scalars.
    """
    if scheme not in KV_SCHEMES:
        raise ValueError(f"scheme {scheme!r}; one of {KV_SCHEMES}")
    out, rows = _fused_call(q, ke, kch, ksc, ve, vch, vsc, pos,
                            scheme=scheme)
    return out, _reduce_flags(rows, per_slot)


def _fused_call(q, ke, kch, ksc, ve, vch, vsc, pos, *, scheme):
    """The kernel call behind :func:`fused_page_attention`. Returns ``(o,
    rows)``: ``rows`` the raw ``(B, KV, 1, 128)`` int32 flag rows, one per
    grid cell (lanes ``_COR``, ``_DUE``, ``_SLOW``)."""
    b, h, _, hd = q.shape
    s, kv = ke.shape[1], ke.shape[2]
    out, rows = _call(
        functools.partial(_kernel, s=s), q, ke, kch, ksc, ve, vch, vsc, pos,
        scheme=scheme, sblk=s, seq_block=lambda: 0, grid=(b, kv),
        semantics=("parallel", "parallel"))
    return out.reshape(b, h, 1, hd), rows


# ---------------------------------------------------------------------------
# page-chunked online-softmax variant: VMEM bounded by chunk, not context
# ---------------------------------------------------------------------------


def _chunked_kernel(*refs, scheme, chunk, nchunks, has_checks, packed):
    r = _unpack(refs, has_checks, packed)
    m_ref, l_ref, acc_ref = r["scratch"]
    flags_ref = r["flags"]
    c = pl.program_id(2)
    pos = r["pos"][pl.program_id(0)]
    base = c * chunk

    @pl.when(c == 0)
    def _init():
        # -1e30 is safe (not a sentinel hazard): chunk 0 always contains
        # token 0, which is valid for every pos >= 0, so m is finite after
        # the first update and exp(-1e30 - m) underflows masked scores to 0.
        m_ref[...] = jnp.full(m_ref.shape, -1e30, jnp.float32)
        l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
        acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
        flags_ref[...] = jnp.zeros(flags_ref.shape, jnp.int32)

    @pl.when(base <= pos)  # chunks wholly past the valid prefix contribute 0
    def _update():
        qb = r["q"][0, 0].astype(jnp.float32)          # (rep, hd)
        hd = qb.shape[-1]
        valid_row = base + jax.lax.broadcasted_iota(
            jnp.int32, (1, chunk), 1) <= pos
        kq, vq, cor, due, slow = _strips(r, base, pos, scheme)
        kf = kq.astype(jnp.float32) * r["ksc"][0]      # (chunk, hd)
        vf = vq.astype(jnp.float32) * r["vsc"][0]
        sc = jax.lax.dot_general(
            qb, kf, dimension_numbers=(((1,), (1,)), ((), ())))
        sc = sc * (1.0 / np.sqrt(hd))                  # (rep, chunk) f32
        sc = jnp.where(valid_row, sc, -1e30)
        m_prev = m_ref[:, :1]                          # (rep, 1)
        l_prev = l_ref[:, :1]
        m_cur = jnp.maximum(m_prev, jnp.max(sc, axis=-1, keepdims=True))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(sc - m_cur)                        # (rep, chunk)
        p = jnp.where(valid_row, p, 0.0)
        l_ref[...] = jnp.broadcast_to(
            alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True),
            l_ref.shape)
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, vf, dimension_numbers=(((1,), (0,)), ((), ())))
        flags_ref[0, 0] += _flag_row(cor, due, slow)

    @pl.when(c == nchunks - 1)
    def _finish():
        r["o"][0, 0] = (acc_ref[...] / l_ref[:, :1]).astype(r["o"].dtype)


@functools.partial(jax.jit, static_argnames=("scheme", "chunk_tokens",
                                             "per_slot"))
def chunked_page_attention(q, ke, kch, ksc, ve, vch, vsc, pos, *,
                           scheme: str = "in-place",
                           chunk_tokens: int = 256,
                           per_slot: bool = False):
    """Page-chunked online-softmax decode-at-use attention.

    Same operands and layout as :func:`fused_page_attention`, but the grid
    is (B, KV, n_chunks) with the chunk axis sequential: VMEM only ever
    holds one ``chunk_tokens``-sized slice of the strips plus the running
    (m, l, acc) online-softmax scratch, so context length is bounded by
    HBM, not VMEM. NOT bit-identical to the reference (see module
    docstring) — gate behind ``attention_impl="chunked"`` and validate
    against :func:`oracle_page_attention`. Flag counts ARE exact.

    ``chunk_tokens`` is clamped to S and, below S, to a multiple of 32
    tokens (a uint8 strip block must span whole 32-row TPU tiles); strips
    whose S is not a multiple of the chunk are zero-padded (padded tokens
    sit past every valid ``pos`` and are masked, and zero pages are
    codec-clean for every scheme).
    """
    if scheme not in KV_SCHEMES:
        raise ValueError(f"scheme {scheme!r}; one of {KV_SCHEMES}")
    if chunk_tokens < 1:
        raise ValueError(f"chunk_tokens must be positive, got {chunk_tokens}")
    out, rows = _chunked_call(q, ke, kch, ksc, ve, vch, vsc, pos,
                              scheme=scheme, chunk_tokens=chunk_tokens)
    return out, _reduce_flags(rows, per_slot)


def _chunked_call(q, ke, kch, ksc, ve, vch, vsc, pos, *, scheme,
                  chunk_tokens):
    """The kernel call behind :func:`chunked_page_attention`; returns
    ``(o, rows)`` as :func:`_fused_call` does."""
    b, h, _, hd = q.shape
    s, kv = ke.shape[1], ke.shape[2]
    rep = h // kv
    chunk = s if chunk_tokens >= s else min(s, max(32, chunk_tokens
                                                   - chunk_tokens % 32))
    pad = (-s) % chunk
    if pad:
        grow = lambda a: None if a is None else jnp.pad(
            a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        ke, kch, ve, vch = grow(ke), grow(kch), grow(ve), grow(vch)
        ksc, vsc = grow(ksc), grow(vsc)
    nc = (s + pad) // chunk
    out, rows = _call(
        functools.partial(_chunked_kernel, chunk=chunk, nchunks=nc),
        q, ke, kch, ksc, ve, vch, vsc, pos, scheme=scheme, sblk=chunk,
        seq_block=lambda c: c, grid=(b, kv, nc),
        scratch=[pltpu.VMEM((rep, 128), jnp.float32),   # running max m
                 pltpu.VMEM((rep, 128), jnp.float32),   # running normalizer l
                 pltpu.VMEM((rep, hd), jnp.float32)],   # running accumulator
        semantics=("parallel", "parallel", "arbitrary"))
    return out.reshape(b, h, 1, hd), rows


# ---------------------------------------------------------------------------
# fp64 oracle + VMEM accounting (the chunked kernel's acceptance gates)
# ---------------------------------------------------------------------------


def oracle_page_attention(q, ke, kch, ksc, ve, vch, vsc, pos, *,
                          scheme: str = "in-place",
                          backend: str = "xla") -> np.ndarray:
    """Float64 NumPy oracle over the SAME encoded strips -> (B, H, 1, hd).

    The codec decode is integer-exact (reuses ``kvcache._decode_kv``, so
    repaired/zeroed bytes match what either kernel sees bit for bit); the
    dequant, score, softmax, and PV reduction then all run in fp64 — the
    tolerance reference the chunked kernel is validated against, replacing
    the bit-identity contract it forfeits. Runs entirely on the host; no
    ``jax_enable_x64`` global flag needed.
    """
    from repro.serving import kvcache  # deferred: kvcache imports us
    kq = np.asarray(kvcache._decode_kv(ke, kch, scheme, backend)[0],
                    np.float64)
    vq = np.asarray(kvcache._decode_kv(ve, vch, scheme, backend)[0],
                    np.float64)
    kf = kq * np.asarray(ksc, np.float64)[..., None, None]  # (B, S, KV, hd)
    vf = vq * np.asarray(vsc, np.float64)[..., None, None]
    qf = np.asarray(jnp.asarray(q).astype(jnp.float32), np.float64)
    b, h, _, hd = qf.shape
    s, kv = kf.shape[1], kf.shape[2]
    rep = h // kv
    pos_np = np.asarray(pos)
    valid = np.arange(s)[None, :] <= pos_np[:, None]        # (B, S)
    out = np.zeros((b, h, 1, hd), np.float64)
    for bi in range(b):
        for g in range(kv):
            for r in range(rep):
                qv = qf[bi, g * rep + r, 0]                 # (hd,)
                sc = (kf[bi, :, g] @ qv) / math.sqrt(hd)    # (S,)
                sc = np.where(valid[bi], sc, -np.inf)
                p = np.exp(sc - sc.max())
                out[bi, g * rep + r, 0] = (p / p.sum()) @ vf[bi, :, g]
    return out


def strip_vmem_bytes(s: int, hd: int, rep: int,
                     scheme: str = "in-place") -> int:
    """Estimated VMEM working set of the strip kernel per (batch, kv-head)
    grid cell: encoded K+V strips, their int8 decodes, f32 dequants and
    compute-dtype copies, parity planes (parity-zero only), and the
    f32 score/softmax/cast-prob buffers. Linear in ``s`` — the structural
    wall the chunked kernel removes."""
    strips = 2 * s * hd * (1 + 1 + 4 + 2)   # enc + int8 + f32 + bf16, K and V
    checks = 2 * s * (hd // 8) if scheme == "parity-zero" else 0
    scores = rep * s * (4 + 4 + 2)          # f32 scores + softmax + cast
    return strips + checks + scores


def chunked_vmem_bytes(chunk_tokens: int, hd: int, rep: int,
                       scheme: str = "in-place") -> int:
    """Chunked-kernel VMEM working set per grid cell: one chunk's strip
    working set plus the f32 online-softmax scratch — independent of
    context length."""
    scratch = 4 * rep * (128 + 128 + hd)    # m, l, acc
    return strip_vmem_bytes(chunk_tokens, hd, rep, scheme) + scratch


def strip_vmem_crossover(hd: int, rep: int, scheme: str = "in-place",
                         budget: int = VMEM_BUDGET_BYTES) -> int:
    """Smallest context length whose strip-kernel working set exceeds the
    VMEM budget — past this, only the chunked kernel is honest on TPU."""
    per_token = strip_vmem_bytes(1, hd, rep, scheme)
    return budget // per_token + 1
