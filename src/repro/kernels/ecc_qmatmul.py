"""Pallas TPU kernel: FUSED in-place-ECC decode + matmul (beyond-paper).

The paper keeps decode in hardware. On TPU we instead keep weights
ECC-encoded *at rest in HBM* and decode each weight tile in VMEM on its way
to the MXU. Protection then costs zero HBM space AND zero extra HBM traffic;
the VPU bit-twiddling overlaps with MXU matmul work on neighbouring tiles.

Layout: W (K, N) int8 row-major -> 8-byte ECC blocks run along N, so any
(BK, BN) tile with BN % 8 == 0 contains whole blocks and decodes locally
with the lane-dense block codec (``ecc_decode.decode_lanes``).

Grid (ceil(N/BN), ceil(M/BM), ceil(K/BK)) — K innermost so each output
tile's accumulation visits are CONSECUTIVE (a TPU output block only
persists across back-to-back grid steps). A VMEM scratch holds the decoded
K-strip for the current N tile: the first M tile decodes each (BK, BN)
weight tile into its strip slot, every later M tile reuses it — each
weight tile is ECC-decoded ONCE per (N, K) tile instead of ``ceil(M/BM)``
times, so the VPU decode work no longer scales with batch. The decode
walks the tile in row chunks (a ``fori_loop``) so the unrolled vector code
stays bounded at any K. The N grid dim is marked ``parallel``; M and K
carry the scratch/accumulation dependences and stay ``arbitrary``. Edge
tiles are masked (activation columns past K zeroed, flag counts restricted
to real blocks) so production shapes need no divisibility beyond
N % 8 == 0.

The fill has a fast pass and a correcting one. Where the chunk's row count
``rc`` is a multiple of 32 (every serving shape), each uint8 chunk is
``pltpu.bitcast`` to int32 words, four rows of one lane per word, and the
fast pass computes every block's syndrome four bytes per op
(``ecc_decode.syndrome_words``), stores the packed sign restore
(``restore_words``) bitcast back to int8, and ORs the syndromes of real
blocks (rows < K, lanes < N: edge-tile padding never counts) into one
accumulator for the whole slot. It takes several chunks per loop step (up
to ``_FAST_ROWS`` rows), which gives the scheduler independent work to
overlap. Only if the accumulator is nonzero does the correcting pass
re-check each chunk and run the per-byte ``decode_lanes`` path, with its
flag counts, on each chunk that holds a faulty block, overwriting what the
fast pass stored there. A chunk whose syndromes are all zero decodes to
exactly its sign-restored bytes with no flags, so the strip and the flags
are bit-identical to a per-byte decode of every chunk. Lane ``_SLOW`` of
the counter row counts the chunks that took the correcting path
(``_qmatmul_call`` returns the raw rows); zero weight flags for a call
mean none did. Where ``rc`` is not a multiple of 32 (only at small K),
every chunk takes the per-byte path and ``_SLOW`` stays 0.

Tiles are clamped to what the TPU compiler accepts (the last two block
dims divisible by (8, 128), or equal to the array dims): BN a multiple of
128 or all of N, BK a multiple of 128 or all of K, BM a multiple of 32 or
all of M. Default 128-wide N strips with full-K tiles (bk=0). The decoded
strip is K*BN bytes (int8 paths) or K*BN*itemsize (float path, which
stores the dequantized strip) REGARDLESS of ``bk`` — decode-once needs the
whole K strip resident — so for huge-K layers shrink ``bn`` to bound VMEM.

Three activation paths share the kernel:

* int8 ``a`` -> int32 accumulator (the raw quantized MXU path);
* int8 ``a`` + ``a_scale`` -> the fused REQUANTIZE EPILOGUE: the int32
  accumulator is scaled by ``a_scale * w_scale`` (optionally after an int32
  bias add) and cast to ``out_dtype`` (bf16 default) in VMEM — int8 MXU
  throughput plus halved output traffic, a drop-in replacement for the
  float path in quantized serving;
* float ``a`` (bf16/f32, requires ``w_scale``) -> the decoded tile is
  dequantized in VMEM (``(q * w_scale).astype(a.dtype)``) and the matmul
  accumulates f32 — the value path is identical to decode-then-matmul, so
  fused serving stays numerically identical to the per-step baseline.

``with_flags=True`` additionally returns ``(corrected, due)`` int32 counts
over all weight blocks. Counting happens inside the same predicated block
as the decode itself (first M tile only), so the flag totals double as a
runtime witness that each weight tile decodes exactly once per (N, K) tile.

``with_abft=True`` adds algorithm-based fault tolerance over the COMPUTE
itself (FT-CNN-style checksums): for every (BM, BN) partial product the
kernel verifies the accumulator's row sums against ``a @ rowsum(w)`` and
its column sums against ``colsum(a) @ w`` — the classic ABFT pair, done
per K-tile so multi-``kk`` grids verify each partial dot. On the int8 and
requantize paths both sides live in int32 modular arithmetic, so the
comparison is BIT-EXACT (zero false positives by construction); the float
path is tolerance-gated (``ABFT_RTOL`` against an |a|·|w| checksum scale,
so reordering noise never fires but exponent-scale SDCs do). Mismatch
counts come back per output row (per-slot attributable: decode M = batch)
plus a column-check total. ``clamp=<absmax>`` fuses Geissler-style
activation-range supervision into the same epilogue: the f32 result is
clipped to ``[-clamp, +clamp]`` and out-of-range hits are counted per row
alongside the ABFT mismatches. Both knobs default off and the disabled
kernel is bit-identical to the unguarded one. ``fault_bits`` XORs a bit
pattern into accumulator element (0, 0) of the first tile — a
deterministic in-kernel SDC for tests and campaign calibration.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ecc_decode, platform

# float-path ABFT tolerance: checksum reordering noise is ~K * eps(f32)
# relative to the |a|·|w| scale (~1e-5 at K=128); 1e-4 leaves a decade of
# margin while still firing on any exponent-scale corruption.
ABFT_ATOL = 1e-6
ABFT_RTOL = 1e-4

LANES = 128
# count lanes of the per-N-strip counter row: corrected and detected
# blocks, column-checksum mismatches, chunks that took the correcting path
_SINGLE, _DOUBLE, _COLS, _SLOW = 0, 1, 2, 3
# most weight-tile rows the fast decode pass takes per loop step: chunks
# unrolled into one step let the scheduler overlap their dependence chains
_FAST_ROWS = 2560


def _legal(t: int, full: int, align: int) -> int:
    """Clamp a requested tile to one the TPU compiler accepts: the whole
    dim, or a multiple of ``align`` below it."""
    if t <= 0 or t >= full:
        return full
    return min(full, max(align, t - t % align))


def _row_chunk(bk: int) -> int:
    """Rows decoded per loop step: the largest uint8-tile-aligned chunk
    dividing ``bk`` (bounded unrolled vector code at any K)."""
    for rc in (512, 256, 128, 64, 32):
        if bk % rc == 0:
            return rc
    return bk


def _digits(x, axis):
    """Base-128 int8 digits of int32 ``x`` (|x| < 2**28), all of whose
    slices along ``axis`` are equal: digit d lands at index d of ``axis``
    (least significant first), zeros past index 3."""
    idx = jax.lax.broadcasted_iota(jnp.int32, x.shape, axis)
    out = jnp.zeros_like(x)
    for d in range(4):
        dig = (x >> (7 * d)) if d == 3 else (x >> (7 * d)) & 127
        out = jnp.where(idx == d, dig, out)
    return out.astype(jnp.int8)


def _exact_checksums(a, w):
    """The ABFT pair for int8 ``a (BM, BK)`` and ``w (BK, BN)`` in int32
    modular arithmetic: ``a @ rowsum(w)`` (BM, 1) and ``colsum(a) @ w``
    (1, BN). The sums outgrow int8, so each is split into base-128 int8
    digits and every product runs as an int8 MXU matmul (the MXU has no
    int32 operands); the digit dots recombine exactly."""
    i32 = jnp.int32
    dn = (((1,), (0,)), ((), ()))
    bm, bk = a.shape
    bn = w.shape[1]
    rowsum = jax.lax.dot_general(w, jnp.ones((bn, LANES), jnp.int8), dn,
                                 preferred_element_type=i32)   # (BK, 128)
    parts = jax.lax.dot_general(a, _digits(rowsum, 1), dn,
                                preferred_element_type=i32)    # (BM, 128)
    lane = jax.lax.broadcasted_iota(i32, (1, LANES), 1)
    rs_ref = jnp.sum(parts * jnp.where(lane < 4, 1 << (7 * lane), 0),
                     axis=1, keepdims=True)
    colsum = jax.lax.dot_general(jnp.ones((8, bm), jnp.int8), a, dn,
                                 preferred_element_type=i32)   # (8, BK)
    parts = jax.lax.dot_general(_digits(colsum, 0), w, dn,
                                preferred_element_type=i32)    # (8, BN)
    row = jax.lax.broadcasted_iota(i32, (8, 1), 0)
    cs_ref = jnp.sum(parts * jnp.where(row < 4, 1 << (7 * row), 0),
                     axis=0, keepdims=True)
    return rs_ref, cs_ref


def _kernel(*refs, dims, path, has_bias, has_clamp, with_abft, fault_bits,
            rc):
    m, n, k = dims
    track = with_abft or has_clamp
    it = iter(refs)
    a_ref, w_ref, table_ref, scale_ref = next(it), next(it), next(it), next(it)
    ascale_ref = next(it) if path == "requant" else None
    bias_ref = next(it) if has_bias else None
    clamp_ref = next(it) if has_clamp else None
    out_ref, counts_ref = next(it), next(it)
    abft_rows_ref = next(it) if track else None
    wdec_ref = next(it)
    j, i, kk = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    bm, bk = a_ref.shape
    bn = w_ref.shape[1]
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)

    def bump(slot, count):
        """Add a (1, 1) count into one lane of this N strip's counter row."""
        counts_ref[0] += jnp.where(lane == slot, count, 0)

    @pl.when(jnp.logical_and(i == 0, kk == 0))
    def _init_counts():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    if track:
        # per-(j, i) row counters accumulate over kk (j outermost -> the
        # revisit pattern is consecutive, TPU-legal accumulation).
        @pl.when(kk == 0)
        def _init_abft_rows():
            abft_rows_ref[...] = jnp.zeros_like(abft_rows_ref)

    col = j * bn + jax.lax.broadcasted_iota(jnp.int32, (1, bn), 1)
    colv = col < n

    # decode ONCE per (N, K) tile — the first M tile fills this K-strip slot
    # of the VMEM scratch, every later M tile reuses it. Flag counting lives
    # inside the same predicate (each real block counted exactly once, on
    # the last lane of the block).
    @pl.when(i == 0)
    def _decode():
        table = table_ref[...]
        block_lane = jnp.logical_and(colv, (col & 7) == 7)

        def rows(r0):
            return kk * bk + r0 + jax.lax.broadcasted_iota(jnp.int32,
                                                           (rc, 1), 0)

        def store(r0, q):
            """Write a chunk's int8 values (any int dtype) to its slot."""
            if path == "float":   # the strip holds the dequantized weights
                w = (q.astype(jnp.float32) * scale_ref[0, 0]
                     ).astype(wdec_ref.dtype)
            else:
                w = q.astype(jnp.int8)
            wdec_ref[pl.ds(pl.multiple_of(kk * bk + r0, rc), rc), :] = w

        def correct(r, carry):
            """Per-byte decode of chunk ``r`` with correction; flags
            counted on real blocks."""
            r0 = pl.multiple_of(r * rc, rc)
            x = w_ref[pl.ds(r0, rc), :].astype(jnp.int32)
            dec, single, double = ecc_decode.decode_lanes(x, table)
            store(r0, ecc_decode.signed(dec))
            valid = jnp.logical_and(rows(r0) < k, block_lane)
            s_cnt, d_cnt, n_slow = carry
            s_cnt += jnp.sum(jnp.logical_and(single, valid).astype(
                jnp.int32), axis=0, keepdims=True)
            d_cnt += jnp.sum(jnp.logical_and(double, valid).astype(
                jnp.int32), axis=0, keepdims=True)
            return s_cnt, d_cnt, n_slow

        def packed(r0):
            """Chunk words (four rows of one lane per int32) and their
            block syndromes, padding rows past K zeroed."""
            words = pltpu.bitcast(w_ref[pl.ds(r0, rc), :], jnp.int32)
            syn = ecc_decode.syndrome_words(words, table)
            if k % bk:   # a row mask packed the way the words are
                real = jnp.broadcast_to(jnp.where(rows(r0) < k, -1, 0),
                                        (rc, bn))
                syn = syn & pltpu.bitcast(real.astype(jnp.int8), jnp.int32)
            return words, syn

        def flagged(syn):
            """Does a real block (last lane, inside N) have a syndrome?
            Syndrome bytes are below 128, so the words are >= 0."""
            return jnp.max(jnp.where(block_lane, syn, 0)) > 0

        def restore(r, acc):
            """Fast pass: store the sign-restored chunk, OR its syndromes
            into ``acc``."""
            r0 = pl.multiple_of(r * rc, rc)
            words, syn = packed(r0)
            store(r0, pltpu.bitcast(ecc_decode.restore_words(words),
                                    jnp.int8))
            return acc | syn

        def recheck(r, carry):
            """Redo chunk ``r`` per byte if a real block of it is faulty."""
            def redo(carry):
                s_cnt, d_cnt, n_slow = correct(r, carry)
                return s_cnt, d_cnt, n_slow + 1

            _, syn = packed(pl.multiple_of(r * rc, rc))
            return jax.lax.cond(flagged(syn), redo, lambda c: c, carry)

        zero = jnp.zeros((1, bn), jnp.int32)
        counts = (zero, zero, jnp.int32(0))
        n_ch = bk // rc
        if rc % 32:   # not whole words: per byte throughout
            counts = jax.lax.fori_loop(0, n_ch, correct, counts)
        else:
            per_step = max(u for u in range(1, n_ch + 1)
                           if n_ch % u == 0 and u * rc <= _FAST_ROWS)

            def restore_step(t, acc):
                for u in range(per_step):
                    acc = restore(t * per_step + u, acc)
                return acc

            acc = jax.lax.fori_loop(0, n_ch // per_step, restore_step,
                                    jnp.zeros((rc // 4, bn), jnp.int32))
            counts = jax.lax.cond(
                flagged(acc),
                lambda c: jax.lax.fori_loop(0, n_ch, recheck, c),
                lambda c: c, counts)
        s_cnt, d_cnt, n_slow = counts
        bump(_SINGLE, jnp.sum(s_cnt, axis=1, keepdims=True))
        bump(_DOUBLE, jnp.sum(d_cnt, axis=1, keepdims=True))
        bump(_SLOW, n_slow)

    a = a_ref[...]  # (BM, BK)
    if k % bk:  # mask activation columns past K so edge tiles contribute 0
        kcol = kk * bk + jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 1)
        a = jnp.where(kcol < k, a, jnp.zeros_like(a))
    if with_abft and m % bm:
        # also zero activation rows past M: decoded weights are always
        # finite so garbage columns cancel in the checksum identities, but
        # float-path activation padding could be NaN and would poison the
        # column check. Valid output rows are unaffected.
        mrow = i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, bk), 0)
        a = jnp.where(mrow < m, a, jnp.zeros_like(a))
    w = wdec_ref[pl.ds(pl.multiple_of(kk * bk, bk), bk), :]
    dn = (((1,), (0,)), ((), ()))
    rowv = (i * bm + jax.lax.broadcasted_iota(jnp.int32, (bm, 1), 0)) < m

    def _flip(partial):
        """XOR fault_bits into element (0, 0) of the first tile's partial
        product — a deterministic injected SDC for tests/calibration."""
        hit = jnp.logical_and(
            jax.lax.broadcasted_iota(jnp.int32, partial.shape, 0) == 0,
            jax.lax.broadcasted_iota(jnp.int32, partial.shape, 1) == 0)
        hit = jnp.logical_and(
            hit, jnp.logical_and(j == 0, jnp.logical_and(i == 0, kk == 0)))
        if partial.dtype == jnp.int32:
            return jnp.where(hit, partial ^ jnp.int32(fault_bits), partial)
        bits = jax.lax.bitcast_convert_type(partial, jnp.int32)
        flipped = jax.lax.bitcast_convert_type(
            bits ^ jnp.int32(fault_bits), partial.dtype)
        return jnp.where(hit, flipped, partial)

    def _abft(partial, a_chk, w_chk, exact):
        """Verify this K-tile's partial product against the ABFT pair:
        row sums vs a @ rowsum(w), column sums vs colsum(a) @ w."""
        dt = partial.dtype
        rs_acc = jnp.sum(partial, axis=1, keepdims=True)              # (BM,1)
        cs_acc = jnp.sum(partial, axis=0, keepdims=True)              # (1,BN)
        if exact:
            rs_ref, cs_ref = _exact_checksums(a_chk, w_chk)
            row_bad = rs_acc != rs_ref
            col_bad = cs_acc != cs_ref
        else:
            rs_ref = jax.lax.dot_general(
                a_chk, jnp.sum(w_chk, axis=1, keepdims=True), dn,
                preferred_element_type=dt)
            cs_ref = jax.lax.dot_general(
                jnp.sum(a_chk, axis=0, keepdims=True), w_chk, dn,
                preferred_element_type=dt)
            a_abs, w_abs = jnp.abs(a_chk), jnp.abs(w_chk)
            rs_sc = jax.lax.dot_general(
                a_abs, jnp.sum(w_abs, axis=1, keepdims=True), dn,
                preferred_element_type=dt)
            cs_sc = jax.lax.dot_general(
                jnp.sum(a_abs, axis=0, keepdims=True), w_abs, dn,
                preferred_element_type=dt)
            row_bad = jnp.abs(rs_acc - rs_ref) > ABFT_ATOL + ABFT_RTOL * rs_sc
            col_bad = jnp.abs(cs_acc - cs_ref) > ABFT_ATOL + ABFT_RTOL * cs_sc
        abft_rows_ref[0, :, 0:1] += jnp.logical_and(
            row_bad, rowv).astype(jnp.int32)
        bump(_COLS, jnp.sum(jnp.logical_and(col_bad, colv).astype(jnp.int32),
                            axis=1, keepdims=True))

    def _clamp(res):
        """Geissler-style range supervision: clip the f32 epilogue output
        to ±clamp and count (valid-masked) out-of-range hits per row."""
        c = clamp_ref[0, 0]
        hit = jnp.abs(res) > c
        hit = jnp.logical_and(hit, jnp.logical_and(rowv, colv))
        abft_rows_ref[0, :, 1:2] += jnp.sum(
            hit.astype(jnp.int32), axis=1, keepdims=True)
        return jnp.clip(res, -c, c)

    if path == "float":
        partial = jax.lax.dot_general(
            a, w, dimension_numbers=dn, preferred_element_type=jnp.float32)
        if fault_bits:
            partial = _flip(partial)
        if with_abft:
            _abft(partial, a.astype(jnp.float32), w.astype(jnp.float32),
                  exact=False)

        @pl.when(kk == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        out_ref[...] += partial
        if has_clamp:
            @pl.when(kk == pl.num_programs(2) - 1)
            def _clamp_final():
                out_ref[...] = _clamp(out_ref[...])
    elif path == "int8":
        partial = jax.lax.dot_general(
            a, w, dimension_numbers=dn, preferred_element_type=jnp.int32)
        if fault_bits:
            partial = _flip(partial)
        if with_abft:
            _abft(partial, a, w, exact=True)

        @pl.when(kk == 0)
        def _init():
            out_ref[...] = jnp.zeros_like(out_ref)

        out_ref[...] += partial
    else:  # requant epilogue: full-K tile (single kk), exact int32 acc
        acc = jax.lax.dot_general(
            a, w, dimension_numbers=dn, preferred_element_type=jnp.int32)
        if fault_bits:
            acc = _flip(acc)
        if with_abft:
            _abft(acc, a, w, exact=True)
        if has_bias:
            acc = acc + bias_ref[...]  # (1, BN) int32, accumulator scale
        s = ascale_ref[...] * scale_ref[0, 0]  # (BM, 1) f32
        res = acc.astype(jnp.float32) * s
        if has_clamp:
            res = _clamp(res)
        out_ref[...] = res.astype(out_ref.dtype)


def _smem():
    return pl.BlockSpec(memory_space=pltpu.SMEM)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk",
                                             "with_flags", "out_dtype",
                                             "with_abft", "fault_bits"))
def ecc_qmatmul(a: jnp.ndarray, w_enc: jnp.ndarray, w_scale=None, *,
                a_scale=None, bias=None, out_dtype=None,
                bm: int = 128, bn: int = 128, bk: int = 0,
                with_flags: bool = False,
                with_abft: bool = False, clamp=None, fault_bits: int = 0):
    """``a (M,K) @ decode(w_enc (K,N) uint8)``, decode fused into the matmul.

    int8 ``a``   -> (M, N) int32 accumulator (``w_scale`` ignored).
    int8 ``a`` + ``a_scale`` (per-row ``(M,)``/``(M,1)`` or scalar, requires
                    ``w_scale``) -> the fused requantize epilogue:
                    ``(acc [+ bias]) * (a_scale * w_scale)`` cast to
                    ``out_dtype`` (default bf16) in VMEM. ``bias`` is an
                    optional (N,) int32 at the accumulator scale. The tile is
                    full-K (``bk`` ignored) so the int32 accumulation is one
                    exact MXU pass — bit-identical to quantize->decode->
                    matmul done in XLA.
    float ``a``  -> (M, N) f32 = ``a @ (decode(w_enc) * w_scale)`` — requires
                    ``w_scale``; pass ``bk=0`` (default: full K per tile) to
                    keep the accumulation order identical to one XLA dot.
    with_flags   -> also return ``flags (2,) int32``: (#single-corrected,
                    #double-detected) over all weight blocks.
    with_abft    -> verify ABFT checksums in-kernel (bit-exact on the int8/
                    requant paths, tolerance-gated on float). Adds a final
                    return value ``(rows, col_mm)``: ``rows (M, 2) int32``
                    is per-output-row (row-checksum mismatches, clamp hits)
                    and ``col_mm`` the scalar column-checksum mismatch
                    count.
    clamp        -> f32 absmax bound: the requantize/float epilogue output
                    is clipped to ``[-clamp, +clamp]`` with hits counted in
                    the ABFT rows channel (returned even when ``with_abft``
                    is False; the mismatch column is then all zero). Not
                    supported on the raw int8-accumulator path.
    fault_bits   -> nonzero XORs the pattern into accumulator element
                    (0, 0) of the first tile (deterministic injected SDC).

    Tiles need not divide (M, N, K) — edge tiles are masked — and are
    clamped to TPU-legal sizes (module docstring). N % 8 == 0 is
    structural (ECC blocks run along N). The first M tile decodes each
    weight tile into a K-strip VMEM scratch that later M tiles reuse, so
    per-call decode work is ceil(N/BN) * ceil(K/BK) tiles — independent of
    M.
    """
    out, counts, rows = _qmatmul_call(
        a, w_enc, w_scale, a_scale=a_scale, bias=bias, out_dtype=out_dtype,
        bm=bm, bn=bn, bk=bk, with_abft=with_abft, clamp=clamp,
        fault_bits=fault_bits)
    counts = counts.sum(axis=(0, 1))
    outs = (out,)
    if with_flags:
        outs += (counts[_SINGLE:_DOUBLE + 1],)
    if rows is not None:
        # per-row (mismatch, clamp-hit) counts summed over N strips, plus
        # the column-check mismatch total (not row-attributable).
        outs += ((rows.sum(axis=0), counts[_COLS]),)
    return outs if len(outs) > 1 else out


def _qmatmul_call(a, w_enc, w_scale=None, *, a_scale=None, bias=None,
                  out_dtype=None, bm=128, bn=128, bk=0, with_abft=False,
                  clamp=None, fault_bits=0):
    """The kernel call behind :func:`ecc_qmatmul`, same arguments. Returns
    ``(out, counts, rows)``: ``counts`` the raw ``(ceil(N/BN), 1, 128)``
    int32 counter rows, one per N strip (lanes ``_SINGLE``, ``_DOUBLE``,
    ``_COLS``, ``_SLOW``), and ``rows`` the per-strip ``(ceil(N/BN), M, 2)``
    ABFT/clamp row counts, or None when neither guard is on."""
    m, k = a.shape
    k2, n = w_enc.shape
    assert k == k2 and n % 8 == 0, (a.shape, w_enc.shape)
    float_path = jnp.issubdtype(a.dtype, jnp.floating)
    if float_path and w_scale is None:
        raise ValueError("float activations need w_scale for the in-VMEM "
                         "dequantization")
    if float_path and a_scale is not None:
        raise ValueError("a_scale is the int8 requantize epilogue; float "
                         "activations carry their own scale")
    requant = (not float_path) and a_scale is not None
    if requant and w_scale is None:
        raise ValueError("the requantize epilogue needs w_scale")
    if bias is not None and not requant:
        raise ValueError("bias is only fused by the requantize epilogue")
    path = "float" if float_path else ("requant" if requant else "int8")
    has_clamp = clamp is not None
    if has_clamp and path == "int8":
        raise ValueError("clamp guards the f32 epilogue output; the raw "
                         "int8-accumulator path has none")
    track = with_abft or has_clamp
    if requant:
        bk = 0  # full-K tile: one dot per output tile, XLA-identical order
    bm, bn, bk = _legal(bm, m, 32), _legal(bn, n, LANES), _legal(bk, k, LANES)
    grid = (pl.cdiv(n, bn), pl.cdiv(m, bm), pl.cdiv(k, bk))
    scale = jnp.asarray(w_scale if w_scale is not None else 1.0,
                        jnp.float32).reshape(1, 1)
    if path == "float":
        out_dt = jnp.float32
    elif path == "int8":
        out_dt = jnp.int32
    else:
        out_dt = jnp.dtype(out_dtype) if out_dtype is not None else jnp.bfloat16
    kern = functools.partial(_kernel, dims=(m, n, k), path=path,
                             has_bias=bias is not None, has_clamp=has_clamp,
                             with_abft=with_abft, fault_bits=int(fault_bits),
                             rc=_row_chunk(bk))

    inputs = [a, w_enc, jnp.asarray(ecc_decode.code_table(bn)), scale]
    in_specs = [
        pl.BlockSpec((bm, bk), lambda j, i, kk: (i, kk)),
        pl.BlockSpec((bk, bn), lambda j, i, kk: (kk, j)),
        pl.BlockSpec((8, bn), lambda j, i, kk: (0, 0)),
        _smem(),
    ]
    if requant:
        ascale = jnp.broadcast_to(
            jnp.asarray(a_scale, jnp.float32).reshape(-1, 1)
            if jnp.ndim(a_scale) else
            jnp.asarray(a_scale, jnp.float32).reshape(1, 1), (m, 1))
        inputs.append(ascale)
        in_specs.append(pl.BlockSpec((bm, 1), lambda j, i, kk: (i, 0)))
        if bias is not None:
            inputs.append(jnp.asarray(bias, jnp.int32).reshape(1, n))
            in_specs.append(pl.BlockSpec((1, bn), lambda j, i, kk: (0, j)))
    if has_clamp:
        inputs.append(jnp.asarray(clamp, jnp.float32).reshape(1, 1))
        in_specs.append(_smem())

    out_specs = [
        pl.BlockSpec((bm, bn), lambda j, i, kk: (i, j)),
        pl.BlockSpec((1, 1, LANES), lambda j, i, kk: (j, 0, 0)),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((m, n), out_dt),
        jax.ShapeDtypeStruct((grid[0], 1, LANES), jnp.int32),
    ]
    if track:
        out_specs.append(pl.BlockSpec((1, bm, 2), lambda j, i, kk: (j, i, 0)))
        out_shape.append(jax.ShapeDtypeStruct((grid[0], m, 2), jnp.int32))
    strip_dt = a.dtype if path == "float" else jnp.int8

    res = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((grid[2] * bk, bn), strip_dt)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")),
        interpret=platform.interpret(),
    )(*inputs)
    return res[0], res[1], (res[2] if track else None)
