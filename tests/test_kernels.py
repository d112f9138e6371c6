"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, bit-exactness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from jax.experimental.pallas import tpu as pltpu

from repro.core import ecc, faults
from repro.kernels import ecc_decode as codec
from repro.kernels import ecc_qmatmul as qmm
from repro.kernels import ops, ref
from repro.kernels.ecc_decode import ecc_decode
from repro.kernels.ecc_qmatmul import ecc_qmatmul
from repro.kernels.throttle import throttle


def _wot_weights(rng, shape):
    w = rng.integers(-64, 64, size=shape).astype(np.int8)
    flat = w.reshape(-1)
    flat[7::8] = rng.integers(-128, 128, size=flat[7::8].size)
    return flat.reshape(shape)


@pytest.mark.parametrize("nblk,blk_n", [(64, 64), (1024, 256), (4096, 4096),
                                        (8192, 2048)])
def test_ecc_decode_sweep(nblk, blk_n):
    rng = np.random.default_rng(nblk)
    w = _wot_weights(rng, (nblk, 8))
    enc = np.asarray(ecc.encode64(jnp.asarray(w.view(np.uint8))))
    fenc = jnp.asarray(faults.inject(enc, 1e-4, seed=nblk))
    d_k, f_k = ecc_decode(fenc, blk_n=blk_n)
    d_r, f_r = ref.ecc_decode_ref(fenc)
    assert (np.asarray(d_k) == np.asarray(d_r)).all()
    assert (np.asarray(f_k) == np.asarray(f_r)).all()


def test_ecc_decode_corrects_all_singles():
    rng = np.random.default_rng(0)
    w = _wot_weights(rng, (64, 8))
    enc = np.asarray(ecc.encode64(jnp.asarray(w.view(np.uint8))))
    f = enc.copy()
    for i in range(64):  # one flip per block, all 64 positions covered
        f[i, i // 8] ^= np.uint8(1 << (i % 8))
    d_k, flags = ecc_decode(jnp.asarray(f), blk_n=64)
    assert (np.asarray(d_k).view(np.int8) == w).all()
    assert (np.asarray(flags) == 1).all()


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 128, 128, 128, 128, 128),
    (128, 256, 512, 64, 128, 128),
    (256, 512, 256, 128, 64, 256),
    (64, 64, 64, 64, 64, 64),
])
def test_ecc_qmatmul_sweep(m, k, n, bm, bn, bk):
    rng = np.random.default_rng(m + k + n)
    a = rng.integers(-127, 128, size=(m, k)).astype(np.int8)
    wq = _wot_weights(rng, (k, n))
    wenc = np.asarray(ecc.encode64(
        jnp.asarray(wq.view(np.uint8).reshape(k, n // 8, 8)))).reshape(k, n)
    out_k = ecc_qmatmul(jnp.asarray(a), jnp.asarray(wenc), bm=bm, bn=bn, bk=bk)
    out_r = ref.ecc_qmatmul_ref(jnp.asarray(a), jnp.asarray(wenc))
    plain = a.astype(np.int32) @ wq.astype(np.int32)
    assert (np.asarray(out_k) == np.asarray(out_r)).all()
    assert (np.asarray(out_k) == plain).all()  # bit-exact vs unprotected


def test_ecc_qmatmul_corrects_faults():
    """Faulty encoded weights in HBM -> fused kernel returns the exact
    unfaulted matmul (single-bit faults fully corrected in VMEM)."""
    rng = np.random.default_rng(5)
    m, k, n = 64, 128, 256
    a = rng.integers(-127, 128, size=(m, k)).astype(np.int8)
    wq = _wot_weights(rng, (k, n))
    wenc = np.asarray(ecc.encode64(
        jnp.asarray(wq.view(np.uint8).reshape(k, n // 8, 8)))).reshape(k, n)
    # inject exactly one flip in a handful of distinct blocks
    f = wenc.reshape(-1).copy()
    for blk in [0, 77, 1000, 4095]:
        f[blk * 8 + 3] ^= 0x04
    f = f.reshape(k, n)
    out = ecc_qmatmul(jnp.asarray(a), jnp.asarray(f), bm=64, bn=128, bk=128)
    plain = a.astype(np.int32) @ wq.astype(np.int32)
    assert (np.asarray(out) == plain).all()


def _encode(wq):
    k, n = wq.shape
    return np.asarray(ecc.encode64(jnp.asarray(
        wq.view(np.uint8).reshape(k, n // 8, 8)))).reshape(k, n)


def _flip(enc, flips):
    f = enc.copy()
    for r, c, bits in flips:
        f[r, c] ^= np.uint8(bits)
    return f


@pytest.mark.parametrize("faulty", [False, True], ids=["clean", "faulty"])
def test_packed_word_codec_matches_decode_lanes(faulty):
    """syndrome_words/restore_words on a tile bitcast to int32 words give
    decode_lanes' block syndromes and (on clean blocks) its decoded
    bytes."""
    rng = np.random.default_rng(11)
    r, w = 64, 256
    enc = _encode(_wot_weights(rng, (r, w)))
    if faulty:
        enc = _flip(enc, [(0, 0, 0x01), (9, 77, 0x30), (33, 200, 0x80),
                          (63, 255, 0x40)])
    table = jnp.asarray(codec.code_table(w))

    @jax.jit
    def run(enc):
        x = enc.astype(jnp.int32)
        dec, single, double = codec.decode_lanes(x, table)
        words = pltpu.bitcast(enc, jnp.int32)
        assert words.shape == (r // 4, w)
        syn = pltpu.bitcast(codec.syndrome_words(words, table), jnp.uint8)
        restored = pltpu.bitcast(codec.restore_words(words), jnp.uint8)
        return (dec, single | double, codec._syndrome(x, table), syn,
                restored)

    dec, flagged, syn_bytes, syn, restored = map(np.asarray,
                                                 run(jnp.asarray(enc)))
    # each block's syndrome sits on its last lane
    assert np.array_equal(syn[:, 7::8], syn_bytes[:, 7::8])
    assert np.array_equal(syn[:, 7::8] != 0, flagged[:, 7::8])
    clean = ~flagged
    assert clean.sum() > 0 and faulty == (not clean.all())
    assert np.array_equal(restored[clean], dec[clean])


# (k, n, bk, flips): bn is 128 throughout, m 16
_QMM_CASES = {
    "clean": (256, 256, 128, []),
    "single": (256, 256, 128, [(5, 9, 0x04)]),
    "double": (256, 256, 128, [(130, 40, 0x06)]),
    "two_strips": (256, 256, 128, [(5, 9, 0x10), (200, 250, 0x80)]),
    "fallback": (100, 256, 0, [(5, 9, 0x04), (99, 255, 0x03)]),
    "kedge_clean": (320, 200, 128, []),
    "kedge_single": (320, 200, 128, [(319, 199, 0x01)]),
}
_PAD = 0x5A   # a byte whose 8-byte block has a nonzero syndrome


@pytest.fixture
def garbage_padding(monkeypatch):
    """Interpret-mode edge tiles read this byte past the weight array (it
    is uint8 zero otherwise, whose syndrome is zero), as a TPU reads
    whatever lies there."""
    from jax._src.pallas import primitives
    orig = primitives.uninitialized_value

    def fill(shape, dtype):
        if dtype == jnp.uint8:
            return jnp.full(shape, _PAD, dtype)
        return orig(shape, dtype)
    monkeypatch.setattr(primitives, "uninitialized_value", fill)
    jax.clear_caches()
    yield
    jax.clear_caches()


@pytest.mark.parametrize("case", sorted(_QMM_CASES))
@pytest.mark.parametrize("path", ["float", "int8", "requant", "abft"])
def test_packed_decode_bit_identical(garbage_padding, path, case):
    """Every path of the fused matmul, clean and faulty weights, packed and
    per-byte chunks: output and flags equal the decode-then-matmul
    reference, and the ``_SLOW`` lane counts exactly the chunks holding a
    real faulty block (none on a row chunk that is not whole words)."""
    _, s_pad, d_pad = ecc.decode64(jnp.full((1, 8), _PAD, jnp.uint8))
    assert bool(s_pad[0] | d_pad[0])
    k, n, bk, flips = _QMM_CASES[case]
    m, bm, bn = 16, 16, 128
    rng = np.random.default_rng(k + n + len(flips))
    enc = _flip(_encode(_wot_weights(rng, (k, n))), flips)
    a = rng.integers(-8, 9, size=(m, k)).astype(np.int8)
    acc = np.asarray(ref.ecc_qmatmul_ref(jnp.asarray(a), jnp.asarray(enc)))
    _, single, double = ecc.decode64(jnp.asarray(enc).reshape(k, n // 8, 8))
    want_flags = [int(single.sum()), int(double.sum())]
    scale = jnp.float32(2.0 ** -6)   # exact in bf16: any sum order agrees
    kw = dict(bm=bm, bn=bn, bk=bk)
    if path == "float":
        args = (jnp.asarray(a).astype(jnp.bfloat16), enc, scale)
        want = acc.astype(np.float32) * np.float32(2.0 ** -6)
    elif path == "requant":
        kw["a_scale"] = jnp.asarray(rng.uniform(0.5, 2, (m, 1)), jnp.float32)
        args = (jnp.asarray(a), enc, scale)
        want = np.asarray((jnp.asarray(acc).astype(jnp.float32)
                           * (kw["a_scale"] * scale)).astype(jnp.bfloat16))
    else:
        args, kw["with_abft"] = (jnp.asarray(a), enc), path == "abft"
        want = acc
    out, counts, rows = qmm._qmatmul_call(*args, **kw)
    counts = np.asarray(counts).sum(axis=(0, 1))
    assert np.array_equal(np.asarray(out), want)
    assert counts[qmm._SINGLE:qmm._DOUBLE + 1].tolist() == want_flags
    if path == "abft":
        assert not np.asarray(rows).any() and counts[qmm._COLS] == 0
    # the public wrapper returns the same numbers
    out2, flags2 = ecc_qmatmul(*args, **kw, with_flags=True)[:2]
    assert np.array_equal(np.asarray(out2), np.asarray(out))
    assert np.asarray(flags2).tolist() == want_flags
    rc = qmm._row_chunk(qmm._legal(0 if path == "requant" else bk, k, 128))
    dirty = {(c // bn, r // rc) for r, c, _ in flips}
    assert (rc % 32 != 0) == (case == "fallback")
    assert counts[qmm._SLOW] == (0 if rc % 32 else len(dirty))


@pytest.mark.parametrize("nblk", [64, 1000, 4096])
def test_throttle_sweep(nblk):
    rng = np.random.default_rng(nblk)
    q = jnp.asarray(rng.integers(-128, 128, size=(nblk, 8)).astype(np.int8))
    blk = min(nblk, 512)
    if nblk % blk:
        blk = nblk
    t_k = throttle(q, blk_n=blk)
    assert (np.asarray(t_k) == np.asarray(ref.throttle_ref(q))).all()


def test_ops_wrappers():
    rng = np.random.default_rng(9)
    w = _wot_weights(rng, (2048,))
    enc = np.asarray(ecc.encode64(jnp.asarray(w.view(np.uint8).reshape(-1, 8))))
    dec, flags = ops.decode_weights(jnp.asarray(enc.reshape(-1)))
    assert (np.asarray(dec) == w).all()
    q = jnp.asarray(rng.integers(-128, 128, size=(4096,)).astype(np.int8))
    t = ops.throttle_flat(q)
    from repro.core import wot
    assert wot.satisfies_constraint(t)


def _entry_points():
    from repro.kernels import (ecc_encode, flash_attention, paged_attention,
                               quant_throttle)
    from repro.protection.backends import PallasBackend
    from repro.serving.kvcache import KVProtectionPolicy
    return {
        "ecc_decode": ecc_decode, "ecc_encode": ecc_encode.ecc_encode,
        "ecc_qmatmul": ecc_qmatmul, "throttle": throttle,
        "quantize_throttle": quant_throttle.quantize_throttle,
        "flash_attention": flash_attention.flash_attention,
        "fused_page_attention": paged_attention.fused_page_attention,
        "chunked_page_attention": paged_attention.chunked_page_attention,
        "ops.decode_weights": ops.decode_weights,
        "ops.qmatmul_protected": ops.qmatmul_protected,
        "ops.attention": ops.attention,
        "PallasBackend": PallasBackend, "KVProtectionPolicy":
            KVProtectionPolicy}


@pytest.mark.parametrize("name", sorted(_entry_points()))
def test_no_interpret_option(name):
    """The Pallas mode comes from the platform (kernels.platform), never
    from a caller: no entry point, backend or KV policy takes it."""
    import inspect
    fn = _entry_points()[name]
    assert "interpret" not in inspect.signature(fn).parameters


def test_interpret_follows_the_platform():
    from repro.kernels import platform
    assert platform.interpret() == (jax.default_backend() != "tpu")
