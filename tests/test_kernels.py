"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, bit-exactness."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import ecc, faults
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
