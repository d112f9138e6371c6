"""Chunked online-softmax paged attention: the fp64-oracle tolerance
gates that replace the bit-identity contract the chunked kernel forfeits.

Four layers of acceptance:

* tolerance sweep — 3 KV schemes x ragged lengths (including a chunk-pad
  tail) x injected faults, chunked output vs ``oracle_page_attention``
  (integer-exact codec decode, fp64 softmax/PV), flags exact vs the
  strip kernel;
* short-length cross-check — chunked also tracks the strip reference
  itself, and per-slot flag rows attribute faults to the right request;
* beyond-VMEM lengths — at >= 2 context lengths past the strip kernel's
  16 MiB VMEM crossover (~8113 tokens @ hd=128, rep=2) the chunked
  kernel still meets the oracle tolerance while its own VMEM need stays
  bounded by the chunk;
* serving plumbing — the ``attention_impl="chunked"`` override on
  ``make_serve_step`` and the ``*-chunked`` presets route real decode
  steps through the chunked kernel with logits tracking the strip twin.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import paged_attention
from repro.models import lm
from repro.serving import kvcache, protected


def _randn(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


def _strips(rng, b, s, kv, hd, scheme, faults=()):
    """Encoded K/V strips with optional injected bit flips in ke."""
    pol = kvcache.KVProtectionPolicy(scheme=scheme)
    ke, kch, ksc = kvcache._encode_kv(_randn(rng, (b, s, kv, hd)), pol)
    ve, vch, vsc = kvcache._encode_kv(_randn(rng, (b, s, kv, hd)), pol)
    if faults:
        flat = np.asarray(ke).copy()
        for bi, t, g, byte, bit in faults:
            flat[bi, t, g, byte] ^= 1 << bit
        ke = jnp.asarray(flat)
    return ke, kch, ksc, ve, vch, vsc


def _tol(oracle):
    """The acceptance tolerance: 2% of the oracle's largest magnitude."""
    return 0.02 * (np.abs(oracle).max() + 1e-6)


@pytest.mark.parametrize("s,chunk", [(96, 64), (256, 64)])
@pytest.mark.parametrize("scheme", kvcache.KV_SCHEMES)
def test_chunked_matches_fp64_oracle(scheme, s, chunk):
    """Tolerance sweep: ragged positions, GQA rep=2, faults in tokens
    valid for both batch rows; (96, 64) exercises the zero-pad tail."""
    rng = np.random.default_rng(7)
    b, kv, hd, rep = 2, 2, 16, 2
    strips = _strips(rng, b, s, kv, hd, scheme,
                     faults=[(0, 1, 0, 3, 2), (1, 5, 1, 0, 6)])
    q = _randn(rng, (b, kv * rep, 1, hd), jnp.bfloat16)
    pos = jnp.asarray([s - 1, s // 3], jnp.int32)

    o, fl = paged_attention.chunked_page_attention(
        q, *strips, pos, scheme=scheme, chunk_tokens=chunk)
    oracle = paged_attention.oracle_page_attention(
        q, *strips, pos, scheme=scheme)
    err = np.abs(np.asarray(o, np.float64) - oracle).max()
    assert err <= _tol(oracle), (scheme, s, chunk, err)
    # flag counts are exact, not tolerance-gated: cross-check vs strip
    _, fl_ref = paged_attention.fused_page_attention(
        q, *strips, pos, scheme=scheme)
    assert np.array_equal(np.asarray(fl), np.asarray(fl_ref))
    if scheme != "faulty":
        assert int(fl[0]) == 2          # one repaired flip per row


@pytest.mark.parametrize("scheme", kvcache.KV_SCHEMES)
def test_chunked_tracks_strip_reference_at_short_length(scheme):
    """Short-length cross-check: chunked vs the bit-exact strip kernel
    stays inside the same oracle tolerance, flags identical; per-slot
    rows keep the injected fault attributed to sequence 0 only."""
    rng = np.random.default_rng(9)
    b, s, kv, hd, rep = 2, 32, 2, 16, 2
    strips = _strips(rng, b, s, kv, hd, scheme, faults=[(0, 1, 0, 3, 2)])
    q = _randn(rng, (b, kv * rep, 1, hd), jnp.bfloat16)
    pos = jnp.asarray([s - 1, s // 2], jnp.int32)

    o_c, fl_c = paged_attention.chunked_page_attention(
        q, *strips, pos, scheme=scheme, chunk_tokens=16)
    o_f, fl_f = paged_attention.fused_page_attention(
        q, *strips, pos, scheme=scheme)
    oracle = paged_attention.oracle_page_attention(
        q, *strips, pos, scheme=scheme)
    tol = _tol(oracle)
    assert np.abs(np.asarray(o_c, np.float64) - oracle).max() <= tol
    assert np.abs(np.asarray(o_c, np.float64)
                  - np.asarray(o_f, np.float64)).max() <= tol
    assert np.array_equal(np.asarray(fl_c), np.asarray(fl_f))

    o_p, fl_p = paged_attention.chunked_page_attention(
        q, *strips, pos, scheme=scheme, chunk_tokens=16, per_slot=True)
    assert np.array_equal(np.asarray(o_p), np.asarray(o_c))
    assert fl_p.shape == (2, b)
    assert np.array_equal(np.asarray(fl_p).sum(axis=1), np.asarray(fl_c))
    if scheme != "faulty":
        assert int(fl_p[0, 0]) == 1 and int(fl_p[0, 1]) == 0


@pytest.mark.parametrize("scheme", kvcache.KV_SCHEMES)
def test_chunked_beyond_strip_vmem_budget(scheme):
    """The long-context acceptance: two context lengths past the strip
    kernel's VMEM crossover, all three schemes, fault injected — chunked
    meets the oracle tolerance with chunk-bounded VMEM."""
    b, kv, hd, rep, chunk = 1, 1, 128, 2, 2048
    xo = paged_attention.strip_vmem_crossover(hd, rep, scheme)
    assert (paged_attention.chunked_vmem_bytes(chunk, hd, rep, scheme)
            <= paged_attention.VMEM_BUDGET_BYTES)
    for s in (10240, 12288):
        assert s > xo
        assert (paged_attention.strip_vmem_bytes(s, hd, rep, scheme)
                > paged_attention.VMEM_BUDGET_BYTES)
        rng = np.random.default_rng(s)
        strips = _strips(rng, b, s, kv, hd, scheme,
                         faults=[(0, 7, 0, 1, 4)])
        q = _randn(rng, (b, kv * rep, 1, hd), jnp.bfloat16)
        pos = jnp.asarray([s - 1], jnp.int32)
        o, fl = paged_attention.chunked_page_attention(
            q, *strips, pos, scheme=scheme, chunk_tokens=chunk)
        oracle = paged_attention.oracle_page_attention(
            q, *strips, pos, scheme=scheme)
        err = np.abs(np.asarray(o, np.float64) - oracle).max()
        assert err <= _tol(oracle), (scheme, s, err)
        if scheme != "faulty":
            assert int(fl[0]) == 1 and int(fl[1]) == 0


def test_serve_step_attention_impl_override(plan_setup):
    """``make_serve_step(..., attention_impl="chunked")`` routes decode
    through the chunked kernel on the SAME encoded cache: logits track
    the strip twin closely, KV flags stay clean, and the knob is
    validated (needs a kv_policy; bogus impl names rejected)."""
    cfg, plan, enc = plan_setup(arch="deepseek-7b", backend="xla")
    kvp = kvcache.get_kv_policy("in-place")
    mk = lambda **kw: jax.jit(protected.make_serve_step(
        cfg, plan=plan, with_flags=True, kv_policy=kvp, **kw))
    step_s, step_c = mk(), mk(attention_impl="chunked")

    # both twins eat the SAME token stream (greedy over random-init
    # weights has near-tie logits, so per-stream greedy would fork);
    # logits then stay within a few bf16 quanta of each other
    caches = [kvcache.init_cache(cfg, 1, 32, kv_policy=kvp)
              for _ in range(2)]
    toks = jnp.zeros((1, 1), jnp.int32)
    for t in range(4):
        pos = jnp.full((1,), t, jnp.int32)
        outs = []
        for i, step in enumerate((step_s, step_c)):
            logits, caches[i], flags = step(enc, caches[i], toks, pos)
            assert int(np.asarray(flags["layers_kv"]).sum()) == 0
            outs.append(np.asarray(logits, np.float64))
        a, b = outs
        assert np.isfinite(b).all()
        assert np.abs(a - b).max() <= 0.05 * (np.abs(a).max() + 1e-6)
        toks = jnp.argmax(jnp.asarray(a), axis=-1).astype(jnp.int32)

    with pytest.raises(ValueError, match="attention_impl"):
        protected.make_serve_step(cfg, plan=plan,
                                  attention_impl="chunked")
    with pytest.raises(ValueError, match="attention_impl"):
        protected.make_serve_step(cfg, plan=plan, kv_policy=kvp,
                                  attention_impl="flash")
    with pytest.raises(ValueError, match="attention_impl"):
        protected.make_prefill(cfg, plan=plan,
                               attention_impl="chunked")


def test_chunked_preset_through_paged_gqa_decode(smoke_params):
    """The ``in-place-chunked`` preset drives ``lm.decode_step`` through
    ``paged_gqa_decode``'s chunked route: logits track the strip-preset
    twin on the same token stream."""
    cfg, params = smoke_params("deepseek-7b")
    pol_c = kvcache.get_kv_policy("in-place-chunked")
    assert pol_c.attention_impl == "chunked" and pol_c.fused
    assert pol_c.chunk_pages * pol_c.page_size >= 1

    caches = {name: kvcache.init_cache(cfg, 1, 32, kv_policy=name)
              for name in ("in-place", "in-place-chunked")}
    toks = jnp.zeros((1, 1), jnp.int32)
    for t in range(3):
        pos = jnp.full((1,), t, jnp.int32)
        outs = {}
        for name in caches:
            logits, caches[name] = lm.decode_step(
                cfg, params, caches[name], toks, pos, kv_policy=name)
            outs[name] = np.asarray(logits, np.float64)
        a, b = outs["in-place"], outs["in-place-chunked"]
        assert np.isfinite(b).all()
        assert np.abs(a - b).max() <= 0.02 * (np.abs(a).max() + 1e-6)
        toks = jnp.argmax(jnp.asarray(outs["in-place"]),
                          axis=-1).astype(jnp.int32)


def test_chunked_policy_replace_revalidates():
    """``dataclasses.replace`` re-runs the policy validators — the same
    path the serve-step override uses."""
    kvp = kvcache.get_kv_policy("in-place")
    with pytest.raises(ValueError, match="attention_impl"):
        dataclasses.replace(kvp, attention_impl="flash")
    with pytest.raises(ValueError, match="chunk_pages"):
        dataclasses.replace(kvp, chunk_pages=0)
