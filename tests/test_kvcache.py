"""``repro.serving.kvcache`` — the paged, zero-space-protected KV cache:
page codec fault behaviour, fused-vs-reference bit identity, the paged
serving chain (prefill -> decode), live-pool injection and per-layer KV
flags, KV fault campaigns, byte accounting, the plan-level KV knob, and
the windowed-ring / ragged-length attention regressions it leans on."""
import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, protection
from repro.kernels import paged_attention
from repro.models import layers as L
from repro.models import lm
from repro.serving import kvcache, protected

CFG = configs.get_smoke("deepseek-7b")    # dense smoke: 4 heads / 4 kv
GQA = configs.get_smoke("minitron-4b")    # 4 heads / 2 kv (GQA rep = 2)


def _randn(rng, shape, dtype=jnp.float32):
    return jnp.asarray(rng.standard_normal(shape), dtype=dtype)


# ---------------------------------------------------------------------------
# attention regressions the paged path leans on
# ---------------------------------------------------------------------------


def test_windowed_ring_ignores_cache_overallocation():
    """A windowed decode must attend to exactly the last ``window`` tokens
    no matter how large the ring buffer was allocated. The old slot mask
    treated every slot as valid once pos >= smax, silently widening the
    window to smax when the cache was over-allocated."""
    cfg, b, window, steps = CFG, 2, 4, 11
    rng = np.random.default_rng(0)
    p = {k: _randn(rng, s) * 0.05
         for k, s in L.gqa_params_shape(cfg).items()}
    xs = [_randn(rng, (b, 1, cfg.d_model)) for _ in range(steps)]
    outs = {}
    for smax in (window, 3 * window):   # exact ring vs over-allocated ring
        cache = {"k": jnp.zeros((b, smax, cfg.n_kv_heads, cfg.head_dim)),
                 "v": jnp.zeros((b, smax, cfg.n_kv_heads, cfg.head_dim))}
        outs[smax] = []
        for t, x in enumerate(xs):
            pos = jnp.full((b,), t, jnp.int32)
            o, cache = L.gqa_decode(p, x, cfg, cache, pos=pos, window=window)
            outs[smax].append(np.asarray(o, np.float32))
    # steps > smax wraps the small ring twice and leaves the big ring with
    # never-written slots — both must still see only the last 4 tokens
    for a, c in zip(outs[window], outs[3 * window]):
        np.testing.assert_allclose(a, c, rtol=1e-5, atol=1e-6)


def test_decode_attention_ragged_lengths():
    """Per-sequence ``length_mask``: each row of a ragged batch must equal
    single-sequence attention over its own truncated cache, and garbage in
    masked slots must not leak into any row."""
    rng = np.random.default_rng(1)
    b, h, s, d = 3, 2, 9, 8
    q = _randn(rng, (b, h, 1, d))
    k = _randn(rng, (b, h, s, d))
    v = _randn(rng, (b, h, s, d))
    lengths = np.array([2, 5, 9])
    mask = jnp.asarray(np.arange(s)[None, :] < lengths[:, None])
    o = L.decode_attention(q, k, v, mask)
    for i, n in enumerate(lengths):
        ref = L.decode_attention(q[i:i + 1], k[i:i + 1, :, :n],
                                 v[i:i + 1, :, :n])
        np.testing.assert_allclose(np.asarray(o[i:i + 1]), np.asarray(ref),
                                   rtol=1e-5, atol=1e-6)
    poison = mask[:, None, :, None]
    o2 = L.decode_attention(q, jnp.where(poison, k, 1e4),
                            jnp.where(poison, v, -1e4), mask)
    assert np.array_equal(np.asarray(o), np.asarray(o2))


# ---------------------------------------------------------------------------
# page codec
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("scheme", kvcache.KV_SCHEMES)
def test_page_codec_fault_behaviour(scheme):
    """One flipped bit in an encoded page: in-place corrects it, parity-zero
    detects and zeroes the byte, the unprotected baseline silently serves
    the corruption. Clean pages decode with zero flags everywhere."""
    pol = kvcache.KVProtectionPolicy(scheme=scheme)
    rng = np.random.default_rng(2)
    kf = _randn(rng, (2, 16, 2, 16))                     # (B, S, kv, hd)
    enc, checks, scale = kvcache._encode_kv(kf, pol)
    assert enc.dtype == jnp.uint8 and scale.shape == (2, 16)
    q0, cor0, due0 = kvcache._decode_kv(enc, checks, pol.scheme, pol.backend)
    assert q0.dtype == jnp.int8
    assert int(jnp.sum(cor0)) == 0 and int(jnp.sum(due0)) == 0

    flat = np.asarray(enc).copy()
    flat.flat[37] ^= 1 << 3                              # one data-bit fault
    q1, cor1, due1 = kvcache._decode_kv(jnp.asarray(flat), checks,
                                        pol.scheme, pol.backend)
    if scheme == "faulty":
        assert not np.array_equal(np.asarray(q0), np.asarray(q1))
        assert int(jnp.sum(cor1)) == 0 and int(jnp.sum(due1)) == 0
    elif scheme == "parity-zero":
        diff = np.asarray(q0) != np.asarray(q1)
        assert diff.sum() == 1 and np.asarray(q1).flat[37] == 0
        assert int(jnp.sum(cor1)) == 1 and int(jnp.sum(due1)) == 0
    else:                                                # in-place corrects
        assert np.array_equal(np.asarray(q0), np.asarray(q1))
        assert int(jnp.sum(cor1)) == 1 and int(jnp.sum(due1)) == 0


def test_page_quantization_error_bound():
    """The unprotected int8 page codec is plain per-token absmax
    quantization: dequantized error stays within half an LSB."""
    pol = kvcache.KVProtectionPolicy(scheme="faulty")
    rng = np.random.default_rng(3)
    kf = _randn(rng, (2, 8, 2, 16))
    enc, checks, scale = kvcache._encode_kv(kf, pol)
    q, _, _ = kvcache._decode_kv(enc, checks, pol.scheme, pol.backend)
    deq = np.asarray(q, np.float32) * np.asarray(scale)[..., None, None]
    err = np.abs(deq - np.asarray(kf))
    lsb = np.asarray(scale)[..., None, None]
    assert (err <= 0.5 * lsb + 1e-6).all()


# ---------------------------------------------------------------------------
# fused kernel == XLA reference, bit for bit
# ---------------------------------------------------------------------------


# name -> (s, flips): a flip XORs ``bits`` into byte ``byte`` of token
# ``t`` of kv head ``g`` of batch row ``bi`` of the K or V strip. The
# positions are (s - 1, s // 2), so token 40 is past row 1's.
_STRIP_CASES = {
    "clean": (64, []),
    "single": (64, [("k", 0, 1, 0, 3, 1 << 2)]),
    "double": (64, [("v", 1, 5, 1, 2, 0b10010)]),
    "past_pos": (64, [("k", 1, 40, 0, 3, 1 << 2)]),
    "two_chunks": (64, [("k", 0, 3, 1, 1, 1), ("k", 0, 50, 1, 9, 1 << 6),
                        ("v", 1, 10, 0, 4, 1 << 3)]),
    "odd_s": (48, [("k", 0, 1, 0, 3, 1 << 2)]),
}


@pytest.mark.parametrize("case", sorted(_STRIP_CASES))
@pytest.mark.parametrize("kernel", ["strip", "chunked"])
@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("scheme", kvcache.KV_SCHEMES)
def test_fused_page_attention_bitexact(scheme, backend, kernel, case):
    """The fused decode-at-use strip kernel must match decode-then-
    ``decode_attention`` bit for bit (the chunked kernel: within the fp64
    oracle's tolerance), with identical flag counts, in total and per
    slot (ragged positions, GQA rep=2), on clean and faulted strips. The
    reference is jitted, as in the serving paths: eager op-by-op execution
    materializes an intermediate bf16 rounding of the score dot that XLA
    elides when the reference compiles as one program.

    The in-place packed pass (strips of a multiple of 32 rows) counts in
    lane ``_SLOW`` of the raw flag rows the 32-row chunks it redid per
    byte: exactly the dirty chunks among valid rows. A 48-token strip
    (the chunked kernel given it whole) decodes per byte and counts 0."""
    rng = np.random.default_rng(4)
    s, flips = _STRIP_CASES[case]
    b, kv, hd, rep = 2, 2, 16, 2
    pol = kvcache.KVProtectionPolicy(scheme=scheme, backend=backend,
                                     per_slot_flags=True)
    q = _randn(rng, (b, kv * rep, 1, hd), jnp.bfloat16)
    strips = {"k": kvcache._encode_kv(_randn(rng, (b, s, kv, hd)), pol),
              "v": kvcache._encode_kv(_randn(rng, (b, s, kv, hd)), pol)}
    pos = np.asarray([s - 1, s // 2], np.int32)        # ragged batch
    flat = {name: np.asarray(enc[0]).copy() for name, enc in strips.items()}
    slow = np.zeros((b, kv), np.int32)
    expect = np.zeros((2, b), np.int32)     # (corrected, DUE) per slot
    for name, bi, t, g, byte, bits in flips:
        flat[name][bi, t, g, byte] ^= bits
        n = bin(bits).count("1")
        if t > pos[bi] or scheme == "faulty":
            continue
        if scheme == "in-place":            # one flip per block
            expect[n - 1, bi] += 1
        elif n % 2:                         # parity sees odd flips only
            expect[0, bi] += 1
    for name, bi, g, c in {(name, bi, g, t // 32)
                           for name, bi, t, g, *_ in flips if t <= pos[bi]}:
        slow[bi, g] += 1
    ke, kch, ksc = jnp.asarray(flat["k"]), *strips["k"][1:]
    ve, vch, vsc = jnp.asarray(flat["v"]), *strips["v"][1:]
    args = (q, ke, kch, ksc, ve, vch, vsc, jnp.asarray(pos))

    o_r, cor, due = jax.jit(lambda *a: kvcache._reference_paged_attention(
        *a, pol))(*args)
    if kernel == "strip":
        attend = functools.partial(paged_attention.fused_page_attention,
                                   scheme=scheme)
        raw = functools.partial(paged_attention._fused_call, scheme=scheme)
    else:  # 32-token chunks, but a 48-token strip in one chunk
        chunk = 32 if s % 32 == 0 else s
        attend = functools.partial(paged_attention.chunked_page_attention,
                                   scheme=scheme, chunk_tokens=chunk)
        raw = functools.partial(paged_attention._chunked_call,
                                scheme=scheme, chunk_tokens=chunk)
    o_f, fl_f = attend(*args)
    if kernel == "strip":
        assert np.array_equal(np.asarray(o_f), np.asarray(o_r))
    else:
        oracle = paged_attention.oracle_page_attention(*args, scheme=scheme)
        tol = 0.02 * (np.abs(oracle).max() + 1e-6)
        assert np.abs(np.asarray(o_f, np.float64) - oracle).max() <= tol
    assert np.array_equal(np.stack([cor, due]), expect)
    assert np.array_equal(np.asarray(fl_f), expect.sum(axis=1))

    # per-slot rows: same output, flags resolved per batch row
    o_p, fl_p = attend(*args, per_slot=True)
    assert np.array_equal(np.asarray(o_p), np.asarray(o_f))
    assert np.array_equal(np.asarray(fl_p), expect)

    _, rows = jax.jit(raw)(*args)
    packed = scheme == "in-place" and s % 32 == 0
    assert np.array_equal(np.asarray(rows)[:, :, 0, paged_attention._SLOW],
                          slow if packed else 0 * slow)


# ---------------------------------------------------------------------------
# the paged serving chain
# ---------------------------------------------------------------------------


def test_paged_decode_tracks_dense(smoke_params):
    """Paged int8 decode (GQA arch, rep=2) follows the dense bf16 chain:
    same shapes, finite logits, strongly correlated — exact agreement is
    not expected (the pages are int8-quantized)."""
    (_, params), (cfg, b, smax) = smoke_params("minitron-4b"), (GQA, 2, 32)
    dense = kvcache.init_cache(cfg, b, smax)
    paged = kvcache.init_cache(cfg, b, smax, kv_policy="unprotected")
    assert "k_pages" in paged and "k_checks" not in paged
    toks_d = toks_p = jnp.zeros((b, 1), jnp.int32)
    corrs = []
    for t in range(5):
        pos = jnp.full((b,), t, jnp.int32)
        ld, dense = lm.decode_step(cfg, params, dense, toks_d, pos)
        lp, paged = lm.decode_step(cfg, params, paged, toks_p, pos,
                                   kv_policy="unprotected")
        assert ld.shape == lp.shape == (b, 1, cfg.vocab_padded)
        a = np.asarray(ld, np.float32).ravel()
        c = np.asarray(lp, np.float32).ravel()
        assert np.isfinite(c).all()
        corrs.append(np.corrcoef(a, c)[0, 1])
        toks_d = jnp.argmax(ld, axis=-1).astype(jnp.int32)
        toks_p = jnp.argmax(lp, axis=-1).astype(jnp.int32)
    assert np.mean(corrs) > 0.5, corrs


def test_paged_decode_requires_policy(smoke_params):
    cfg, params = smoke_params("deepseek-7b")
    cache = kvcache.init_cache(cfg, 1, 16, kv_policy="in-place")
    with pytest.raises(ValueError, match="kv_policy"):
        lm.decode_step(cfg, params, cache, jnp.zeros((1, 1), jnp.int32),
                       jnp.zeros((1,), jnp.int32))


def test_prefill_then_decode_chain(smoke_params):
    """``prefill_with_cache`` fills the pools so decode steps continue from
    them; clean pools report all-zero per-layer KV flags."""
    (_, params), (cfg, b, n) = smoke_params("deepseek-7b", 1), (CFG, 2, 20)
    cache = kvcache.init_cache(cfg, b, 48, kv_policy="in-place")
    toks = jnp.asarray(
        np.random.default_rng(5).integers(0, cfg.vocab, (b, n)), jnp.int32)
    logits, cache, flags = lm.prefill_with_cache(
        cfg, params, cache, toks, kv_policy="in-place", collect_flags=True)
    assert logits.shape == (b, n, cfg.vocab_padded)
    assert flags["layers_kv"].shape == (cfg.n_layers, 2)
    assert int(jnp.sum(flags["layers_kv"])) == 0
    nxt = jnp.argmax(logits[:, -1:], axis=-1).astype(jnp.int32)
    l2, cache, f2 = lm.decode_step(cfg, params, cache, nxt,
                                   jnp.full((b,), n, jnp.int32),
                                   kv_policy="in-place", collect_flags=True)
    assert l2.shape == (b, 1, cfg.vocab_padded)
    assert int(jnp.sum(f2["layers_kv"])) == 0


def test_live_pool_injection_flags(smoke_params):
    """Faults injected into the LIVE pools surface as per-layer (corrected,
    DUE) counts — both through ``tree_layer_flags`` and through the next
    decode step's ``layers_kv`` flags."""
    (_, params), (cfg, b) = smoke_params("deepseek-7b", 2), (CFG, 2)
    pol = kvcache.get_kv_policy("in-place")
    cache = kvcache.init_cache(cfg, b, 32, kv_policy=pol)
    toks = jnp.asarray(
        np.random.default_rng(6).integers(0, cfg.vocab, (b, 24)), jnp.int32)
    _, cache = lm.prefill_with_cache(cfg, params, cache, toks, kv_policy=pol)

    tree = kvcache.as_protected_tree(cache, pol)
    clean = np.asarray(kvcache.tree_layer_flags(tree))
    assert clean.shape == (cfg.n_layers, 2) and clean.sum() == 0
    dirty = protection.inject_tree_device(tree, 3e-3,
                                          jax.random.PRNGKey(7))
    rows = np.asarray(kvcache.tree_layer_flags(dirty))
    assert rows[:, 0].sum() > 0

    cache = kvcache.from_protected_tree(cache, dirty)
    _, _, flags = lm.decode_step(cfg, params, cache,
                                 jnp.zeros((b, 1), jnp.int32),
                                 jnp.full((b,), 24, jnp.int32),
                                 kv_policy=pol, collect_flags=True)
    assert int(jnp.sum(flags["layers_kv"][:, 0])) > 0


# ---------------------------------------------------------------------------
# KV fault campaigns
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("seq", [16, 48])
def test_due_campaign_kv_target(seq, smoke_params):
    """``due_campaign(target="kv")`` sweeps the serving state at multiple
    context lengths and carries per-layer rows; JSON round-trips losslessly
    and pre-KV artifacts (no target / layer_rows keys) still load."""
    (_, params), (cfg, b) = smoke_params("deepseek-7b", 3), (CFG, 2)
    pol = kvcache.get_kv_policy("in-place")
    cache = kvcache.init_cache(cfg, b, seq, kv_policy=pol)
    toks = jnp.asarray(
        np.random.default_rng(8).integers(0, cfg.vocab, (b, seq)), jnp.int32)
    _, cache = lm.prefill_with_cache(cfg, params, cache, toks, kv_policy=pol)
    tree = kvcache.as_protected_tree(cache, pol)

    res = protection.due_campaign(None, "in-place", rates=(1e-3, 5e-3),
                                  trials=2, key=jax.random.PRNGKey(9),
                                  target="kv", kv_tree=tree)
    assert res.target == "kv"
    assert len(res.layer_rows) == cfg.n_layers
    assert sum(r[0] for r in res.layer_rows) > 0   # corrected singles
    rt = protection.CampaignResult.from_json(res.to_json())
    assert rt == res

    legacy = res.to_dict()
    legacy.pop("target"), legacy.pop("layer_rows")
    old = protection.CampaignResult.from_dict(legacy)
    assert old.target == "weights" and old.layer_rows == ()


def test_due_campaign_both_targets(smoke_params):
    (_, params), (cfg, b) = smoke_params("deepseek-7b", 4), (CFG, 1)
    pol = kvcache.get_kv_policy("in-place")
    cache = kvcache.init_cache(cfg, b, 16, kv_policy=pol)
    toks = jnp.asarray(
        np.random.default_rng(10).integers(0, cfg.vocab, (b, 16)), jnp.int32)
    _, cache = lm.prefill_with_cache(cfg, params, cache, toks, kv_policy=pol)
    tree = kvcache.as_protected_tree(cache, pol)
    policy = protection.ProtectionPolicy(default_scheme="in-place")
    enc = policy.encode_tree(params)
    res = protection.due_campaign(enc, policy, rates=(5e-3,), trials=1,
                                  key=jax.random.PRNGKey(11),
                                  target="both", kv_tree=tree)
    assert res.target == "both" and len(res.layer_rows) == cfg.n_layers
    with pytest.raises(ValueError, match="kv_tree"):
        protection.due_campaign(enc, policy, target="kv")


# ---------------------------------------------------------------------------
# byte accounting: the zero-space claim as bytes
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("preset",
                         ["unprotected", "parity-zero", "in-place"])
def test_freed_page_reuse_no_stale_carryover(preset, smoke_params):
    """Page free/reuse hygiene: a freed-then-reassigned page serves a new
    sequence EXACTLY like a fresh pool — no stale-scale, stale-parity, or
    stale-fault carryover from the previous tenant, even after the
    tenant's pages absorbed injected faults while live."""
    cfg, params = smoke_params("deepseek-7b")
    pol = kvcache.get_kv_policy(preset)
    b, max_len, n_pages = 2, 32, 6
    rng = np.random.default_rng(13)
    seq_a = rng.integers(0, cfg.vocab, 8)
    seq_b = rng.integers(0, cfg.vocab, 6)

    def step(cache, toks, pos):
        return lm.decode_step(cfg, params, cache, toks, pos,
                              kv_policy=pol, collect_flags=True)

    # tenant A lives on slot 0, pages (2, 3); slot 1 idles on its parking
    # page (keep-alive token 0 at pos 0, like the serving front-end)
    cache = kvcache.init_paged_cache(cfg, b, max_len, pol,
                                     n_pages=n_pages)
    cache = kvcache.set_slot_pages(cache, 0, (2, 3))
    for t, tok in enumerate(seq_a):
        _, cache, _ = step(cache, jnp.asarray([[int(tok)], [0]], jnp.int32),
                           jnp.asarray([t, 0], jnp.int32))
    # the pool absorbs faults while A is live (scales/parity now reflect
    # A's tenancy plus flipped bits)
    tree = kvcache.as_protected_tree(cache, pol)
    dirty = protection.inject_tree_device(tree, 2e-3,
                                          jax.random.PRNGKey(21))
    cache = kvcache.from_protected_tree(cache, dirty)
    # A finishes: zero its pages, park its slot — the free-side hygiene
    cache = kvcache.zero_pages(cache, (2, 3))
    cache = kvcache.set_slot_pages(cache, 0, ())

    def serve_b(c):
        # tenant B reuses pages (2, 3) from slot 1
        c = kvcache.set_slot_pages(c, 1, (2, 3))
        outs, nflags = [], 0
        for t, tok in enumerate(seq_b):
            lg, c, fl = step(c, jnp.asarray([[0], [int(tok)]], jnp.int32),
                             jnp.asarray([0, t], jnp.int32))
            outs.append(np.asarray(lg, np.float32))
            nflags += int(jnp.sum(fl["layers_kv"]))
        return outs, nflags

    reused, fl_reused = serve_b(cache)
    fresh, fl_fresh = serve_b(kvcache.init_paged_cache(
        cfg, b, max_len, pol, n_pages=n_pages))
    assert fl_reused == 0 and fl_fresh == 0   # nothing stale surfaces
    for got, want in zip(reused, fresh):
        assert np.array_equal(got, want)      # bit-identical serving


def test_page_allocator_and_pool_helpers():
    """Host-side allocator contract: deterministic lowest-id-first order,
    parking pages never handed out, double-free and foreign-free rejected,
    refcounted sharing exact (free releases a page only when its LAST
    reference drops, and reports exactly which pages it released)."""
    a = kvcache.PageAllocator(8, reserved=2)
    assert a.free_count == 6 and a.can(6) and not a.can(7)
    assert a.alloc(3) == (2, 3, 4)
    assert a.free([3]) == (3,)
    assert a.alloc(1) == (3,)                 # lowest id first, reused
    with pytest.raises(ValueError, match="exhausted"):
        a.alloc(5)
    with pytest.raises(ValueError, match="not allocatable"):
        a.free([1])                           # parking page
    with pytest.raises(ValueError, match="not allocatable"):
        a.free([8])                           # out of pool
    assert a.free([2]) == (2,)
    with pytest.raises(ValueError, match="double free"):
        a.free([2])
    # refcounts: a shared page survives all but its last free
    assert a.refcount(3) == 1 and a.refcount(2) == 0
    a.retain([3, 4])
    assert a.refcount(3) == a.refcount(4) == 2
    assert a.free([3, 4]) == ()               # sharers still hold them
    assert a.free([3]) == (3,)
    with pytest.raises(ValueError, match="double free"):
        a.free([3])
    with pytest.raises(ValueError, match="no live reference"):
        a.retain([3])                         # can't revive a dead page
    assert a.free_count + a.live_count == 6   # conservation, always
    assert kvcache.pages_needed(1, 16) == 1
    assert kvcache.pages_needed(16, 16) == 1
    assert kvcache.pages_needed(17, 16) == 2

    pol = kvcache.get_kv_policy("parity-zero")
    cache = kvcache.init_paged_cache(CFG, 2, 32, pol, n_pages=6)
    # parking layout: slot b's whole table row points at page b
    assert (np.asarray(cache["kv_table"][:, 0]) == 0).all()
    assert (np.asarray(cache["kv_table"][:, 1]) == 1).all()
    with pytest.raises(ValueError, match="parking"):
        kvcache.init_paged_cache(CFG, 2, 32, pol, n_pages=2)
    cache = kvcache.set_slot_pages(cache, 1, (4,))
    row = np.asarray(cache["kv_table"][:, 1])
    assert (row[:, 0] == 4).all() and (row[:, 1] == 1).all()  # tail parks
    with pytest.raises(ValueError, match="pages_per_seq"):
        kvcache.set_slot_pages(cache, 0, (2, 3, 4))


def test_kv_bytes_accounting():
    cfg, b, s = CFG, 4, 64
    by = {}
    for scheme in kvcache.KV_SCHEMES:
        pol = kvcache.KVProtectionPolicy(scheme=scheme)
        cache = jax.eval_shape(lambda p=pol: kvcache.init_paged_cache(
            cfg, b, s, p))
        by[scheme] = kvcache.kv_bytes(cache)
    stored = by["in-place"]["stored"]
    assert stored == by["faulty"]["stored"] == by["parity-zero"]["stored"]
    assert by["in-place"]["checks"] == 0          # zero-space: no growth
    assert by["faulty"]["checks"] == 0
    assert by["parity-zero"]["checks"] == stored // 8
    assert kvcache.dense_kv_bytes(cfg, b, s) == 2 * stored  # bf16 vs int8


def test_kv_policy_presets():
    assert set(kvcache.KV_POLICY_PRESETS) == {
        "unprotected", "parity-zero", "in-place",
        "unprotected-fused", "parity-zero-fused", "in-place-fused",
        "unprotected-chunked", "parity-zero-chunked", "in-place-chunked"}
    assert kvcache.get_kv_policy(None) is None
    p = kvcache.get_kv_policy("in-place-fused")
    assert p.scheme == "in-place" and p.fused
    assert p.attention_impl == "strip"
    assert kvcache.get_kv_policy(p) is p
    assert kvcache.get_kv_policy("faulty").scheme == "faulty"  # alias
    c = kvcache.get_kv_policy("in-place-chunked")
    assert c.scheme == "in-place" and c.attention_impl == "chunked"
    with pytest.raises(ValueError, match="unknown KV policy"):
        kvcache.get_kv_policy("triplicate")
    with pytest.raises(ValueError, match="attention_impl"):
        kvcache.KVProtectionPolicy(attention_impl="flash")
    with pytest.raises(ValueError, match="chunk_pages"):
        kvcache.KVProtectionPolicy(chunk_pages=0)


# ---------------------------------------------------------------------------
# plan-level KV knob + serving entry points
# ---------------------------------------------------------------------------


def test_plan_kv_policy_drives_serving(smoke_params):
    """``ProtectionPlan.with_kv_policy`` makes one plan object carry both
    the weight and the serving-state decisions: ``make_serve_step`` /
    ``make_prefill`` default their KV policy from it."""
    (_, params), (cfg, b) = smoke_params("deepseek-7b", 5), (CFG, 2)
    policy = protection.ProtectionPolicy(default_scheme="in-place")
    plan = policy.plan(params).with_kv_policy("in-place")
    assert plan.kv_policy.scheme == "in-place"
    assert plan.summary()["kv_policy"]["scheme"] == "in-place"
    assert plan.summary()["kv_policy"]["attention_impl"] == "strip"
    assert plan.with_act_quant("dynamic").kv_policy is plan.kv_policy

    enc = plan.encode_tree(params)
    cache = kvcache.init_cache(cfg, b, 32, kv_policy=plan.kv_policy)
    step = protected.make_serve_step(cfg, plan=plan, with_flags=True)
    logits, cache, flags = step(enc, cache, jnp.zeros((b, 1), jnp.int32),
                                jnp.zeros((b,), jnp.int32))
    assert logits.shape == (b, 1, cfg.vocab_padded)
    assert flags["layers_kv"].shape == (cfg.n_layers, 2)

    prefill = protected.make_prefill(cfg, plan=plan, with_flags=True)
    cache2 = kvcache.init_cache(cfg, b, 32, kv_policy=plan.kv_policy)
    toks = jnp.zeros((b, 8), jnp.int32)
    logits, cache2, flags = prefill(enc, cache2, toks)
    assert logits.shape == (b, 8, cfg.vocab_padded)
    assert "layers_kv" in flags and "k_pages" in cache2


# ---------------------------------------------------------------------------
# bench artifact: bench_kernels/v6 attention + long-context + ABFT rows
# ---------------------------------------------------------------------------


def test_autotune_attention_rows():
    entry = {"shape": [256, 256], "xla_us": 1.0, "pallas_us": 2.0,
             "best": "xla"}
    row = {"shape": [2, 128, 2, 32], "scheme": "in-place",
           "fused_us": 1.0, "ref_us": 2.0, "bitexact": True}
    long_row = {"shape": [1, 8192, 1, 128], "scheme": "in-place",
                "chunk_tokens": 2048, "chunked_us": 9.0, "strip_us": 8.0,
                "strip_vmem_bytes": 17_000_000, "over_budget": True,
                "oracle_max_abs_err": 1e-3, "tol": 2e-2,
                "within_tol": True}
    xo = {"head_dim": 128, "rep": 2, "vmem_budget_bytes": 16 * 2 ** 20,
          "chunk_tokens": 2048, "tokens_by_scheme": {"in-place": 8113}}
    t = protection.AutotuneTable.from_dict(
        {"schema": "bench_kernels/v5", "platform": "cpu",
         "entries": [entry], "attention": [row],
         "attention_long": [long_row], "crossover": xo})
    assert t.schema == protection.BENCH_KERNELS_SCHEMA_V5 == "bench_kernels/v5"
    assert t.attention == [row]
    assert t.attention_long == [long_row] and t.crossover == xo
    rt = protection.AutotuneTable.from_dict(t.to_dict())
    assert rt.attention == [row] and rt.attention_long == [long_row]
    assert rt.crossover == xo
    # v4 artifacts (attention rows, no long-context section) still load
    v4 = protection.AutotuneTable.from_dict(
        {"schema": protection.BENCH_KERNELS_SCHEMA_V4,
         "entries": [entry], "attention": [row]})
    assert v4.attention == [row] and v4.attention_long == []
    assert v4.crossover is None
    for old in (protection.BENCH_KERNELS_SCHEMA_V1,
                protection.BENCH_KERNELS_SCHEMA_V2,
                protection.BENCH_KERNELS_SCHEMA_V3):
        legacy = protection.AutotuneTable.from_dict(
            {"schema": old, "entries": [entry]})
        assert legacy.attention == [] and legacy.lookup([256, 256]) == "xla"
    with pytest.raises(ValueError, match="unsupported autotune schema"):
        protection.AutotuneTable.from_dict({"schema": "bench_kernels/v9"})

    checked_in = os.path.join(os.path.dirname(__file__), os.pardir,
                              "BENCH_kernels.json")
    shipped = protection.AutotuneTable.from_json(checked_in)
    assert shipped.schema == protection.BENCH_KERNELS_SCHEMA == "bench_kernels/v6"
    assert shipped.attention and all(r["bitexact"] for r in shipped.attention)
    assert shipped.attention_long and shipped.crossover
    assert all(r["within_tol"] for r in shipped.attention_long)
    assert any(r["over_budget"] for r in shipped.attention_long)
    # v6 ABFT twin rows: priced at the winning tiles for reporting, never
    # consulted by the lookups
    assert all(e.get("fused_abft_us") and e.get("fused_int8_abft_us")
               for e in shipped.entries)
