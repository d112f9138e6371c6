"""Fused decode-at-use serving: kernel flags, per-leaf routing, and the
numerical-identity acceptance — decode-at-use logits == decode-per-step
baseline on a trained model, for mixed-scheme plans, on both backends."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, protection
from repro.core import ecc
from repro.data import synthetic
from repro.models import lm
from repro.protection.fused import is_matmul_weight
from repro.serving import protected


def _wot_weights(rng, shape):
    w = rng.integers(-64, 64, size=shape).astype(np.int8)
    flat = w.reshape(-1)
    flat[7::8] = rng.integers(-128, 128, size=flat[7::8].size)
    return flat.reshape(shape)


def _enc(wq):
    k, n = wq.shape
    return np.asarray(ecc.encode64(jnp.asarray(
        wq.view(np.uint8).reshape(k, n // 8, 8)))).reshape(k, n)


# ---------------------------------------------------------------------------
# fused-kernel fault accounting (the flags _kernel used to drop)
# ---------------------------------------------------------------------------


def test_fused_kernel_counts_injected_doubles_and_singles():
    """Regression: the fused path must DETECT double-bit errors (DUE), not
    silently matmul through them — and count each corrected single."""
    from repro.kernels.ecc_qmatmul import ecc_qmatmul
    rng = np.random.default_rng(3)
    m, k, n = 32, 64, 128
    a = rng.integers(-127, 128, size=(m, k)).astype(np.int8)
    wenc = _enc(_wot_weights(rng, (k, n)))
    f = wenc.reshape(-1).copy()
    double_blocks, single_blocks = [1, 40, 777], [5, 123]
    for blk in double_blocks:
        f[blk * 8 + 2] ^= 0x06  # two flips in one 64-bit block
    for blk in single_blocks:
        f[blk * 8 + 4] ^= 0x20
    out, flags = ecc_qmatmul(jnp.asarray(a), jnp.asarray(f.reshape(k, n)),
                             bm=16, bn=64, bk=32, with_flags=True)
    assert int(flags[0]) == len(single_blocks)
    assert int(flags[1]) == len(double_blocks)
    # flag counting must not depend on the M grid (blocks counted once)
    _, flags2 = ecc_qmatmul(jnp.asarray(a), jnp.asarray(f.reshape(k, n)),
                            bm=8, bn=32, bk=64, with_flags=True)
    assert np.array_equal(np.asarray(flags), np.asarray(flags2))


def test_fused_kernel_edge_tiles_and_float_path():
    """No divisibility asserts: ragged (m, k) with tile sizes that don't
    divide, int8 exact vs the plain matmul; float path bit-identical to
    decode-then-matmul."""
    from repro.kernels.ecc_qmatmul import ecc_qmatmul
    rng = np.random.default_rng(7)
    m, k, n = 45, 100, 72
    wq = _wot_weights(rng, (k, n))
    wenc = _enc(wq)
    a = rng.integers(-127, 128, size=(m, k)).astype(np.int8)
    out = ecc_qmatmul(jnp.asarray(a), jnp.asarray(wenc), bm=32, bn=32, bk=64)
    assert (np.asarray(out) == a.astype(np.int32) @ wq.astype(np.int32)).all()

    scale = jnp.float32(0.02)
    x = jnp.asarray(rng.normal(size=(5, k)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    outf = ecc_qmatmul(x, jnp.asarray(wenc), scale)
    base = x @ (jnp.asarray(wq).astype(jnp.float32) * scale
                ).astype(jnp.bfloat16)
    assert np.array_equal(np.asarray(outf.astype(jnp.bfloat16), np.float32),
                          np.asarray(base, np.float32))


# ---------------------------------------------------------------------------
# the acceptance: fused decode-at-use == decode-per-step, trained model,
# mixed-scheme plan, both backends
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_decode_at_use_matches_per_step_on_trained_model(backend,
                                                         trained_minitron):
    cfg, params = trained_minitron()  # session fixture: trained ONCE
    policy = protection.get_policy_preset("attn-inplace-mlp-secded",
                                          backend=backend)
    plan = protected.make_plan(params, policy)
    assert set(plan.summary()["by_scheme"]) == {"in-place", "secded72"}
    enc = plan.encode_tree(params)
    cache = lm.init_cache(cfg, 2, 32)
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)

    at_use = jax.jit(protected.make_serve_step(cfg, plan=plan))
    per_step = jax.jit(protected.make_serve_step(cfg, plan=plan,
                                                 decode_at_use=False))
    l1, c1 = at_use(enc, cache, tok, pos)
    l2, c2 = per_step(enc, cache, tok, pos)
    assert np.array_equal(np.asarray(l1, np.float32),
                          np.asarray(l2, np.float32))
    for a, b in zip(jax.tree.leaves(c1), jax.tree.leaves(c2)):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))

    # prefill: same identity through lm.forward
    pre1 = jax.jit(protected.make_prefill(cfg, plan=plan, chunk=16))
    pre2 = jax.jit(protected.make_prefill(cfg, plan=plan, chunk=16,
                                          decode_at_use=False))
    toks = jnp.asarray(synthetic.token_batch(
        cfg.vocab_padded, 2, 16, seed=9, step=0)["tokens"])
    assert np.array_equal(
        np.asarray(pre1(enc, toks, {}), np.float32),
        np.asarray(pre2(enc, toks, {}), np.float32))


@pytest.mark.parametrize("arch", ["mamba2-2.7b", "recurrentgemma-2b"])
def test_decode_at_use_prefill_conv_archs(arch):
    """ssm/hybrid regression: depthwise conv kernels are indexed elementwise
    by _causal_conv, so they must decode to arrays (not lazy views) — and
    prefill must still match the whole-tree decode bit-for-bit."""
    cfg = configs.get_smoke(arch)
    params = lm.init_params(cfg, jax.random.PRNGKey(2))
    policy = protection.ProtectionPolicy(backend="pallas")
    plan = protected.make_plan(params, policy)
    enc = plan.encode_tree(params)
    toks = jnp.zeros((2, 16), jnp.int32)
    pre1 = jax.jit(protected.make_prefill(cfg, plan=plan, chunk=16))
    pre2 = jax.jit(protected.make_prefill(cfg, plan=plan, chunk=16,
                                          decode_at_use=False))
    assert np.array_equal(np.asarray(pre1(enc, toks, {}), np.float32),
                          np.asarray(pre2(enc, toks, {}), np.float32))


@pytest.mark.parametrize("arch", ["qwen1.5-4b", "deepseek-7b"])
def test_benchmark_policy_serves_bit_identical(arch):
    """The benchmark cells' policy: every matmul leaf takes the Pallas route
    from the policy default, and the decode-at-use serve step (the fused
    kernel at its own tiles) equals the per-step decode baseline."""
    cfg = configs.get_smoke(arch)
    params = lm.init_params(cfg, jax.random.PRNGKey(4))
    policy = protection.get_policy_preset("all-in-place", backend="pallas")
    plan = protected.make_plan(params, policy)
    mats = [lp for lp in plan
            if lp.protected and is_matmul_weight(lp.path)]
    assert mats
    assert {(lp.backend, lp.backend_src) for lp in mats} == {
        ("pallas", "policy")}
    enc = plan.encode_tree(params)
    cache = lm.init_cache(cfg, 2, 32)
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    l1, _ = jax.jit(protected.make_serve_step(cfg, plan=plan))(
        enc, cache, tok, pos)
    l2, _ = jax.jit(protected.make_serve_step(cfg, plan=plan,
                                              decode_at_use=False))(
        enc, cache, tok, pos)
    assert np.array_equal(np.asarray(l1, np.float32),
                          np.asarray(l2, np.float32))


def test_serve_flags_count_head_faults():
    """The output head decodes after the layer scans — its flags must land
    in the 'top' row, not vanish."""
    import dataclasses
    cfg = configs.get_smoke("deepseek-7b")  # untied head
    params = lm.init_params(cfg, jax.random.PRNGKey(6))
    plan = protected.make_plan(params, protection.ProtectionPolicy())
    enc = plan.encode_tree(params)
    head = enc["head"]
    img = np.asarray(head.enc).copy()
    img.reshape(-1)[5] ^= 0x03  # double-bit error in the head image
    enc["head"] = dataclasses.replace(head, enc=jnp.asarray(img))
    serve = jax.jit(protected.make_serve_step(cfg, plan=plan,
                                              with_flags=True))
    cache = lm.init_cache(cfg, 2, 32)
    _, _, flags = serve(enc, cache, jnp.zeros((2, 1), jnp.int32),
                        jnp.zeros((2,), jnp.int32))
    assert int(np.asarray(flags["top"])[1]) == 1
    assert int(np.asarray(flags["layers"]).sum()) == 0


def test_serve_flags_count_injected_faults_per_layer():
    """Per-layer (corrected, DUE) accounting: singles land in 'corrected'
    of the right row, doubles in 'due', clean tree reports zeros."""
    cfg = configs.get_smoke("deepseek-7b")
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    policy = protection.ProtectionPolicy()  # all in-place
    plan = protected.make_plan(params, policy)
    enc = plan.encode_tree(params)
    serve = jax.jit(protected.make_serve_step(cfg, plan=plan,
                                              with_flags=True))
    cache = lm.init_cache(cfg, 2, 32)
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    _, _, clean_flags = serve(enc, cache, tok, pos)
    assert set(clean_flags) == {"top", "layers"}
    assert clean_flags["layers"].shape == (lm.n_scan_layers(cfg), 2)
    assert all(int(np.asarray(v).sum()) == 0 for v in clean_flags.values())

    # one double-bit fault in layer 0's wq image, one single in the embed
    import dataclasses
    wq = enc["layers"]["attn"]["wq"]
    img = np.asarray(wq.enc).copy()
    img.reshape(-1)[3] ^= 0x03  # two flips, block 0 of layer 0
    enc["layers"]["attn"]["wq"] = dataclasses.replace(
        wq, enc=jnp.asarray(img))
    emb = enc["embed"]
    img = np.asarray(emb.enc).copy()
    img.reshape(-1)[8] ^= 0x10  # one flip
    enc["embed"] = dataclasses.replace(emb, enc=jnp.asarray(img))

    _, _, flags = serve(enc, cache, tok, pos)
    layers = np.asarray(flags["layers"])
    assert layers[0, 1] >= 1          # the DUE, attributed to layer 0
    assert layers[1:, 1].sum() == 0   # and only layer 0
    assert int(np.asarray(flags["top"])[0]) == 1  # embed single corrected


def test_due_campaign_consumes_flags():
    rng = np.random.default_rng(0)
    q = _wot_weights(rng, (64, 64)).astype(np.float32) * 0.01
    tree = {"w": jnp.asarray(q)}
    policy = protection.ProtectionPolicy(
        predicate=lambda p, l: getattr(l, "ndim", 0) >= 2)
    res = protection.due_campaign(tree, policy, rates=(0.0, 0.03), trials=2,
                                  key=jax.random.PRNGKey(20))
    assert res.metric == "due_count"
    assert res.clean == 0.0
    assert res.mean()[0] == 0.0          # zero rate -> zero DUE
    assert res.mean()[1] > 0.0           # 3% bit flips -> some doubles
    # corrected counts sweep too, and see even more events than DUEs
    corr = protection.due_campaign(tree, policy, rates=(0.03,), trials=2,
                                   key=jax.random.PRNGKey(21),
                                   what="corrected")
    assert corr.mean()[0] > 0.0
