"""Int8 end-to-end fused serving: the requantize epilogue, the decode-once
grid, activation calibration, and the acceptance — int8 at-use serving is
bit-exact vs the quantize->decode->matmul reference on both backends."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, protection
from repro.core import ecc, quant
from repro.kernels import ref
from repro.kernels.ecc_qmatmul import ecc_qmatmul
from repro.models import lm
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
# kernel: the fused requantize epilogue
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n,bm,bn", [
    (32, 64, 128, 16, 64),     # clean tiles
    (45, 100, 72, 16, 32),     # ragged everything (edge-tile masking)
])
def test_epilogue_bit_exact_vs_requantize_reference(m, k, n, bm, bn):
    """int8 a + a_scale -> (acc * a_scale*w_scale) cast bf16 in VMEM, equal
    BIT FOR BIT to the XLA quantize->decode->matmul->rescale sequence (the
    int32 accumulation is one exact MXU pass)."""
    rng = np.random.default_rng(m + n)
    wq = _wot_weights(rng, (k, n))
    wenc = jnp.asarray(_enc(wq))
    a = jnp.asarray(rng.integers(-127, 128, size=(m, k)).astype(np.int8))
    w_scale = jnp.float32(0.013)
    # per-row (dynamic per-token) scales AND a scalar (static) scale
    for a_scale in (jnp.asarray(rng.uniform(0.005, 0.05, size=(m, 1))
                                .astype(np.float32)),
                    jnp.float32(0.02)):
        out = ecc_qmatmul(a, wenc, w_scale, a_scale=a_scale, bm=bm, bn=bn)
        assert out.dtype == jnp.bfloat16
        acc = ref.ecc_qmatmul_ref(a, wenc)
        want = (acc.astype(jnp.float32) * (a_scale * w_scale)
                ).astype(jnp.bfloat16)
        assert np.array_equal(np.asarray(out, np.float32),
                              np.asarray(want, np.float32))


def test_epilogue_int32_bias_add():
    rng = np.random.default_rng(9)
    m, k, n = 16, 64, 64
    wq = _wot_weights(rng, (k, n))
    wenc = jnp.asarray(_enc(wq))
    a = jnp.asarray(rng.integers(-127, 128, size=(m, k)).astype(np.int8))
    bias = jnp.asarray(rng.integers(-5000, 5000, size=(n,)).astype(np.int32))
    a_scale = jnp.float32(0.01)
    w_scale = jnp.float32(0.02)
    out = ecc_qmatmul(a, wenc, w_scale, a_scale=a_scale, bias=bias,
                      bm=8, bn=32)
    acc = ref.ecc_qmatmul_ref(a, wenc) + bias[None, :]
    want = (acc.astype(jnp.float32) * (a_scale * w_scale)).astype(jnp.bfloat16)
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(want, np.float32))


def test_epilogue_out_dtype_and_guards():
    rng = np.random.default_rng(2)
    k, n = 32, 32
    wenc = jnp.asarray(_enc(_wot_weights(rng, (k, n))))
    a = jnp.asarray(rng.integers(-127, 128, size=(4, k)).astype(np.int8))
    out = ecc_qmatmul(a, wenc, jnp.float32(0.1), a_scale=jnp.float32(0.1),
                      out_dtype=jnp.float32)
    assert out.dtype == jnp.float32
    with pytest.raises(ValueError, match="requantize epilogue needs w_scale"):
        ecc_qmatmul(a, wenc, a_scale=jnp.float32(0.1))
    with pytest.raises(ValueError, match="bias"):
        ecc_qmatmul(a, wenc, bias=jnp.zeros((n,), jnp.int32))
    with pytest.raises(ValueError, match="a_scale"):
        ecc_qmatmul(a.astype(jnp.bfloat16), wenc, jnp.float32(0.1),
                    a_scale=jnp.float32(0.1))


# ---------------------------------------------------------------------------
# kernel: the decode-once (M-innermost, VMEM scratch) grid
# ---------------------------------------------------------------------------


def test_decode_once_flags_tied_to_single_decode():
    """Flag counting lives inside the same predicated block as the decode
    into the VMEM scratch, so exact flag totals across MANY M tiles are a
    runtime witness that each weight tile decodes once per (N, K) tile —
    re-decoding per M tile would multiply the counts by ceil(M/BM)."""
    rng = np.random.default_rng(4)
    m, k, n = 128, 64, 128
    wq = _wot_weights(rng, (k, n))
    f = _enc(wq).reshape(-1).copy()
    double_blocks, single_blocks = [0, 33, 500], [7, 250, 900]
    for blk in double_blocks:
        f[blk * 8 + 1] ^= 0x05
    for blk in single_blocks:
        f[blk * 8 + 6] ^= 0x40
    fenc = jnp.asarray(f.reshape(k, n))
    a = jnp.asarray(rng.integers(-127, 128, size=(m, k)).astype(np.int8))
    # 16 M tiles x 4 N tiles x 2 K tiles — heavy M grid
    out, flags = ecc_qmatmul(a, fenc, bm=8, bn=32, bk=32, with_flags=True)
    assert int(flags[0]) == len(single_blocks)
    assert int(flags[1]) == len(double_blocks)
    # and the scratch reuse path (i > 0) computes the right values: singles
    # corrected, so all M rows equal the unfaulted matmul
    plain = np.asarray(a).astype(np.int32) @ wq.astype(np.int32)
    doubles_cols = set()
    for blk in double_blocks:  # columns touched by uncorrectable blocks
        doubles_cols.update(range(blk % (n // 8) * 8, blk % (n // 8) * 8 + 8))
    clean_cols = [c for c in range(n) if c not in doubles_cols]
    assert np.array_equal(np.asarray(out)[:, clean_cols], plain[:, clean_cols])


def test_decode_once_matches_reference_across_m_grids():
    """Same output and flags for 1, 2, and 9 M tiles (scratch-reuse
    regression extending the PR 4 M-grid independence test)."""
    rng = np.random.default_rng(5)
    m, k, n = 72, 96, 64
    wq = _wot_weights(rng, (k, n))
    wenc = jnp.asarray(_enc(wq))
    a = jnp.asarray(rng.integers(-127, 128, size=(m, k)).astype(np.int8))
    plain = np.asarray(a).astype(np.int32) @ wq.astype(np.int32)
    ref_flags = None
    for bm in (128, 64, 8):
        out, flags = ecc_qmatmul(a, wenc, bm=bm, bn=32, bk=32,
                                 with_flags=True)
        assert np.array_equal(np.asarray(out), plain)
        if ref_flags is None:
            ref_flags = np.asarray(flags)
        assert np.array_equal(np.asarray(flags), ref_flags)


# ---------------------------------------------------------------------------
# ProtectedWeight: fused routes are bit-identical to inline decode
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("m,k,n", [
    (5, 64, 136),      # ragged second N tile
    (45, 200, 72),     # ragged M, K and N inside one tile each
    (141, 104, 264),   # two M tiles and three N tiles, both ragged
])
def test_protected_weight_float_fused_equals_inline(m, k, n):
    """Float activations: the fused Pallas route at the kernel's default
    tiles equals decode-then-matmul bit for bit — the XLA route's decoded
    weights under one f32-accumulated dot, rounded once. (XLA's CPU bf16
    dot sums in a batch-size-dependent order from 128 rows on, so its own
    ``x @ w`` is not a fixed reference there.)"""
    from repro.protection.fused import ProtectedWeight
    rng = np.random.default_rng(5)
    w = jnp.asarray(_wot_weights(rng, (k, n)).astype(np.float32) * 0.01)
    pt = protection.ProtectionPolicy().encode_leaf(w, "in-place")
    x = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    fused = ProtectedWeight(pt, "pallas")
    assert fused.fuse
    out = fused.matmul(x)
    w_dec = ProtectedWeight(pt, "xla").astype(x.dtype)
    want = jnp.dot(x, w_dec, preferred_element_type=jnp.float32
                   ).astype(x.dtype)
    assert out.shape == (m, n) and out.dtype == x.dtype
    assert np.array_equal(np.asarray(out, np.float32),
                          np.asarray(want, np.float32))


@pytest.mark.parametrize("mode,lead,k,n", [
    ("dynamic", (3, 5), 64, 128),
    ("static", (3, 5), 64, 128),
    # M 141: two M tiles and three N tiles, both ragged
    ("dynamic", (3, 47), 200, 264),
    ("static", (3, 47), 200, 264),
], ids=["dynamic", "static", "dynamic-ragged", "static-ragged"])
def test_protected_weight_int8_fused_equals_inline(mode, lead, k, n):
    from repro.protection.fused import ProtectedWeight
    rng = np.random.default_rng(6)
    w = jnp.asarray(_wot_weights(rng, (k, n)).astype(np.float32) * 0.01)
    policy = protection.ProtectionPolicy()
    pt = policy.encode_leaf(w, "in-place")
    x = jnp.asarray(rng.normal(size=(*lead, k)).astype(np.float32)
                    ).astype(jnp.bfloat16)
    kw = dict(act_quant=mode, a_scale=0.02 if mode == "static" else None)
    out_fused = ProtectedWeight(pt, "pallas", **kw).matmul(x)
    out_inline = ProtectedWeight(pt, "xla", **kw).matmul(x)
    assert out_fused.shape == (*lead, n)
    assert np.array_equal(np.asarray(out_fused, np.float32),
                          np.asarray(out_inline, np.float32))
    # and both equal the explicit quantize->decode->matmul reference
    x2 = x.reshape(-1, k).astype(jnp.float32)
    if mode == "static":
        a_scale = jnp.float32(0.02)
    else:
        a_scale = quant.compute_scale(x2, axis=1)
    q = jnp.clip(jnp.round(x2 / a_scale), -127, 127).astype(jnp.int8)
    acc = ref.ecc_qmatmul_ref(q, pt.enc)
    want = (acc.astype(jnp.float32) * (a_scale * pt.scale)
            ).astype(jnp.bfloat16).reshape(*lead, n)
    assert np.array_equal(np.asarray(out_fused, np.float32),
                          np.asarray(want, np.float32))


def test_protected_weight_raw_int8_needs_static_scale():
    from repro.protection.fused import ProtectedWeight
    rng = np.random.default_rng(7)
    w = jnp.asarray(_wot_weights(rng, (32, 32)).astype(np.float32) * 0.01)
    pt = protection.ProtectionPolicy().encode_leaf(w, "in-place")
    q = jnp.ones((2, 32), jnp.int8)
    with pytest.raises(TypeError, match="static a_scale"):
        ProtectedWeight(pt, "pallas").matmul(q)
    out = ProtectedWeight(pt, "pallas", act_quant="static",
                          a_scale=0.05).matmul(q)
    assert out.dtype == jnp.bfloat16 and out.shape == (2, 32)


def test_proj_bias_not_truncated_on_int8_activations():
    """layers._proj must add the bias at the OUTPUT dtype: raw int8
    activations through a biased projection produce float y, and the bias
    (here 500.0, unrepresentable in int8) must survive."""
    from repro.models.layers import _proj
    from repro.protection.fused import ProtectedWeight
    rng = np.random.default_rng(8)
    w = jnp.asarray(_wot_weights(rng, (32, 32)).astype(np.float32) * 0.01)
    pt = protection.ProtectionPolicy().encode_leaf(w, "in-place")
    view = ProtectedWeight(pt, "pallas", act_quant="static", a_scale=0.05)
    q = jnp.ones((2, 32), jnp.int8)
    b = jnp.full((32,), 500.0, jnp.float32)
    y = _proj(q, view, b)
    assert np.allclose(np.asarray(y - view.matmul(q), np.float32), 500.0,
                       atol=2.0)  # bf16 rounding, not int8 wraparound


def test_calibration_floors_zero_activation_scale():
    """A projection whose calibration activations are all zero must not
    bake a_scale=0 (divide-by-zero at serve time) — same 1e-12 floor as
    quant.compute_scale."""
    cfg = configs.get_smoke("minitron-4b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    # zero the embedding: every hidden state (and thus every projection
    # input) in the calibration forward is exactly zero
    params["embed"] = jnp.zeros_like(params["embed"])
    plan = protected.make_plan(params, protection.ProtectionPolicy())
    enc = plan.encode_tree(params)
    toks = jnp.zeros((2, 16), jnp.int32)
    scales = protected.calibrate_act_scales(cfg, enc, toks, plan=plan,
                                            chunk=16)
    assert scales and all(s > 0 for s in scales.values())
    plan_q = plan.with_act_quant("static", scales)
    step = jax.jit(protected.make_serve_step(cfg, plan=plan_q,
                                             act_quant="plan"))
    cache = lm.init_cache(cfg, 2, 32)
    logits, _ = step(enc, cache, jnp.zeros((2, 1), jnp.int32),
                     jnp.zeros((2,), jnp.int32))
    assert bool(jnp.isfinite(logits.astype(jnp.float32)).all())


# ---------------------------------------------------------------------------
# end-to-end: int8 at-use serving, calibration, plan decisions
# ---------------------------------------------------------------------------


def test_int8_at_use_serving_bit_exact_on_both_backends(plan_setup):
    """The acceptance: the fused int8 MXU path (Pallas epilogue) serves
    end-to-end and its logits equal the XLA quantize->decode->matmul
    reference route bit for bit — decode step AND prefill."""
    outs = {}
    for backend in ("xla", "pallas"):
        cfg, plan, enc = plan_setup(backend=backend)
        cache = lm.init_cache(cfg, 2, 32)
        tok = jnp.zeros((2, 1), jnp.int32)
        pos = jnp.zeros((2,), jnp.int32)
        step = jax.jit(protected.make_serve_step(cfg, plan=plan,
                                                 act_quant="dynamic"))
        logits, _ = step(enc, cache, tok, pos)
        pre = jax.jit(protected.make_prefill(cfg, plan=plan, chunk=16,
                                             act_quant="dynamic"))
        toks = jnp.zeros((2, 16), jnp.int32)
        outs[backend] = (np.asarray(logits, np.float32),
                         np.asarray(pre(enc, toks, {}), np.float32))
    assert np.array_equal(outs["xla"][0], outs["pallas"][0])
    assert np.array_equal(outs["xla"][1], outs["pallas"][1])


def test_calibrate_then_static_serving(plan_setup):
    """calibrate_act_scales -> plan.with_act_quant('static') -> act_quant
    'plan' serves the calibrated set; static logits match across backends
    and the plan summary reports the decisions."""
    toks = jnp.zeros((2, 16), jnp.int32)
    outs, n_static = {}, None
    for backend in ("xla", "pallas"):
        cfg, plan, enc = plan_setup(backend=backend)
        scales = protected.calibrate_act_scales(cfg, enc, toks, plan=plan,
                                                chunk=16)
        assert scales and all(s > 0 for s in scales.values())
        assert "layers/attn/wq" in scales and "head" in scales
        plan_q = plan.with_act_quant("static", scales)
        s = plan_q.summary()
        assert s["act_quant"].get("static") == len(scales)
        if n_static is None:
            n_static = s["act_quant"]["static"]
        assert s["act_quant"]["static"] == n_static  # same set per backend
        cache = lm.init_cache(cfg, 2, 32)
        step = jax.jit(protected.make_serve_step(cfg, plan=plan_q,
                                                 act_quant="plan"))
        logits, _ = step(enc, cache, jnp.zeros((2, 1), jnp.int32),
                         jnp.zeros((2,), jnp.int32))
        outs[backend] = np.asarray(logits, np.float32)
    assert np.array_equal(outs["xla"], outs["pallas"])


def test_with_act_quant_modes_and_guards(plan_setup):
    cfg, plan, _ = plan_setup()
    dyn = plan.with_act_quant("dynamic")
    assert dyn.summary()["act_quant"].get("dynamic", 0) > 0
    # original plan untouched
    assert not plan.summary()["act_quant"]
    with pytest.raises(ValueError, match="calibrated"):
        plan.with_act_quant("static")
    with pytest.raises(ValueError, match="mode"):
        plan.with_act_quant("sometimes")
    with pytest.raises(ValueError, match="decode-at-use"):
        protected.make_serve_step(cfg, plan=plan, decode_at_use=False,
                                  act_quant="dynamic")
    with pytest.raises(ValueError, match="decode-at-use"):
        protected.make_prefill(cfg, plan=plan, decode_at_use=False,
                               act_quant="dynamic")


def test_int8_serving_flags_still_attribute_faults(plan_setup):
    """The epilogue path keeps the per-layer (corrected, DUE) accounting: a
    double-bit fault in layer 0's wq surfaces in layer 0's DUE row when
    serving int8."""
    cfg, plan, enc = plan_setup(arch="deepseek-7b")
    wq = enc["layers"]["attn"]["wq"]
    img = np.asarray(wq.enc).copy()
    img.reshape(-1)[3] ^= 0x03
    enc["layers"]["attn"]["wq"] = dataclasses.replace(
        wq, enc=jnp.asarray(img))
    serve = jax.jit(protected.make_serve_step(cfg, plan=plan,
                                              act_quant="dynamic",
                                              with_flags=True))
    cache = lm.init_cache(cfg, 2, 32)
    _, _, flags = serve(enc, cache, jnp.zeros((2, 1), jnp.int32),
                        jnp.zeros((2,), jnp.int32))
    layers = np.asarray(flags["layers"])
    assert layers[0, 1] >= 1
    assert layers[1:, 1].sum() == 0


def test_int8_conv_arch_prefill_runs():
    """ssm arch: conv kernels keep decoding to arrays, matmul projections
    quantize — the int8 prefill must still run end-to-end."""
    cfg = configs.get_smoke("mamba2-2.7b")
    params = lm.init_params(cfg, jax.random.PRNGKey(2))
    plan = protected.make_plan(params,
                               protection.ProtectionPolicy(backend="pallas"))
    enc = plan.encode_tree(params)
    toks = jnp.zeros((2, 16), jnp.int32)
    pre = jax.jit(protected.make_prefill(cfg, plan=plan, chunk=16,
                                         act_quant="dynamic"))
    out = pre(enc, toks, {})
    assert bool(jnp.isfinite(out.astype(jnp.float32)).all())
