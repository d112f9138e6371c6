"""``repro.protection.plan`` — materialized per-leaf protection decisions:
summary-vs-CoverageReport byte agreement, mixed scheme+backend trees,
backend resolution order (first rule > policy default), preset policies, and
the plan-driven serving step."""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs, protection
from repro.models import lm
from repro.serving import protected


def wot_params(rng, shape=(16, 64)):
    """fp32 weights that quantize exactly back to a WOT-compliant q."""
    q = rng.integers(-64, 64, size=int(np.prod(shape))).astype(np.int8)
    q.reshape(-1)[7::8] = rng.integers(-127, 128, size=q[7::8].size)
    q.reshape(-1)[7] = 127
    return jnp.asarray(q.reshape(shape).astype(np.float32) * 0.01)


PRED = lambda p, l: getattr(l, "ndim", 0) >= 2


# ---------------------------------------------------------------------------
# materialization + accounting
# ---------------------------------------------------------------------------


def test_plan_summary_matches_coverage_report_byte_for_byte():
    """The acceptance contract: plan.summary() and CoverageReport agree on
    every byte count, on a real arch tree with mixed schemes."""
    cfg = configs.get_smoke("minitron-4b")
    abstract = jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    policy = protection.get_policy_preset("attn-inplace-mlp-secded")
    plan = policy.plan(abstract)
    rep = policy.coverage(abstract)
    s = plan.summary()
    assert s["protected_bytes"] == rep.protected_bytes
    assert s["unprotected_bytes"] == rep.unprotected_bytes
    assert s["pad_bytes"] == rep.pad_bytes
    assert s["n_protected"] == rep.n_protected
    assert s["n_unprotected"] == rep.n_unprotected
    assert {k: v["n_tensors"] for k, v in s["by_scheme"].items()} == \
        rep.by_scheme()
    # the preset actually mixes schemes on an LM tree
    assert set(s["by_scheme"]) == {"in-place", "secded72"}
    # per-scheme stored bytes partition the total
    assert sum(v["stored_bytes"] for v in s["by_scheme"].values()) == \
        s["protected_bytes"]
    # secded72 leaves store 12.5% checks; in-place stores zero extra
    sd = s["by_scheme"]["secded72"]
    ip = s["by_scheme"]["in-place"]
    assert sd["check_bytes"] == (sd["weight_bytes"] + sd["pad_bytes"]) // 8
    assert ip["stored_bytes"] == ip["weight_bytes"] + ip["pad_bytes"]


def test_plan_is_coverage_report_source():
    """CoverageReport is a thin view: plan.coverage() entries equal the
    policy's report exactly (order, reasons, bytes)."""
    rng = np.random.default_rng(0)
    params = {"wq": wot_params(rng), "odd": wot_params(rng, (6, 13)),
              "norm": jnp.ones((64,), jnp.float32)}
    policy = protection.ProtectionPolicy(predicate=PRED)
    assert policy.plan(params).coverage().entries == \
        policy.coverage(params).entries


def test_plan_encode_decode_mixed_schemes_and_backends():
    rng = np.random.default_rng(1)
    params = {"attn": {"wq": wot_params(rng)},
              "mlp": {"w_up": wot_params(rng)},
              "odd": wot_params(rng, (32, 18))}
    policy = protection.ProtectionPolicy(
        rules=[("mlp/", "secded72")],
        backend_rules=[("attn/", "pallas")], predicate=PRED)
    plan = policy.plan(params)
    assert plan["attn/wq"].scheme_id == "in-place"
    assert plan["attn/wq"].backend == "pallas"
    assert plan["attn/wq"].backend_src == "rule"
    assert plan["mlp/w_up"].scheme_id == "secded72"
    assert plan["mlp/w_up"].backend == "xla"
    assert plan["mlp/w_up"].backend_src == "policy"
    assert plan["odd"].layout == "flat-padded"
    assert plan["odd"].enc_shape == (576,)

    enc = plan.encode_tree(params)
    assert enc["attn"]["wq"].scheme_id == "in-place"
    assert enc["mlp"]["w_up"].checks is not None
    dec = plan.decode_tree(enc, jnp.float32)
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(dec)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    # the plan path is what policy.encode_tree/decode_tree now run
    dec2 = policy.decode_tree(policy.encode_tree(params), jnp.float32)
    for a, b in zip(jax.tree.leaves(dec), jax.tree.leaves(dec2)):
        assert np.array_equal(np.asarray(a), np.asarray(b))


def test_plan_rejects_mismatched_tree():
    rng = np.random.default_rng(2)
    plan = protection.ProtectionPolicy(predicate=PRED).plan(
        {"w": wot_params(rng)})
    with pytest.raises(KeyError, match="not in this ProtectionPlan"):
        plan.encode_tree({"other": wot_params(rng)})


# ---------------------------------------------------------------------------
# backend resolution: first matching rule > policy default
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("default,rules,path,expect", [
    ("pallas", [("special", "xla")], "special/w", ("xla", "rule")),
    ("xla", [("blk", "pallas"), ("/w$", "xla")], "blk/w",
     ("pallas", "rule")),                               # first rule wins
    ("xla", [("special", "pallas")], "blk/w", ("xla", "policy")),
    ("pallas", [("special", "xla")], "blk/w", ("pallas", "policy")),
], ids=["rule-hit", "first-rule-wins", "xla-default", "pallas-default"])
def test_backend_resolution_order(default, rules, path, expect):
    policy = protection.ProtectionPolicy(
        backend=default, backend_rules=rules, predicate=PRED)
    be, src = policy.resolve_backend(path)
    assert (be.name, src) == expect


# ---------------------------------------------------------------------------
# presets
# ---------------------------------------------------------------------------


def test_policy_presets_materialize_on_lm_tree():
    cfg = configs.get_smoke("qwen1.5-4b")
    abstract = jax.eval_shape(
        lambda: lm.init_params(cfg, jax.random.PRNGKey(0), jnp.float32))
    seen = {}
    for name in protection.POLICY_PRESETS:
        plan = protection.get_policy_preset(name).plan(abstract)
        seen[name] = plan.summary()
    assert set(seen["all-in-place"]["by_scheme"]) == {"in-place"}
    assert set(seen["all-secded72"]["by_scheme"]) == {"secded72"}
    assert set(seen["unprotected"]["by_scheme"]) == {"faulty"}
    assert set(seen["attn-inplace-mlp-secded"]["by_scheme"]) == \
        {"in-place", "secded72"}
    # zero-space story: in-place and faulty store the same bytes,
    # secded72 stores 12.5% more
    ip, un = seen["all-in-place"], seen["unprotected"]
    sd = seen["all-secded72"]
    assert ip["protected_bytes"] == un["protected_bytes"]
    assert sd["protected_bytes"] > ip["protected_bytes"]
    with pytest.raises(ValueError, match="unknown policy preset"):
        protection.get_policy_preset("everything-bagel")


# ---------------------------------------------------------------------------
# plan-driven serving (the acceptance end-to-end)
# ---------------------------------------------------------------------------


def test_serve_step_from_plan_mixed_scheme_mixed_backend():
    """One model tree, two schemes, two backends, one jitted serve step —
    logits match the homogeneous all-xla in-place serve bit-for-bit (all
    schemes round-trip the same throttled int8 weights at rate 0)."""
    cfg = configs.get_smoke("minitron-4b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0))
    policy = protection.get_policy_preset(
        "attn-inplace-mlp-secded",
        backend_rules=[(r"(^|/)(wq|wk|wv)($|/)", "pallas")])
    plan = protected.make_plan(params, policy)
    s = plan.summary()
    assert len(s["by_scheme"]) == 2 and len(s["by_backend"]) == 2
    assert s == protected.make_plan(params, policy).summary()  # deterministic
    # summary vs CoverageReport byte-for-byte (acceptance wording)
    rep = protection.coverage(params, policy)
    assert s["protected_bytes"] == rep.protected_bytes
    assert s["unprotected_bytes"] == rep.unprotected_bytes

    enc = plan.encode_tree(params)
    serve = jax.jit(protected.make_serve_step(cfg, plan=plan))
    cache = lm.init_cache(cfg, 2, 32)
    tok = jnp.zeros((2, 1), jnp.int32)
    pos = jnp.zeros((2,), jnp.int32)
    logits, _ = serve(enc, cache, tok, pos)
    assert np.isfinite(np.asarray(logits, np.float32)).all()

    ref_policy = protection.ProtectionPolicy()
    ref_enc = ref_policy.encode_tree(params)
    ref_serve = jax.jit(protected.make_serve_step(cfg))
    ref_logits, _ = ref_serve(ref_enc, cache, tok, pos)
    assert np.array_equal(np.asarray(logits, np.float32),
                          np.asarray(ref_logits, np.float32))


def test_prefill_from_plan():
    cfg = configs.get_smoke("qwen1.5-4b")
    params = lm.init_params(cfg, jax.random.PRNGKey(1))
    policy = protection.get_policy_preset("attn-inplace-mlp-secded")
    plan = protected.make_plan(params, policy)
    enc = plan.encode_tree(params)
    prefill = jax.jit(protected.make_prefill(cfg, plan=plan, chunk=16))
    logits = prefill(enc, jnp.zeros((2, 16), jnp.int32), {})
    assert np.isfinite(np.asarray(logits, np.float32)).all()


# ---------------------------------------------------------------------------
# import hygiene (satellite: dryrun must not clobber the environment)
# ---------------------------------------------------------------------------


def test_dryrun_import_is_env_clean():
    prog = ("import os; os.environ.pop('XLA_FLAGS', None); "
            "import repro.launch.dryrun as d; "
            "assert 'XLA_FLAGS' not in os.environ, os.environ['XLA_FLAGS']; "
            "d.setup_host_devices(8); "
            "assert 'device_count=8' in os.environ['XLA_FLAGS']; "
            "print('CLEAN')")
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, env=dict(os.environ, PYTHONPATH="src"),
                       cwd=os.path.dirname(os.path.dirname(__file__)))
    assert r.returncode == 0 and "CLEAN" in r.stdout, r.stderr[-2000:]
