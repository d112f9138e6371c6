"""Bring-up check on one TPU chip: Qwen1.5-4B at its published widths,
served through the fused Pallas path and checked against the XLA route.

  python chip_smoke.py [--seed 0]

One process holds the chip and runs, in order:

1. device — platform, kind and count; anything but a TPU exits non-zero
   before any result is printed;
2. kernels — the fused decode+matmul and the block codec against their
   XLA references on one real-width weight carrying injected single- and
   double-bit errors (the corrected/DUE counts must come out exact);
3. build — the protection plan from ``lm.param_specs`` and the encoded
   tree from one jitted init->encode program with a bf16 source, so the
   float weights never exist whole on the device;
4. serve — four requests (prompts of 8-32 tokens, 16 new tokens each)
   through the request front-end, weights through the fused Pallas
   kernel and the KV cache through ``in-place-fused`` paged attention;
5. reference — the same requests on the same encoded tree through the
   XLA route (XLA decode, decode-then-attend KV reference).

It fails unless every request finishes, every ECC flag is zero at fault
rate 0, and each request's first generated token is the same on both
routes. Earlier lines report compile and serve seconds, peak device
memory and the largest first-step logit gap between the routes; the last
line is one JSON object naming the device.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen1.5-4b"
REQUESTS, PROMPT_LEN, NEW_TOKENS = 4, (8, 32), 16


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def check_kernels(enc):
    """Fused matmul and block codec vs their XLA references on layer 0's
    ``w_gate`` with 3 single-bit and 2 double-bit errors injected."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import ecc
    from repro.kernels import ecc_decode, ecc_encode
    from repro.kernels.ecc_qmatmul import ecc_qmatmul

    pt = enc["layers"]["mlp"]["w_gate"]
    img = pt.enc[0]
    k, n = img.shape
    singles = [(0, 3, 0x01), (k // 2, 8 * 7 + 2, 0x10), (k - 1, n - 1, 0x80)]
    doubles = [(1, 16, 0x03), (k // 3, n // 2 + 5, 0x24)]
    for r, c, bits in singles + doubles:
        img = img.at[r, c].set(img[r, c] ^ jnp.uint8(bits))
    blocks = img.reshape(k, n // 8, 8)
    dec_x, single_x, double_x = ecc.decode64(blocks)
    dec_p, flags_p = ecc_decode.ecc_decode(blocks)
    assert int(single_x.sum()) == len(singles), int(single_x.sum())
    assert int(double_x.sum()) == len(doubles), int(double_x.sum())
    assert bool(jnp.array_equal(dec_p, dec_x)), "ecc_decode != decode64"
    flags_x = single_x.astype(jnp.uint8) | (double_x.astype(jnp.uint8) << 1)
    assert bool(jnp.array_equal(flags_p, flags_x)), "decode flags differ"
    assert bool(jnp.array_equal(ecc_encode.ecc_encode(dec_x),
                                ecc.encode64(dec_x))), "ecc_encode differs"

    x = jax.random.normal(jax.random.PRNGKey(1), (REQUESTS, k), jnp.bfloat16)
    out, flags = ecc_qmatmul(x, img, pt.scale, with_flags=True)
    w = (jax.lax.bitcast_convert_type(dec_x.reshape(k, n), jnp.int8)
         .astype(jnp.float32) * pt.scale).astype(jnp.bfloat16)
    ref = jax.lax.dot_general(x, w, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)
    gap = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    log(f"kernels: fused matmul flags {np.asarray(flags).tolist()} "
        f"(want [{len(singles)}, {len(doubles)}]), relative gap to XLA "
        f"{gap:.3e}; block codec bit-identical to XLA")
    assert np.asarray(flags).tolist() == [len(singles), len(doubles)]
    assert gap < 1e-3, gap


def serve_route(cfg, enc, plan, kv_preset, waves, *, max_len):
    """Compile one route's serve step (timed on its first call, which also
    returns the first-step logits), then run the requests through the
    front-end. -> (compile_s, serve_s, logits, results, flag totals)."""
    import jax
    import jax.numpy as jnp

    from repro.serving import frontend, kvcache, protected

    kvp = dataclasses.replace(kvcache.get_kv_policy(kv_preset),
                              per_slot_flags=True)
    step = jax.jit(protected.make_serve_step(cfg, plan=plan, with_flags=True,
                                             kv_policy=kvp))
    npg = kvcache.pages_per_seq(max_len, kvp.page_size)
    cache = kvcache.init_paged_cache(cfg, batch=REQUESTS, max_len=max_len,
                                     policy=kvp,
                                     n_pages=REQUESTS * (1 + npg))
    tokens = jnp.asarray([[r.prompt[0]] for r in waves], jnp.int32)
    pos = jnp.zeros((REQUESTS,), jnp.int32)
    t0 = time.perf_counter()
    logits, _, _ = jax.block_until_ready(step(enc, cache, tokens, pos))
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    events, summary, results = frontend.run_burst(
        cfg, enc, plan=plan, waves=waves, slots=REQUESTS, max_len=max_len,
        kv_policy=kvp, serve_step=step)
    serve_s = time.perf_counter() - t0
    steps = [e for e in events if e["event"] == "step"]
    flags = {key: sum(int(e[key]) for e in steps)
             for key in ("w_corrected", "w_due", "kv_corrected", "kv_due")}
    return compile_s, serve_s, logits, results, flags, summary


def run(cfg, seed: int = 0) -> None:
    """Phases 2-5 on ``cfg``; raises on any failed check."""
    import jax
    import jax.numpy as jnp

    from repro import protection
    from repro.models import lm
    from repro.serving import frontend, protected

    specs = lm.param_specs(cfg, jnp.bfloat16)
    plan_p = protection.ProtectionPolicy(backend="pallas").plan(specs)
    plan_x = protection.ProtectionPolicy(backend="xla").plan(specs)
    s = plan_p.summary()
    log(f"{cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads x {cfg.head_dim}, d_ff {cfg.d_ff}, vocab "
        f"{cfg.vocab}; {s['n_protected']} protected leaves, "
        f"{s['protected_bytes']} stored bytes")
    t0 = time.perf_counter()
    enc = jax.block_until_ready(
        protected.init_encoded(cfg, plan_p, jax.random.PRNGKey(seed)))
    log(f"build: init->encode compile+run {time.perf_counter() - t0:.2f} s")

    check_kernels(enc)

    waves = frontend.make_waves(seed=seed, n_waves=1, wave_size=REQUESTS,
                                vocab=cfg.vocab, prompt_len=PROMPT_LEN,
                                max_new=(NEW_TOKENS, NEW_TOKENS),
                                gap_steps=0)
    max_len = max(len(r.prompt) for r in waves) + NEW_TOKENS
    routes = {}
    for name, plan, kv in (("pallas", plan_p, "in-place-fused"),
                           ("xla", plan_x, "in-place")):
        compile_s, serve_s, logits, results, flags, summ = serve_route(
            cfg, enc, plan, kv, waves, max_len=max_len)
        routes[name] = (logits, results)
        log(f"serve[{name}]: compile+first step {compile_s:.2f} s, "
            f"{summ['requests']['finished']}/{REQUESTS} requests, "
            f"{summ['gen_tokens']} tokens in {summ['steps']} steps, "
            f"{serve_s:.2f} s; (corrected, DUE) weights "
            f"({flags['w_corrected']}, {flags['w_due']}) KV "
            f"({flags['kv_corrected']}, {flags['kv_due']})")
        done = [rid for rid, toks in results.items()
                if len(toks) == NEW_TOKENS]
        assert len(done) == REQUESTS, f"{name}: finished {sorted(done)}"
        assert not any(flags.values()), f"{name}: flags {flags}"
    stats = jax.devices()[0].memory_stats() or {}
    log(f"peak device memory: {stats.get('peak_bytes_in_use', 'n/a')} bytes")
    (lp, rp), (lx, rx) = routes["pallas"], routes["xla"]
    gap = float(jnp.max(jnp.abs(lp.astype(jnp.float32)
                                - lx.astype(jnp.float32))))
    scale = float(jnp.max(jnp.abs(lx.astype(jnp.float32))))
    log(f"first decode step: max |logit gap| pallas vs xla {gap:.4g} "
        f"(max |logit| {scale:.4g})")
    first = {rid: (rp[rid][0], rx[rid][0]) for rid in sorted(rp)}
    log(f"first generated tokens (pallas, xla): {first}")
    assert all(a == b for a, b in first.values()), "first tokens differ"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and the requests")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform {dev.platform}, kind {dev.device_kind}, "
        f"count {len(devices)}")
    if dev.platform != "tpu":
        log("no TPU found: this check runs only on the chip")
        return 1

    from repro import configs
    from repro.launch.compile_cache import setup_compile_cache
    log(f"compile cache: {setup_compile_cache()}")
    run(configs.get(ARCH), seed=args.seed)
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                             "kind": dev.device_kind,
                                             "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
