"""Protected-serving driver: batched decode with ECC-encoded weights.

Runs the full serving path: build a ``ProtectionPolicy`` (scheme + backend
selectable), encode random weights from ``--seed`` at the architecture's
published widths (``--smoke`` for the reduced same-family config, the CPU
size), report coverage, inject memory faults at a chosen rate, and
decode-serve batched requests — faults are corrected on the fly. The
encoded tree is built by one compiled init->encode program, so the float
weights never exist whole on the device.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-4b \
      --backend pallas --kv-policy in-place-fused          # one TPU chip
  PYTHONPATH=src python -m repro.launch.serve --arch deepseek-7b --smoke \
      --fault-rate 1e-4 --tokens 32 [--scheme in-place] [--backend xla] \
      [--policy attn-inplace-mlp-secded] [--abft] [--act-clamp]

``--abft`` turns on in-kernel ABFT checksum verification for every
protected matmul (compute-fault detection next to the memory-fault ECC
flags; see docs/abft.md); ``--act-clamp`` calibrates per-leaf activation
absmax bounds from a seeded batch and fuses the Geissler-style range
clamps into the same epilogue. Both report through the ``*_abft`` flags
channel after the run.

``--policy`` serves under a named mixed-scheme preset: the materialized
``ProtectionPlan`` decides scheme and backend per leaf, and the serve step
decodes each leaf accordingly — one model, many schemes, many backends.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro import configs, protection
from repro.launch.compile_cache import setup_compile_cache
from repro.models import lm
from repro.serving import kvcache, protected


def inject_tree(enc_params, rate: float, seed: int):
    """Flip random bits in every encoded weight image (memory fault model).

    Kept as the serving-facing name; delegates to the on-device
    :func:`repro.protection.inject_tree_device` (jit-safe, no host
    round-trip per leaf).  Injection builds a transient per-bit parity
    vector per leaf (8x its stored bytes), sized for smoke/eval-scale
    weights — production-scale leaves should shard the image first.
    """
    return protection.inject_tree_device(enc_params, rate,
                                         jax.random.PRNGKey(seed))


def fault_smoke_check(enc, policy, rate: float, seed: int, *,
                      trials: int = 2, campaign_key: int | None = None,
                      out_path: str | None = None):
    """Compiled campaign smoke-check before serving with injected faults:
    sweep {rate/10, rate, 10*rate} x ``trials`` in one device program and
    report the decode fidelity (fraction of protected weights that still
    decode to their clean values) AND the DUE (detected-uncorrectable)
    count at each rate.  ``batch="scan"`` keeps peak memory at one cell's
    buffers — serving trees are the big-model case of the vmap-vs-scan
    guidance in docs/campaigns.md.

    ``campaign_key`` seeds the campaigns' own key stream (default: derive
    from ``seed``); ``out_path`` writes the full JSON record — trials,
    key, per-rate fidelity and DUE means — next to the printed digest."""
    rates = tuple(sorted({rate / 10, rate, min(rate * 10, 0.01)}))
    ckey = seed + 1 if campaign_key is None else campaign_key
    res = protection.fidelity_campaign(enc, policy, rates=rates,
                                       trials=trials,
                                       key=jax.random.PRNGKey(ckey),
                                       batch="scan")
    cells = "  ".join(f"{r:.0e}:{m * 100:6.2f}%"
                      for r, m in zip(res.rates, res.mean()))
    print(f"[serve] fault smoke-check ({res.scheme}, {res.batch} campaign, "
          f"{trials} trials, compile {res.compile_s:.1f}s, sweep "
          f"{res.wall_clock_s:.2f}s): decode fidelity {cells}")
    due = protection.due_campaign(enc, policy, rates=rates, trials=trials,
                                  key=jax.random.PRNGKey(ckey + 1),
                                  batch="scan")
    cells = "  ".join(f"{r:.0e}:{m:7.1f}"
                      for r, m in zip(due.rates, due.mean()))
    print(f"[serve] DUE (double-error) counts per rate: {cells}")
    if out_path:
        import json
        rec = {"trials": trials, "campaign_key": ckey,
               "rates": list(res.rates), "scheme": res.scheme,
               "batch": res.batch,
               "fidelity_mean": [float(m) for m in res.mean()],
               "due_mean": [float(m) for m in due.mean()]}
        with open(out_path, "w") as fh:
            json.dump(rec, fh, indent=2)
            fh.write("\n")
        print(f"[serve] wrote campaign record to {out_path}")
    return res


def run_burst_mode(cfg, enc, plan, args, repair_kit=None):
    """``--burst``: replay a seeded wave workload through the
    request-level front-end (see :mod:`repro.serving.frontend` and
    docs/serving.md) and print the telemetry roll-up."""
    import os

    from repro.serving import frontend, telemetry

    kvp = args.kv_policy or "in-place"
    waves = frontend.make_waves(seed=args.seed, n_waves=2,
                                wave_size=args.batch, vocab=cfg.vocab,
                                prompt_len=(4, 8),
                                max_new=(4, args.tokens),
                                gap_steps=6)
    tpath = None
    if args.burst_out:
        os.makedirs(args.burst_out, exist_ok=True)
        tpath = os.path.join(args.burst_out, "telemetry.jsonl")
    events, summ, _ = frontend.run_burst(
        cfg, enc, plan=plan, waves=waves, slots=max(2, args.batch // 2),
        max_len=max(32, args.tokens * 2), kv_policy=kvp,
        fault_rate=args.fault_rate, fault_seed=args.seed,
        telemetry_path=tpath, scrub_every=args.scrub_every,
        repair=args.repair, repair_kit=repair_kit)
    r, t, d, p = (summ["requests"], summ["throughput"], summ["due"],
                  summ["pool"])
    print(f"[serve] burst ({kvp} KV): {r['finished']}/{r['submitted']} "
          f"requests in {summ['steps']} steps "
          f"({t['tokens_per_step']:.2f} tok/step)")
    print(f"[serve] TTFT p50/p95/p99: {summ['ttft_steps']['p50']}/"
          f"{summ['ttft_steps']['p95']}/{summ['ttft_steps']['p99']} steps; "
          f"per-token p99 {summ['per_token_ms']['p99']:.2f}ms")
    print(f"[serve] KV faults: {d['corrected_total']} corrected, "
          f"{d['total']} DUE ({d['requests_with_due']} requests); "
          f"pages leaked {p['leaked_pages']}")
    heal = summ["healing"]
    if heal["scrub_passes"]:
        fd = heal["final_due"]
        tail = (f", final at-rest DUE {fd['w']}w/{fd['kv']}kv"
                if fd else "")
        print(f"[serve] self-healing: {heal['scrub_passes']} scrub passes, "
              f"corrected w={heal['w_corrected']} kv={heal['kv_corrected']}"
              f", repairs {heal['repairs'] or '{}'}{tail}")
    if args.burst_out:
        telemetry.write_requests_csv(
            events, os.path.join(args.burst_out, "requests.csv"))
        telemetry.write_summary(summ,
                                os.path.join(args.burst_out,
                                             "summary.json"))
        print(f"[serve] wrote {args.burst_out}/telemetry.jsonl, "
              f"requests.csv, summary.json")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--smoke", action="store_true",
                    help="serve the reduced same-family config (CPU size) "
                         "instead of the published widths")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--fault-rate", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--scheme", default="in-place",
                    choices=sorted(set(protection.scheme_ids()) |
                                   set(protection.ALIASES)))
    ap.add_argument("--backend", default="xla",
                    choices=sorted(protection.BACKENDS))
    ap.add_argument("--policy", default=None,
                    choices=sorted(protection.POLICY_PRESETS),
                    help="serve under a named mixed-scheme preset "
                         "(overrides --scheme)")
    ap.add_argument("--kv-policy", default=None,
                    choices=sorted(kvcache.KV_POLICY_PRESETS),
                    help="serve against the paged protected KV cache under "
                         "this preset; with --fault-rate, faults are also "
                         "injected into the LIVE cache pools mid-run")
    ap.add_argument("--burst", action="store_true",
                    help="serve a seeded burst workload through the "
                         "request-level front-end (continuous batching, "
                         "admission control, telemetry summary) instead of "
                         "the fixed-batch loop; uses --kv-policy (default "
                         "in-place), --fault-rate as the live-KV injection "
                         "rate, and --seed for the workload")
    ap.add_argument("--burst-out", default=None, metavar="DIR",
                    help="with --burst: write telemetry JSONL + "
                         "requests CSV + summary JSON here")
    ap.add_argument("--trials", type=int, default=2,
                    help="trials per rate for the fault smoke-check "
                         "campaigns (fidelity + DUE)")
    ap.add_argument("--campaign-key", type=int, default=None,
                    help="explicit base key for the smoke-check campaign "
                         "streams (default: seed + 1)")
    ap.add_argument("--campaign-out", default=None, metavar="FILE",
                    help="write the smoke-check campaign record "
                         "(trials, key, per-rate means) as JSON")
    ap.add_argument("--scrub-every", type=int, default=0,
                    help="self-healing: scrub weights (and, in --burst "
                         "mode, live KV pages) every N steps")
    ap.add_argument("--repair", action="store_true",
                    help="pin a MILR repair kit from the clean tree and "
                         "repair/quarantine scrub-detected weight DUEs")
    ap.add_argument("--abft", action="store_true",
                    help="verify ABFT checksums inside every protected "
                         "matmul (row/col sums vs the accumulator, same "
                         "kernel pass); mismatches surface on the "
                         "*_abft flags channel")
    ap.add_argument("--act-clamp", action="store_true",
                    help="calibrate per-leaf activation absmax bounds from "
                         "a seeded batch and fuse the range clamps into "
                         "the matmul epilogue; clamp hits ride the *_abft "
                         "flags channel")
    args = ap.parse_args()

    setup_compile_cache()
    cfg = (configs.get_smoke if args.smoke else configs.get)(args.arch)
    label = f"policy={args.policy}" if args.policy else f"scheme={args.scheme}"
    print(f"[serve] {cfg.name} {'smoke' if args.smoke else 'published'} "
          f"config, {label}, backend={args.backend}, "
          f"fault_rate={args.fault_rate}")
    params = lm.param_specs(cfg, jnp.bfloat16)
    if args.policy:
        policy = protection.get_policy_preset(args.policy,
                                              backend=args.backend)
    else:
        policy = protection.ProtectionPolicy(default_scheme=args.scheme,
                                             backend=args.backend)
    plan = policy.plan(params)
    s = plan.summary()
    print("[serve] " +
          plan.coverage().summary().replace("\n", "\n[serve] "))
    schemes = ", ".join(f"{k}={v['stored_bytes']}B"
                        for k, v in sorted(s["by_scheme"].items()))
    print(f"[serve] plan: schemes {{{schemes}}}, backends {s['by_backend']}, "
          f"{s['n_flat_padded']} flat-padded leaves")
    enc = protected.init_encoded(cfg, plan, jax.random.PRNGKey(args.seed))
    if args.abft or args.act_clamp:
        clamps = None
        if args.act_clamp:
            from repro.core import quant
            cal = jax.random.randint(jax.random.PRNGKey(args.seed + 7),
                                     (2, 16), 0, cfg.vocab, jnp.int32)
            scales = protected.calibrate_act_scales(cfg, enc, cal, plan=plan,
                                                    backend=args.backend)
            clamps = {p: s * quant.QMAX for p, s in scales.items()}
        # use-time knobs only — the encoded images above stay valid
        plan = plan.with_abft(args.abft, clamps=clamps)
        s = plan.summary()
        print(f"[serve] ABFT guard: {s['n_abft']} checksum-verified leaves, "
              f"{s['n_clamped']} activation-clamped")
    kit = None
    if args.repair:
        from repro.protection import repair as repair_mod
        kit = repair_mod.build_repair_kit(enc, seed=args.seed)
        print(f"[serve] pinned MILR repair kit over {len(kit)} leaves")
    if args.fault_rate:
        fault_smoke_check(enc, policy, args.fault_rate, args.seed,
                          trials=args.trials,
                          campaign_key=args.campaign_key,
                          out_path=args.campaign_out)
        enc = inject_tree(enc, args.fault_rate, args.seed)
        print("[serve] injected faults into the resident weight images")

    if args.burst:
        run_burst_mode(cfg, enc, plan, args, repair_kit=kit)
        return

    kvp = kvcache.get_kv_policy(args.kv_policy)
    serve_step = jax.jit(protected.make_serve_step(cfg, plan=plan,
                                                   with_flags=True,
                                                   kv_policy=kvp))
    max_len = max(64, args.tokens * 2)
    cache = kvcache.init_cache(cfg, args.batch, max_len, kv_policy=kvp)
    if kvp is not None:
        kb = kvcache.kv_bytes(cache)
        dense = kvcache.dense_kv_bytes(cfg, args.batch, max_len)
        print(f"[serve] paged KV cache ({kvp.scheme}, page_size="
              f"{kvp.page_size}): stored {kb['stored']}B + checks "
              f"{kb['checks']}B + scales {kb['scales']}B (dense bf16 cache: "
              f"{dense}B)")
    tokens = jnp.zeros((args.batch, 1), jnp.int32)
    scrubber_obj = None
    scrub_tot = {"corrected": 0, "repaired": 0, "quarantined": 0}
    if args.scrub_every:
        from repro.serving.scrubber import Scrubber
        scrubber_obj = Scrubber(leaves_per_step=2)
    t0 = time.time()
    out, step_flags = [], []
    for t in range(args.tokens):
        if scrubber_obj is not None and t % args.scrub_every == 0:
            enc, wst = scrubber_obj.scrub_weights(enc)
            scrub_tot["corrected"] += wst["corrected"]
            if wst["due_paths"] and kit is not None:
                from repro.protection import repair as repair_mod
                enc, reps = repair_mod.repair_tree(enc, kit,
                                                   paths=wst["due_paths"])
                for r in reps:
                    key = ("repaired" if r["status"] == "repaired"
                           else "quarantined")
                    scrub_tot[key] += 1
        if (kvp is not None and args.fault_rate and t == args.tokens // 2
                and t > 0):
            # the serving-state fault story: hit the LIVE pools mid-run, so
            # every later step decodes (and corrects) a faulted history
            tree = kvcache.as_protected_tree(cache, kvp)
            dirty = protection.inject_tree_device(
                tree, args.fault_rate, jax.random.PRNGKey(args.seed + 3))
            cache = kvcache.from_protected_tree(cache, dirty)
            print(f"[serve] injected faults into the live KV pools at "
                  f"step {t}")
        pos = jnp.full((args.batch,), t, jnp.int32)
        logits, cache, flags = serve_step(enc, cache, tokens, pos)
        tokens = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out.append(int(tokens[0, 0]))
        step_flags.append(flags)  # device arrays; summed after the timer
    dt = time.time() - t0
    corrected = due = kv_corrected = kv_due = 0
    abft_mm = clamp_hits = 0
    for flags in step_flags:
        for k, v in flags.items():
            pair = jnp.sum(jnp.asarray(v).reshape(-1, 2), axis=0)
            if k.endswith("_abft"):  # (mismatches, clamp hits), not ECC
                abft_mm += int(pair[0])
                clamp_hits += int(pair[1])
            elif k == "layers_kv":
                kv_corrected += int(pair[0])
                kv_due += int(pair[1])
            else:
                corrected += int(pair[0])
                due += int(pair[1])
    print(f"[serve] {args.tokens} steps x batch {args.batch} in {dt:.2f}s "
          f"({args.tokens * args.batch / dt:.1f} tok/s)")
    print(f"[serve] decode-at-use fault accounting over the run: "
          f"{corrected} corrected, {due} DUE (detected-uncorrectable)")
    if kvp is not None:
        print(f"[serve] KV decode-at-use accounting: {kv_corrected} "
              f"corrected, {kv_due} DUE")
    if args.abft or args.act_clamp:
        print(f"[serve] ABFT compute-fault accounting: {abft_mm} checksum "
              f"mismatches, {clamp_hits} activation clamp hits")
    if scrubber_obj is not None:
        from repro.serving.scrubber import scrub_tree
        enc, fin = scrub_tree(enc)
        scrub_tot["corrected"] += fin["corrected"]
        residual = fin["due_paths"]
        if residual and kit is not None:
            from repro.protection import repair as repair_mod
            enc, reps = repair_mod.repair_tree(enc, kit, paths=residual)
            for r in reps:
                key = ("repaired" if r["status"] == "repaired"
                       else "quarantined")
                scrub_tot[key] += 1
            enc, fin = scrub_tree(enc)
            residual = fin["due_paths"]
        print(f"[serve] self-healing: wrote back "
              f"{scrub_tot['corrected']} corrected bits during the run, "
              f"{scrub_tot['repaired']} leaves repaired, "
              f"{scrub_tot['quarantined']} quarantined; residual DUE "
              f"leaves after the final pass: {len(residual)}")
    print(f"[serve] sample continuation: {out}")


if __name__ == "__main__":
    main()
