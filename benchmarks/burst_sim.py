"""Burst-load serving benchmark: seeded request waves through the
continuous-batching front-end, with an SLO comparison against the
unprotected-KV twin.

Replays a deterministic wave workload (``repro.serving.frontend.
make_waves``) through the request-level front-end under one or more KV
protection policies and fault rates, and emits:

* ``telemetry_<policy>_r<rate>.jsonl`` — the raw event stream
* ``requests_<policy>_r<rate>.csv``   — one row per request
* ``summary.json``                    — per-cell roll-ups (throughput,
  p50/p95/p99 TTFT + per-token latency, queue depth, DUE-per-request,
  page-pool accounting) plus an ``slo`` section comparing each protected
  cell's p99 per-token latency against the unprotected twin at the same
  fault rate.

  PYTHONPATH=src python benchmarks/burst_sim.py --smoke \
      --out-dir results/burst [--kv-policies unprotected,in-place] \
      [--fault-rates 0,1e-3] [--seed 0]

``--shared-prefix-len N`` prepends one common N-token prefix to every
prompt and serves with the front-end's prefix cache on — the summary's
``sharing`` section then reports pages shared, CoW copies, and pages
allocated vs what solo (no-sharing) admissions would have cost.

``--abft`` runs a checksum-guarded twin (``plan.with_abft()`` — in-kernel
ABFT over every protected matmul, see docs/abft.md) of every no-scrub
cell and prices it in the summary's ``abft_slo`` section: p99 per-token
ratio vs the unguarded twin, mismatch/clamp totals (zero here — the
burst injects MEMORY faults, which ECC absorbs before the MXU sees
them), and a token cross-check. The guarded cells' ``abft_mismatches`` /
``clamp_hits`` step fields carry no wall suffix, so they sit inside the
deterministic view and ABFT-enabled cells replay bit for bit.

``--smoke`` is the CI micro-run: 2 waves x 3 requests on the
deepseek-7b smoke config — small enough to compile and drain on a CPU
runner, large enough to exercise admission, queueing, eviction, and page
reuse. Determinism contract: for a fixed ``--seed`` the deterministic
view of every telemetry stream (wall-clock fields stripped) and every
token stream is bit-identical run-to-run; CI asserts the SLO envelope on
top (see .github/workflows/ci.yml).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro import configs, protection  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serving import frontend, kvcache, protected  # noqa: E402
from repro.serving import telemetry  # noqa: E402
from repro.launch.compile_cache import setup_compile_cache  # noqa: E402


def _cell_tag(policy: str, rate: float, scrub_every: int = 0,
              abft: bool = False) -> str:
    tag = f"{policy}_r{rate:g}"
    if scrub_every:
        tag = f"{tag}_scrub{scrub_every}"
    return f"{tag}_abft" if abft else tag


def run_grid(cfg, enc, plan, waves, *, kv_policies, fault_rates,
             slots, max_len, n_pages, seed, out_dir=None,
             prefix_sharing=False, scrub_every=0, repair=False,
             weight_fault_rate=0.0, abft_plan=None):
    """(policy x rate) grid over one workload; shares one jitted serve
    step per policy across its rate axis (and across twin comparisons) so
    wall-clock cells differ by faults, not compile noise.

    ``scrub_every > 0`` runs every (policy, rate) cell TWICE — a no-scrub
    baseline and a self-healing twin with the budgeted scrubber on (tag
    suffix ``_scrubN``) ending in a full at-rest pass — so the
    ``scrub_slo`` section can price healing against its own baseline.

    ``abft_plan`` (the plan with ``with_abft()`` applied) additionally
    runs an ABFT-guarded twin of every no-scrub cell (tag suffix
    ``_abft``, its own jitted step) so ``abft_slo`` can price the
    checksum-guarded matmuls against the unguarded twin — same workload,
    same faults, value paths identical by construction."""
    import dataclasses
    cells = {}
    for pol_name in kv_policies:
        kvp = kvcache.get_kv_policy(pol_name)
        # per-request attribution on every path (fused/chunked kernels
        # reduce flags per batch row in-grid since bench_kernels/v5)
        kvp = dataclasses.replace(kvp, per_slot_flags=True)
        step = jax.jit(protected.make_serve_step(
            cfg, plan=plan, with_flags=True, kv_policy=kvp))
        step_abft = (jax.jit(protected.make_serve_step(
            cfg, plan=abft_plan, with_flags=True, kv_policy=kvp))
            if abft_plan is not None else None)
        for rate in fault_rates:
            variants = [(s, False)
                        for s in ([0, scrub_every] if scrub_every else [0])]
            if abft_plan is not None:
                variants.append((0, True))
            for scrub, abft_on in variants:
                tag = _cell_tag(pol_name, rate, scrub, abft_on)
                tpath = (os.path.join(out_dir, f"telemetry_{tag}.jsonl")
                         if out_dir else None)
                kw = dict(plan=abft_plan if abft_on else plan,
                          waves=waves, slots=slots,
                          max_len=max_len, n_pages=n_pages, kv_policy=kvp,
                          fault_rate=rate, fault_seed=seed,
                          serve_step=step_abft if abft_on else step,
                          prefix_sharing=prefix_sharing,
                          scrub_every=scrub, repair=repair and scrub > 0,
                          # weight faults ride the cell's fault-rate axis:
                          # the rate-0 scrub twin stays fault-free so its
                          # SLO row prices PURE scrub overhead (the ratio
                          # CI gates), while faulted cells demonstrate
                          # healing (final at-rest DUE pinned to zero)
                          weight_fault_rate=(weight_fault_rate
                                             if scrub and rate > 0
                                             else 0.0))
                # run every cell three times: the first eats serve-step
                # and injection compiles (keeping them out of the latency
                # percentiles); the two measured runs double as the
                # bit-determinism check, and each wall-clock percentile
                # takes the min of the pair — a scheduler hiccup in one
                # run cannot flip the SLO gate.
                warm_ev, _, warm_res = frontend.run_burst(cfg, enc, **kw)
                ev_a, summ_a, res_a = frontend.run_burst(cfg, enc, **kw)
                events, summ, results = frontend.run_burst(
                    cfg, enc, telemetry_path=tpath, **kw)
                det_views = [telemetry.deterministic_view(e)
                             for e in (warm_ev, ev_a, events)]
                deterministic = (det_views[0] == det_views[1]
                                 == det_views[2]
                                 and warm_res == res_a == results)
                for sect in ("per_token_ms", "ttft_s"):
                    summ[sect] = {k: (min(v, summ_a[sect][k])
                                      if v is not None
                                      and summ_a[sect][k] is not None
                                      else v)
                                  for k, v in summ[sect].items()}
                summ["cell"] = {"kv_policy": pol_name, "fault_rate": rate,
                                "seed": seed, "slots": slots,
                                "max_len": max_len,
                                "prefix_sharing": prefix_sharing,
                                "scrub_every": scrub,
                                "repair": repair and scrub > 0,
                                "abft": abft_on,
                                "weight_fault_rate": kw[
                                    "weight_fault_rate"],
                                "bit_deterministic": deterministic}
                if out_dir:
                    telemetry.write_requests_csv(
                        events,
                        os.path.join(out_dir, f"requests_{tag}.csv"))
                cells[tag] = {"summary": summ, "results": results}
                p99 = summ["per_token_ms"]["p99"]
                p99s = f"{p99:.2f}ms" if p99 is not None else "n/a"
                heal = summ["healing"]
                print(f"[burst] {tag}: {summ['requests']['finished']}/"
                      f"{summ['requests']['submitted']} finished in "
                      f"{summ['steps']} steps, "
                      f"{summ['throughput']['tokens_per_step']:.2f} "
                      f"tok/step, p99 per-token {p99s}, "
                      f"DUE total {summ['due']['total']}, "
                      f"leaked pages {summ['pool']['leaked_pages']}"
                      + (f", shared pages "
                         f"{summ['sharing']['pages_shared']}, "
                         f"cow {summ['sharing']['cow_copies']}, "
                         f"alloc {summ['sharing']['pages_allocated_total']}"
                         f"/{summ['sharing']['solo_pages_total']} solo"
                         if prefix_sharing else "")
                      + (f", scrub corrected w={heal['w_corrected']} "
                         f"kv={heal['kv_corrected']}, final DUE "
                         f"{heal['final_due']['w']}w/"
                         f"{heal['final_due']['kv']}kv"
                         if scrub and heal["final_due"] else ""))
    return cells


def slo_section(cells, kv_policies, fault_rates):
    """Per (protected policy, rate): p99 per-token latency ratio vs the
    unprotected twin at the same rate — the envelope CI asserts."""
    slo = []
    if "unprotected" not in kv_policies:
        return slo
    for pol in kv_policies:
        if pol == "unprotected":
            continue
        for rate in fault_rates:
            base = cells[_cell_tag("unprotected", rate)]["summary"]
            prot = cells[_cell_tag(pol, rate)]["summary"]
            b99 = base["per_token_ms"]["p99"]
            p99 = prot["per_token_ms"]["p99"]
            slo.append({
                "kv_policy": pol, "fault_rate": rate,
                "p99_per_token_ms": p99,
                "unprotected_p99_per_token_ms": b99,
                "p99_ratio": (p99 / b99) if (p99 and b99) else None,
                "due_total": prot["due"]["total"],
                "leaked_pages": prot["pool"]["leaked_pages"],
                "tokens_match_unprotected":
                    cells[_cell_tag(pol, rate)]["results"] ==
                    cells[_cell_tag("unprotected", rate)]["results"]
                    if rate == 0 else None,
            })
    return slo


def scrub_slo_section(cells, kv_policies, fault_rates, scrub_every):
    """Per (policy, rate): the self-healing twin priced against ITS OWN
    no-scrub baseline — p99 per-token ratio, scrub totals, and the
    residual at-rest DUE state CI pins to zero."""
    rows = []
    if not scrub_every:
        return rows
    for pol in kv_policies:
        for rate in fault_rates:
            base = cells[_cell_tag(pol, rate)]["summary"]
            scrub = cells[_cell_tag(pol, rate, scrub_every)]["summary"]
            b99 = base["per_token_ms"]["p99"]
            s99 = scrub["per_token_ms"]["p99"]
            heal = scrub["healing"]
            rows.append({
                "kv_policy": pol, "fault_rate": rate,
                "scrub_every": scrub_every,
                "p99_per_token_ms": s99,
                "noscrub_p99_per_token_ms": b99,
                "p99_ratio": (s99 / b99) if (s99 and b99) else None,
                "scrub_passes": heal["scrub_passes"],
                "w_corrected": heal["w_corrected"],
                "kv_corrected": heal["kv_corrected"],
                "final_due": heal["final_due"],
                "leaked_pages": scrub["pool"]["leaked_pages"],
                "tokens_match_noscrub":
                    cells[_cell_tag(pol, rate, scrub_every)]["results"]
                    == cells[_cell_tag(pol, rate)]["results"],
            })
    return rows


def abft_slo_section(cells, kv_policies, fault_rates):
    """Per (policy, rate): the ABFT-guarded twin priced against ITS OWN
    unguarded baseline — p99 per-token ratio, the checksum/clamp totals
    (both must be zero here: the burst injects MEMORY faults, which ECC
    absorbs before the MXU ever sees them), and the token cross-check
    (guarded and unguarded value paths are identical by construction)."""
    rows = []
    for pol in kv_policies:
        for rate in fault_rates:
            twin = cells.get(_cell_tag(pol, rate, abft=True))
            if twin is None:
                continue
            base = cells[_cell_tag(pol, rate)]["summary"]
            summ = twin["summary"]
            b99 = base["per_token_ms"]["p99"]
            a99 = summ["per_token_ms"]["p99"]
            rows.append({
                "kv_policy": pol, "fault_rate": rate,
                "p99_per_token_ms": a99,
                "noabft_p99_per_token_ms": b99,
                "p99_ratio": (a99 / b99) if (a99 and b99) else None,
                "abft_mismatches": summ["abft"]["mismatches_total"],
                "clamp_hits": summ["abft"]["clamp_hits_total"],
                "leaked_pages": summ["pool"]["leaked_pages"],
                "bit_deterministic": summ["cell"]["bit_deterministic"],
                "tokens_match_noabft":
                    twin["results"] == cells[_cell_tag(pol, rate)]["results"],
            })
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="deepseek-7b")
    ap.add_argument("--smoke", action="store_true",
                    help="CI micro-run: 2 waves x 3 requests, tiny dims")
    ap.add_argument("--waves", type=int, default=4)
    ap.add_argument("--wave-size", type=int, default=6)
    ap.add_argument("--gap-steps", type=int, default=8)
    ap.add_argument("--prompt-len", default="4,12",
                    help="lo,hi prompt-length range (the per-request "
                         "suffix when --shared-prefix-len is set)")
    ap.add_argument("--shared-prefix-len", type=int, default=0,
                    help="prepend ONE common prefix of this many tokens "
                         "to every prompt and serve with the front-end's "
                         "prefix cache (page sharing + copy-on-write)")
    ap.add_argument("--max-new", default="4,8",
                    help="lo,hi generation-length range")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--pages", type=int, default=None,
                    help="pool size incl. per-slot parking pages "
                         "(default: full occupancy)")
    ap.add_argument("--kv-policies", default="unprotected,in-place")
    ap.add_argument("--fault-rates", default="0",
                    help="comma list of per-bit KV fault rates")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--policy", default="all-in-place",
                    choices=sorted(protection.POLICY_PRESETS),
                    help="weight-protection preset")
    ap.add_argument("--scrub-every", type=int, default=0,
                    help="run a self-healing twin of every cell with a "
                         "budgeted scrub pass every N steps (plus a full "
                         "at-rest pass after drain)")
    ap.add_argument("--repair", action="store_true",
                    help="attach a MILR repair kit to the scrub twins "
                         "(weight-DUE reconstruction + quarantine)")
    ap.add_argument("--weight-fault-rate", type=float, default=0.0,
                    help="per-bit weight fault rate injected into the "
                         "scrub twins on the KV injection cadence")
    ap.add_argument("--abft", action="store_true",
                    help="run an ABFT-guarded twin of every no-scrub cell "
                         "(plan.with_abft(): in-kernel checksum-guarded "
                         "matmuls) and price it in the abft_slo section")
    ap.add_argument("--out-dir", default=None)
    args = ap.parse_args(argv)
    setup_compile_cache()

    if args.smoke:
        # one page per slot (prompt+gen <= 10 < page_size 16): keeps the
        # KV-decode fraction of step time small enough that the protected
        # twin's p99 per-token SLO ratio has real margin under 1.10 on a
        # noisy CPU runner
        args.waves, args.wave_size, args.gap_steps = 2, 3, 4
        args.slots, args.max_len = 2, 16
        args.prompt_len, args.max_new = "3,6", "2,4"
    if args.out_dir:
        os.makedirs(args.out_dir, exist_ok=True)

    cfg = configs.get_smoke(args.arch)
    kv_policies = args.kv_policies.split(",")
    fault_rates = [float(r) for r in args.fault_rates.split(",")]
    p_lo, p_hi = (int(x) for x in args.prompt_len.split(","))
    n_lo, n_hi = (int(x) for x in args.max_new.split(","))

    print(f"[burst] {cfg.name} smoke config, {args.waves} waves x "
          f"{args.wave_size} reqs, slots={args.slots}, "
          f"kv={kv_policies}, rates={fault_rates}, seed={args.seed}")
    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    policy = protection.get_policy_preset(args.policy)
    plan = policy.plan(params)
    enc = plan.encode_tree(params)

    sharing = args.shared_prefix_len > 0
    waves = frontend.make_waves(
        seed=args.seed, n_waves=args.waves, wave_size=args.wave_size,
        vocab=cfg.vocab, prompt_len=(p_lo, p_hi), max_new=(n_lo, n_hi),
        gap_steps=args.gap_steps,
        shared_prefix_len=args.shared_prefix_len)
    cells = run_grid(cfg, enc, plan, waves, kv_policies=kv_policies,
                     fault_rates=fault_rates, slots=args.slots,
                     max_len=args.max_len, n_pages=args.pages,
                     seed=args.seed, out_dir=args.out_dir,
                     prefix_sharing=sharing, scrub_every=args.scrub_every,
                     repair=args.repair,
                     weight_fault_rate=args.weight_fault_rate,
                     abft_plan=plan.with_abft() if args.abft else None)
    out = {
        "schema": telemetry.SUMMARY_SCHEMA,
        "arch": cfg.name,
        "workload": {"seed": args.seed, "waves": args.waves,
                     "wave_size": args.wave_size,
                     "gap_steps": args.gap_steps,
                     "prompt_len": [p_lo, p_hi], "max_new": [n_lo, n_hi],
                     "shared_prefix_len": args.shared_prefix_len,
                     "prefix_sharing": sharing,
                     "scrub_every": args.scrub_every,
                     "repair": args.repair,
                     "weight_fault_rate": args.weight_fault_rate,
                     "abft": args.abft},
        "cells": {tag: c["summary"] for tag, c in cells.items()},
        "slo": slo_section(cells, kv_policies, fault_rates),
        "scrub_slo": scrub_slo_section(cells, kv_policies, fault_rates,
                                       args.scrub_every),
        "abft_slo": abft_slo_section(cells, kv_policies, fault_rates),
    }
    for row in out["slo"]:
        ratio = row["p99_ratio"]
        print(f"[burst] SLO {row['kv_policy']} @rate {row['fault_rate']}: "
              f"p99 ratio {ratio:.3f}x vs unprotected"
              if ratio is not None else
              f"[burst] SLO {row['kv_policy']}: no latency samples")
    for row in out["scrub_slo"]:
        ratio = row["p99_ratio"]
        fd = row["final_due"]
        print(f"[burst] scrub SLO {row['kv_policy']} @rate "
              f"{row['fault_rate']}: "
              + (f"p99 ratio {ratio:.3f}x vs no-scrub" if ratio is not None
                 else "no latency samples")
              + (f", final DUE {fd['w']}w/{fd['kv']}kv" if fd else ""))
    for row in out["abft_slo"]:
        ratio = row["p99_ratio"]
        print(f"[burst] ABFT SLO {row['kv_policy']} @rate "
              f"{row['fault_rate']}: "
              + (f"p99 ratio {ratio:.3f}x vs unguarded" if ratio is not None
                 else "no latency samples")
              + f", mismatches {row['abft_mismatches']}, tokens match "
              + str(row["tokens_match_noabft"]))
    if args.out_dir:
        path = os.path.join(args.out_dir, "summary.json")
        telemetry.write_summary(out, path)
        print(f"[burst] wrote {path}")
    return out


if __name__ == "__main__":
    main()
