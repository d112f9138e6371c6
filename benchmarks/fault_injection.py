"""Paper Table 2: accuracy drop under memory faults, per protection scheme.

{faulty, parity-zero, secded72, in-place} x fault rates {1e-6..1e-3} (+ an
amplified 3e-3 row where small-model effects are visible), multiple trials,
on WOT-trained CNNs.  Since PR 2 the grid runs through the compiled
on-device campaign engine (``repro.protection.campaign``): one encode and
one jit compile per (model, scheme), then the whole (trial x rate) sweep
executes inside a single device program — Table 2 in seconds instead of one
host round-trip per cell.  ``--batch scan`` trades the vmap grid's speed for
constant memory; ``--json`` dumps every ``CampaignResult`` for BENCH_*.json
artifacts; ``--compute`` adds the ABFT compute-fault coverage rows
(accumulator/decoded-weight corruption detected by the fused kernel's
checksums — docs/abft.md).  See ``docs/table2.md`` for the full
reproduction walkthrough.
"""
from __future__ import annotations

import argparse
import json
import time

import jax

from repro import protection
from repro.launch.compile_cache import setup_compile_cache
from repro.training.cnn_experiments import (eval_policy, run_scheme_campaign,
                                            train_cnn_wot)

RATES = (1e-6, 1e-5, 1e-4, 1e-3, 3e-3)
SCHEMES = ("faulty", "parity-zero", "secded72", "in-place")


def run(models=("resnet18",), trials=5, rates=RATES, verbose=True,
        batch="scan", json_path=None, policy=None, compute=False):
    """``policy`` (a ``protection.POLICY_PRESETS`` name) adds one extra
    campaign row under that mixed-scheme preset — the per-layer
    heterogeneous deployment the ProtectionPlan serves. ``compute`` adds
    the COMPUTE-fault rows (``protection.compute_campaign``, targets
    ``acc`` and ``wdec``): instead of accuracy drop under memory faults,
    they report the in-kernel ABFT check's detection coverage of silent
    matmul corruption — the fault class ECC cannot see (docs/abft.md)."""
    results = {}
    campaigns = {}
    rows = list(SCHEMES)
    for name in models:
        params, fwd, tmpl = train_cnn_wot(name)
        for i, scheme in enumerate(SCHEMES):
            res = run_scheme_campaign(params, fwd, tmpl, scheme, rates=rates,
                                      trials=trials, batch=batch,
                                      key=jax.random.PRNGKey(i))
            campaigns[(name, scheme)] = res
            results[(name, scheme)] = (res.space_overhead, res.row(),
                                       res.clean)
        if policy:
            pol = protection.get_policy_preset(
                policy, predicate=lambda p, l: getattr(l, "ndim", 0) >= 2)
            res = run_scheme_campaign(params, fwd, tmpl, None, policy=pol,
                                      rates=rates, trials=trials, batch=batch,
                                      key=jax.random.PRNGKey(len(SCHEMES)))
            row_id = f"policy:{policy}"
            campaigns[(name, row_id)] = res
            results[(name, row_id)] = (res.space_overhead, res.row(),
                                       res.clean)
            rows = list(SCHEMES) + [row_id]
        if compute:
            # per-element perturb rates over the probe surface — a CNN's
            # only matmul leaf is its tiny classifier head, so the memory
            # grid's rates would inject ~nothing. Not merged into
            # ``results``: these rows report detection coverage, not
            # accuracy drop.
            crates = (1e-3, 1e-2, 1e-1)
            for j, tgt in enumerate(("acc", "wdec")):
                res = protection.compute_campaign(
                    params, rates=crates, trials=trials, batch=batch,
                    key=jax.random.PRNGKey(100 + j), target=tgt,
                    probe_m=64)
                campaigns[(name, f"compute:{tgt}")] = res
        clean = campaigns[(name, SCHEMES[0])].clean
        if verbose:
            report = protection.coverage(params, eval_policy("in-place"))
            print(f"# {name}: clean int8+WOT accuracy {clean:.3f}")
            print("# " + report.summary().replace("\n", "\n# "))
            sweep = sum(c.wall_clock_s for (m, _), c in campaigns.items()
                        if m == name)
            comp = sum(c.compile_s for (m, _), c in campaigns.items()
                       if m == name)
            dev = campaigns[(name, SCHEMES[0])]
            print(f"# campaign [{dev.platform}/{dev.batch}]: "
                  f"{len(SCHEMES)} compiles {comp:.1f}s, "
                  f"full grid sweep {sweep:.2f}s")
            print(f"# {'scheme':11s} {'ovh%':5s} " +
                  " ".join(f"{r:>13.0e}" for r in rates))
            for scheme in rows:
                res = campaigns[(name, scheme)]
                cells = " ".join(f"{d * 100:6.2f}±{s * 100:4.1f}"
                                 for d, s in res.row())
                print(f"# {scheme:11s} {res.space_overhead * 100:4.1f}%  "
                      f"{cells}")
            if compute:
                for tgt in ("acc", "wdec"):
                    res = campaigns[(name, f"compute:{tgt}")]
                    cov = " ".join(f"{r:.0e}:{m * 100:6.2f}%"
                                   for r, m in zip(res.rates, res.mean()))
                    print(f"# abft-coverage target={tgt}: {cov}  "
                          f"(checksum false positives at rate 0: "
                          f"{res.clean:.0f})")
    if json_path:
        with open(json_path, "w") as f:
            json.dump({f"{m}/{s}": c.to_dict()
                       for (m, s), c in campaigns.items()}, f, indent=2)
        if verbose:
            print(f"# wrote {json_path}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--models", nargs="+", default=["resnet18"])
    ap.add_argument("--trials", type=int, default=5)
    ap.add_argument("--batch", default="scan", choices=("vmap", "scan"),
                    help="grid layout: scan compiles ~3x faster on CPU, "
                         "vmap sweeps fastest on accelerators")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="dump all CampaignResults (BENCH_*.json format)")
    ap.add_argument("--policy", default=None,
                    choices=sorted(protection.POLICY_PRESETS),
                    help="extra row: campaign under a named mixed-scheme "
                         "ProtectionPlan preset")
    ap.add_argument("--compute", action="store_true",
                    help="extra rows: ABFT detection coverage of injected "
                         "COMPUTE faults (accumulator SDCs and decoded-"
                         "weight corruption), per target")
    args = ap.parse_args(argv)
    setup_compile_cache()
    t0 = time.time()
    results = run(models=tuple(args.models), trials=args.trials,
                  batch=args.batch, json_path=args.json, policy=args.policy,
                  compute=args.compute)
    us = (time.time() - t0) * 1e6
    for (name, scheme), (ovh, row, clean) in results.items():
        drops = "/".join(f"{d * 100:.2f}" for d, _ in row)
        print(f"table2_{name}_{scheme},{us:.0f},ovh={ovh:.3f}_drops={drops}")


if __name__ == "__main__":
    main()
