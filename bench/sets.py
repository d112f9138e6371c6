"""Runs one cell several times, one process after another, and collects
each run's result line: the sets of runs that bounds and limits are set
from.

  python3 bench/sets.py --workload <cell> --seeds 11,12,13 --seconds 45 \
      [--trace 0|1] [--control none|int8-act] --out results.jsonl

This process never imports JAX, so each run has the chip to itself.
Each output line is the run's result with ``seed``, ``rc`` and
``elapsed_s`` added; the runs' standard error goes to ``<out>.log``.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import subprocess
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--trace", default="0")
    ap.add_argument("--control", default="none")
    ap.add_argument("--out", required=True)
    ap.add_argument("--dump-gaps", default=None,
                    help="a directory for each run's checked gaps")
    ap.add_argument("--deadline", type=float, default=None,
                    help="start no run after this time (seconds since the "
                         "epoch)")
    args = ap.parse_args(argv)
    out = pathlib.Path(args.out)
    out.parent.mkdir(parents=True, exist_ok=True)
    worst = 0
    with open(out, "a") as fo, open(f"{out}.log", "a") as fl:
        for seed in args.seeds.split(","):
            if args.deadline is not None and time.time() > args.deadline:
                print(f"seed {seed} not run: past the deadline", flush=True)
                continue
            cmd = [sys.executable, str(HERE / "run.py"), "--workload",
                   args.workload, "--seed", seed, "--seconds", args.seconds,
                   "--trace", args.trace, "--control", args.control]
            if args.dump_gaps:
                pathlib.Path(args.dump_gaps).mkdir(parents=True, exist_ok=True)
                cmd += ["--dump-gaps", str(pathlib.Path(args.dump_gaps) /
                        f"gaps-{seed}-{args.control}-t{args.trace}.json")]
            fl.write(f"\n=== {' '.join(cmd)}\n")
            fl.flush()
            t0 = time.perf_counter()
            p = subprocess.run(cmd, stdout=subprocess.PIPE, stderr=fl,
                               text=True)
            elapsed = time.perf_counter() - t0
            lines = p.stdout.strip().splitlines()
            try:
                res = json.loads(lines[-1])
            except (IndexError, ValueError):
                res = {"stdout_tail": p.stdout[-2000:]}
            res.update(seed=int(seed), rc=p.returncode, elapsed_s=elapsed)
            fo.write(json.dumps(res) + "\n")
            fo.flush()
            print(f"seed {seed} rc {p.returncode} {elapsed:.1f} s "
                  f"correct={res.get('correct')} "
                  f"metrics={ {k: v['value'] for k, v in res.get('metrics', {}).items()} } "
                  f"checks={ {k: v['value'] for k, v in res.get('checks', {}).items()} }",
                  flush=True)
            worst = max(worst, p.returncode)
    return worst


if __name__ == "__main__":
    sys.exit(main())
