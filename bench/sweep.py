"""Finds a cell's knee: the highest offered rate the system serves with no
growing backlog. Builds the model once, then serves the cell's mix at
each rate in turn (fill, then window) with a fresh front-end.

  python3 bench/sweep.py --workload <cell> --rates 0.4,0.6,0.8 \
      --seed 5 --seconds 45 [--fill 45]

Prints one JSON line per rate: output tokens/s, median TTFT, and the
queue depth over the window's first and last quarters (a backlog that
grows shows as the later being larger).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--fill", type=float, default=None)
    args = ap.parse_args(argv)

    import jax

    from bench import spec, timeline, traffic
    from repro.serving import telemetry

    cell = spec.load_cell(harness.ROOT, args.workload)
    if jax.devices()[0].platform != "tpu":
        harness.log("no TPU found")
        return 2
    harness.setup_compile_cache()
    phases = harness.Phases()
    phases.listen()
    cfg, plan, enc, kvp = harness.build_model(cell, args.seed, phases)
    fill = args.fill if args.fill is not None else cell.params["fill_s"]
    for rate in (float(r) for r in args.rates.split(",")):
        col = telemetry.TelemetryCollector()
        fe = harness.make_frontend(cell, cfg, plan, enc, kvp, phases,
                                   control="none", collector=col)
        arrivals = traffic.schedule(cell.traffic, rate=rate, seed=args.seed,
                                    horizon_s=fill + args.seconds,
                                    vocab=cell.config["vocab_size"])
        rec = {"steps": {}, "late": []}
        start = time.perf_counter()
        i = harness.serve(fe, arrivals, start, start + fill, 0, rec)
        w0 = time.perf_counter()
        harness.serve(fe, arrivals, start, w0 + args.seconds, i, rec)
        w1 = time.perf_counter()
        run = timeline.Run(
            steps=rec["steps"], events=col.events,
            due={a.rid: start + a.due_s for a in arrivals}, w0=w0, w1=w1,
            slots=fe.slots_n, allocatable=fe.allocator.n_pages - fe.slots_n,
            model=cell.config)
        qd = [(rec["steps"][e["step"]][0], e["queue_depth"])
              for e in col.events
              if e["event"] == "step" and e["step"] in rec["steps"]
              and w0 <= rec["steps"][e["step"]][0] < w1]
        quarter = max(1, len(qd) // 4)
        steps = timeline.window_steps(run)
        wall = [rec["steps"][s][1] - rec["steps"][s][0] for s in steps]
        print(json.dumps({
            "rate_per_s": rate,
            "output_tok_s": timeline.window_tokens(run) / (w1 - w0),
            "ttft_p50_s": timeline.percentile(timeline.ttfts(run), 50),
            "itl_p95_ms": (timeline.percentile(timeline.itl_gaps(run), 95)
                           or 0) * 1e3,
            "queue_first_quarter": sum(q for _, q in qd[:quarter]) / quarter,
            "queue_last_quarter": sum(q for _, q in qd[-quarter:]) / quarter,
            "active_mean": sum(len(timeline.live_lens(run, s))
                               for s in steps) / max(1, len(steps)),
            "step_s_mean": sum(wall) / max(1, len(wall)),
            "due_in_window": len(timeline.window_rids(run))}), flush=True)
        for leaf in jax.tree.leaves(fe.cache):
            leaf.delete()
        fe.cache = None
    return 0


if __name__ == "__main__":
    sys.exit(main())
