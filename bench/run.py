"""Runs one benchmark cell once on the chip and prints one JSON line.

  python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration file, a traffic mix
and a cell file under ``bench/``. One process holds the chip and runs:

1. build   the protection plan, and the encoded weights from one jitted
           program that generates bf16 weights from the seed and encodes
           them (``bench.weights``); the serve step compiled for the
           cell's slots and cache, once, and every eager page operation
           the front-end can issue;
2. fill    open-loop arrivals (``bench.traffic``) into the serving
           front-end (``ServingFrontend.submit``, then ``step``) until
           occupancy is steady;
3. window  the same for ``--seconds``; with ``--trace 1`` under the
           profiler;
4. check   peak device memory; then, with the program's state freed, the
           plain reference (``bench.reference``) over a sample of the
           requests the window finished.

Set-up (1 and 2) is ``setup_s``; earlier lines on standard error give its
parts and the compile-cache hits of each. With ``--trace 0`` the metrics
are the cell's end-to-end metrics, with ``--trace 1`` its per-layer ones,
each from ``bench/metrics/<name>.py``. Without a TPU, or with fewer chips
than the cell asks for, it exits 2 before printing any result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import inspect  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import Counter  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

CACHE_DIR = ROOT / ".jax_cache"


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Phases:
    """Seconds and compile-cache traffic of each set-up phase."""

    def __init__(self):
        self.phase = "start"
        self.seconds: dict = {}
        self.cache: dict = {}

    def listen(self):
        import jax.monitoring as mon

        def on_event(name, **_):
            tag = {"/jax/compilation_cache/cache_hits": "hits",
                   "/jax/compilation_cache/cache_misses": "misses",
                   "/jax/compilation_cache/compile_requests_use_cache":
                       "requests"}.get(name)
            if tag:
                self.cache.setdefault(self.phase, Counter())[tag] += 1

        mon.register_event_listener(on_event)

    @contextlib.contextmanager
    def __call__(self, name: str):
        self.phase = name
        t0 = time.perf_counter()
        yield
        self.seconds[name] = time.perf_counter() - t0
        c = self.cache.get(name, Counter())
        log(f"phase {name}: {self.seconds[name]:.3f} s; compile cache "
            f"{c['requests']} lookups, {c['hits']} hits, "
            f"{c['misses']} misses (compiled)")

    def compiles(self, name: str) -> int:
        return self.cache.get(name, Counter())["misses"]


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def setup_compile_cache():
    """JAX's persistent cache: ``$JAX_COMPILATION_CACHE_DIR`` where set,
    else a fixed directory in the checkout; every program is kept, the
    small eager ones too, so only a checkout's first run compiles."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def build_model(cell, seed: int, phases: Phases):
    """-> (arch config, protection plan, encoded weights, KV policy)."""
    import jax
    import jax.numpy as jnp

    from bench import reference, weights
    from repro import configs, protection
    from repro.models import layers, lm
    from repro.serving import kvcache

    conf = cell.config
    cfg = configs.get(conf["arch"])
    if conf.get("arch_overrides"):
        cfg = cfg.with_(**conf["arch_overrides"])
    runs = {"rope_theta": float(cfg.rope_theta),
            "rms_norm_eps": inspect.signature(
                layers.rms_norm).parameters["eps"].default}
    for k, v in runs.items():
        if v != float(conf[k]):
            raise SystemExit(f"the program runs {k} = {v}; the configuration "
                             f"states {conf[k]}")
    specs = lm.param_specs(cfg, jnp.bfloat16)
    have = weights.layout(specs)
    want = reference.expected_layout(conf)
    if have != want:
        raise SystemExit(f"the program's weights {have} are not the layout "
                         f"the configuration states {want}")
    policy = protection.get_policy_preset(conf["weights"]["preset"],
                                          backend=conf["weights"]["backend"])
    plan = policy.plan(specs)
    key = weights.seed_key(seed)
    with phases("build_compile"):
        init = jax.jit(lambda k: weights.make_encoded(
            specs, k, plan.encode_tree)).lower(key).compile()
    with phases("build_run"):
        enc = jax.block_until_ready(init(key))
    kvp = kvcache.get_kv_policy(conf["kv_policy"])
    return cfg, plan, enc, kvp


def make_frontend(cell, cfg, plan, enc, kvp, phases: Phases, *,
                  control: str, collector, wrap=None):
    """The serving front-end over ``enc`` with its serve step compiled
    once for the cell's shapes, and every eager page operation warmed.
    ``wrap``, where given, maps the serve step to the one the front-end
    calls (the tests break it underneath)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.serving import frontend, kvcache
    from repro.serving import protected as sp

    conf = cell.config
    slots, max_len = int(conf["slots"]), int(conf["max_len"])
    kvp = dataclasses.replace(kvp, per_slot_flags=True)
    act_quant = {"none": None, "int8-act": "dynamic"}[control]
    step = jax.jit(sp.make_serve_step(cfg, plan=plan, with_flags=True,
                                      kv_policy=kvp, act_quant=act_quant))
    fe = frontend.ServingFrontend(
        cfg, enc, plan=plan, slots=slots, max_len=max_len, kv_policy=kvp,
        serve_step=lambda *a: None, collector=collector,
        prefix_sharing=bool(conf.get("prefix_sharing", False)))
    tokens = jnp.zeros((slots, 1), jnp.int32)
    pos = jnp.zeros((slots,), jnp.int32)
    with phases("serve_compile"):
        compiled = step.lower(enc, fe.cache, tokens, pos).compile()

    def serve_step(*args):
        with span("bench.serve_step"):
            return compiled(*args)

    fe.serve_step = wrap(serve_step) if wrap else serve_step
    with phases("warmup"):
        npg = fe.cache["kv_table"].shape[2]
        for n in range(1, npg + 1):
            fe.cache = kvcache.zero_pages(fe.cache,
                                          tuple(range(slots, slots + n)))
        fe.cache = kvcache.set_slot_pages(fe.cache, 0, ())
        for _ in range(2):
            fe.step()
        jax.block_until_ready(fe.cache)
        np.asarray(fe.cache["kv_table"][0, 0])
    return fe


def serve(fe, arrivals, start: float, until: float, i: int, rec: dict) -> int:
    """Offer ``arrivals[i:]`` as they fall due and step the front-end until
    ``until``. Returns the index of the first arrival not yet offered."""
    from repro.serving import frontend

    n = len(arrivals)
    while True:
        now = time.perf_counter()
        if now >= until:
            return i
        while i < n and start + arrivals[i].due_s <= now:
            a = arrivals[i]
            with span("bench.submit"):
                fe.submit(frontend.Request(a.rid, a.prompt, a.max_new))
            rec["late"].append(now - (start + a.due_s))
            i += 1
        if fe.active or len(fe.queue):
            t0 = time.perf_counter()
            with span("bench.frontend_step"):
                fe.step()
            rec["steps"][fe.step_no - 1] = (t0, time.perf_counter())
        else:
            nxt = start + arrivals[i].due_s if i < n else until
            with span("bench.wait_arrival"):
                time.sleep(max(0.0, min(nxt, until) - now))


def free_program_state(fe):
    import jax
    for leaf in jax.tree.leaves((fe.cache, fe.enc_params)):
        with contextlib.suppress(Exception):
            leaf.delete()
    fe.cache = fe.enc_params = fe.serve_step = None
    gc.collect()


def check(cell, seed: int, finished: dict, flags: int,
          phases: Phases, dump_gaps: str | None = None) -> tuple:
    """-> (correct, readings, checks): the ECC flags the window's steps
    raised (none may, with no fault injected), and the reference's gaps
    over a sample of the window's finished requests. Every reading is
    returned; the configuration's ``limits`` name those compared.
    ``gap_excess`` is the mean over served tokens of how far each gap
    exceeds ``gap_excess_from`` (logits): the tail of the gaps, where a
    lower-precision path departs from the program's own rounding."""
    from bench import reference, weights

    rules = cell.config["check"]
    picked = reference.sample(finished, seed, rules["sample_requests"])
    checks = {"ecc_flags": {"value": flags, "limit": 0}}
    if not picked:
        checks["requests_checked"] = {"value": 0, "limit": 1}
        return False, {}, checks
    with phases("reference"):
        gaps = reference.score(cell.config, weights.seed_key(seed),
                               [finished[r] for r in picked],
                               rules["block_rows"],
                               int(cell.config["max_len"]) - 1)
    allg = [float(g) for gs in gaps for g in gs]
    if dump_gaps:
        with open(dump_gaps, "w") as f:
            json.dump([{"rid": r, "prompt_len": len(finished[r][0]),
                        "gaps": [float(g) for g in gs]}
                       for r, gs in zip(picked, gaps)], f)
    tail = float(rules["gap_excess_from"])
    readings = {"max_logit_gap": max(allg),
                "mean_logit_gap": sum(allg) / len(allg),
                "not_first_share": sum(g > 0 for g in allg) / len(allg),
                "gap_excess": sum(max(g - tail, 0.0) for g in allg)
                / len(allg)}
    log(f"check: {len(picked)} requests, {len(allg)} served tokens; "
        + ", ".join(f"{k} {v}" for k, v in readings.items()))
    checks["requests_checked"] = {"value": len(picked), "limit": 1}
    ok = flags == 0
    for name, limit in rules["limits"].items():
        checks[name] = {"value": readings[name], "limit": limit}
        ok = ok and readings[name] <= limit
    return ok, readings, checks


def run_cell(cell, *, seed: int, seconds: float, trace: bool,
             control: str = "none", wrap=None,
             keep_trace: str | None = None,
             dump_gaps: str | None = None) -> dict:
    import jax

    from bench import spec, timeline, trace_reduce, traffic
    from bench.peaks import peaks
    from repro.serving import telemetry

    phases = Phases()
    phases.listen()
    log(f"compile cache: {setup_compile_cache()}")
    devices = jax.devices()
    dev = device_info(devices)
    peak = peaks(dev["kind"]) if dev["platform"] == "tpu" else None

    cfg, plan, enc, kvp = build_model(cell, seed, phases)
    col = telemetry.TelemetryCollector()
    fe = make_frontend(cell, cfg, plan, enc, kvp, phases, control=control,
                       collector=col, wrap=wrap)
    del enc

    fill_s = float(cell.params["fill_s"])
    rate = float(cell.params["rate_per_s"])
    arrivals = traffic.schedule(cell.traffic, rate=rate, seed=seed,
                                horizon_s=fill_s + seconds,
                                vocab=cell.config["vocab_size"])
    rec = {"steps": {}, "late": []}
    start = time.perf_counter()
    with phases("fill"):
        i = serve(fe, arrivals, start, start + fill_s, 0, rec)
    tdir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    if trace:
        # the harness's spans and the device's operations, without the
        # Python tracer, whose events would outnumber them a thousandfold
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(tdir, profiler_options=opts)
    w0 = time.perf_counter()
    setup_s = w0 - T_START
    n_ev = len(col.events)
    with phases("window"), span("bench.window"):
        serve(fe, arrivals, start, w0 + seconds, i, rec)
        w1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    in_window = phases.compiles("window")
    log(f"compiles inside the window: {in_window}")
    stats = devices[0].memory_stats() or {}
    mem_peak = int(stats.get("peak_bytes_in_use", 0))

    due = {a.rid: start + a.due_s for a in arrivals}
    page_size = kvp.page_size
    run = timeline.Run(
        steps=rec["steps"], events=col.events, due=due, w0=w0, w1=w1,
        slots=fe.slots_n, allocatable=fe.allocator.n_pages - fe.slots_n,
        model=cell.config, peak=peak, setup_s=setup_s)
    late = sorted(rec["late"])
    log(f"setup_s {setup_s:.3f} (fill {fill_s} s at {rate} req/s); window "
        f"{w1 - w0:.3f} s, {len(timeline.window_steps(run))} steps, "
        f"{len(col.events) - n_ev} events; generator lateness median "
        f"{timeline.percentile(late, 50)} s, max {late[-1] if late else None} s;"
        f" page size {page_size}")
    log(f"peak device memory {mem_peak} bytes")

    rids = timeline.window_rids(run)
    rejected = {e["rid"] for e in col.events if e["event"] == "reject"}
    attempted = len(rids)
    failed = sum(1 for r in rids if r in rejected)

    breakdown = None
    if trace:
        ev = trace_reduce.extract(tdir)
        if keep_trace:
            os.makedirs(keep_trace, exist_ok=True)
            shutil.copy(trace_reduce.find_xplane(tdir),
                        os.path.join(keep_trace, "window.xplane.pb"))
            with open(os.path.join(keep_trace, "describe.txt"), "w") as f:
                f.write(trace_reduce.describe(tdir))
            trace_reduce.save_events(
                ev, os.path.join(keep_trace, "events.json.gz"))
        shutil.rmtree(tdir, ignore_errors=True)
        run.trace = trace_reduce.reduce(ev)
        s = run.trace
        breakdown = {
            "device_ops": [[k, v / 1e9] for k, v in
                           sorted(s.op_ns.items(), key=lambda x: -x[1])[:10]],
            "idle_gaps": [[n, v / 1e9] for n, v in s.gaps[:10]]}
        dev = {**dev, "busy_s": s.busy_ns / 1e9, "window_s": s.window_ns / 1e9}

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = spec.reader(ROOT, m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            log(f"metric {m['name']} = {v} {m['unit']}")

    finished = {}
    for e in col.events:
        if e["event"] == "finish":
            t_fin = rec["steps"].get(e["step"], (0, w1))[1]
            if w0 <= t_fin < w1:
                finished[e["rid"]] = None
    by_rid = {a.rid: a for a in arrivals}
    for rid in finished:
        finished[rid] = (by_rid[rid].prompt, fe.results[rid])
    flags = sum(int(e[k]) for e in col.events[n_ev:] if e["event"] == "step"
                for k in ("w_corrected", "w_due", "kv_corrected", "kv_due"))
    free_program_state(fe)
    correct, readings, checks = check(cell, seed, finished, flags, phases,
                                      dump_gaps)
    dev["memory_peak_bytes"] = mem_peak
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": failed, "metrics": metrics, "device": dev}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["readings"] = readings
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name} {c['value']} limit {c['limit']}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("none", "int8-act"),
                    default="none",
                    help="serve with int8 activations: the lower-precision "
                         "control that the check has to fail")
    ap.add_argument("--keep-trace", default=None,
                    help="copy the raw trace and a description of it here")
    ap.add_argument("--dump-gaps", default=None,
                    help="write every checked token's reference gap here "
                         "(JSON, one entry per request)")
    args = ap.parse_args(argv)

    from bench import spec
    cell = spec.load_cell(ROOT, args.workload)

    import jax
    devices = jax.devices()
    dev = device_info(devices)
    if dev["platform"] != "tpu" or dev["count"] < cell.chips:
        log(f"needs {cell.chips} TPU chip(s); JAX finds {dev}")
        return 2
    result = run_cell(cell, seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace), control=args.control,
                      keep_trace=args.keep_trace, dump_gaps=args.dump_gaps)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
