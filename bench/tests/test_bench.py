"""CPU tests of the benchmark: its arithmetic, its traffic, its trace
reduction, how it finds a cell's files, and that its check fails a broken
or lower-precision serving path.

  JAX_PLATFORMS=cpu PYTHONPATH=src python -m pytest -q bench/tests
"""
from __future__ import annotations

import json
import os
import pathlib
import shutil
import subprocess
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).resolve().parent / "data"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import spec, timeline, trace_reduce, traffic, work  # noqa: E402


# ---------------------------------------------------------------------------
# end-to-end arithmetic on synthetic event streams
# ---------------------------------------------------------------------------


def synthetic_run(step_s=0.1, n_steps=100, stall_at=None, stall_s=0.0,
                  stall_every=0):
    """Two requests, both due at 0.5 s, one slot each, prompts of 3
    tokens and 40 outputs, stepped every ``step_s``; ``stall_at`` adds
    ``stall_s`` before that step (and every ``stall_every`` steps after)."""
    steps, t = {}, 0.0
    for s in range(n_steps):
        if stall_at is not None and s >= stall_at and (
                s == stall_at or (stall_every and
                                  (s - stall_at) % stall_every == 0)):
            t += stall_s
        steps[s] = (t, t + step_s)
        t += step_s
    events = []
    for rid, admit in ((0, 5), (1, 6)):
        events.append({"event": "admit", "rid": rid, "step": admit,
                       "n_pages": 3})
        events.append({"event": "first_token", "rid": rid,
                       "step": admit + 2})
        events.append({"event": "finish", "rid": rid, "step": admit + 41})
    return timeline.Run(steps=steps, events=events, due={0: 0.5, 1: 0.5},
                        w0=0.0, w1=steps[n_steps - 1][1], slots=2,
                        allocatable=10, model={})


def test_ttft_counts_from_due_time():
    run = synthetic_run()
    # rid 0: first token at the end of step 7 (0.8 s) minus due 0.5 s
    assert timeline.ttfts(run) == pytest.approx([0.3, 0.4])


def test_itl_is_every_gap_not_a_mean():
    run = synthetic_run()
    gaps = timeline.itl_gaps(run)
    assert len(gaps) == 2 * 39
    assert max(gaps) == pytest.approx(0.1)


def test_output_tokens_over_window():
    run = synthetic_run()
    assert timeline.window_tokens(run) == 2 * 40


@pytest.mark.parametrize("stall_at", [6, 20])
def test_a_stall_raises_ttft_and_itl(stall_at):
    base = synthetic_run()
    slow = synthetic_run(stall_at=stall_at, stall_s=2.0, stall_every=4)
    itl = lambda r: timeline.percentile(timeline.itl_gaps(r), 95)
    assert itl(slow) > itl(base) + 1.0
    if stall_at <= 7:        # the stall lands before the first tokens
        assert (timeline.percentile(timeline.ttfts(slow), 50)
                > timeline.percentile(timeline.ttfts(base), 50) + 1.0)


def test_request_without_first_token_enters_at_its_wait():
    run = synthetic_run(n_steps=7)       # the window closes before step 7
    assert timeline.ttfts(run) == pytest.approx([run.w1 - 0.5] * 2)


def test_open_gap_at_close_counts():
    run = synthetic_run(n_steps=30)
    assert run.w1 - run.steps[29][1] == 0
    run.w1 += 5.0                         # a stall at the close
    assert max(timeline.itl_gaps(run)) >= 5.0


def test_metric_readers_on_synthetic_run():
    run = synthetic_run()
    run.setup_s = 12.5
    read = lambda m: spec.reader(ROOT, m)(run)
    assert read("setup_s") == 12.5
    assert read("ttft_p50_s") == pytest.approx(0.35)
    assert read("itl_p95_ms") == pytest.approx(100.0)
    assert read("output_tok_s") == pytest.approx(80 / run.w1)
    assert read("queue_wait_p50_s") == pytest.approx(0.05)
    assert 0 < read("slot_occupancy") <= 100
    assert 0 < read("kv_pages_in_use_share") <= 100
    for m in ("step_device_ms", "ecc_qmatmul_roofline",
              "paged_attention_roofline", "device_idle_share", "step_mfu"):
        assert read(m) is None, m      # nothing to read without a trace/chip


# ---------------------------------------------------------------------------
# traffic
# ---------------------------------------------------------------------------

MIX = json.loads((ROOT / "bench" / "traffic" / "chat-short.json").read_text())


def test_one_seed_one_schedule():
    a = traffic.schedule(MIX, rate=0.6, seed=2**33 + 5, horizon_s=300,
                         vocab=1000)
    b = traffic.schedule(MIX, rate=0.6, seed=2**33 + 5, horizon_s=300,
                         vocab=1000)
    c = traffic.schedule(MIX, rate=0.6, seed=2**33 + 6, horizon_s=300,
                         vocab=1000)
    assert a == b
    assert a != c


def test_order_seed_fixes_lengths_and_times_not_ids():
    free = {k: v for k, v in MIX.items() if k != "order_seed"}
    a, b = (traffic.schedule(MIX, rate=0.8, seed=s, horizon_s=120,
                             vocab=1000) for s in (3, 2**31 + 9))
    assert [(x.due_s, len(x.prompt), x.max_new) for x in a] == \
        [(x.due_s, len(x.prompt), x.max_new) for x in b]
    assert [x.prompt for x in a] != [x.prompt for x in b]
    c, d = (traffic.schedule(free, rate=0.8, seed=s, horizon_s=120,
                             vocab=1000) for s in (3, 2**31 + 9))
    assert [x.due_s for x in c] != [x.due_s for x in d]


@pytest.mark.parametrize("seed", [1, 2**31 + 7, 9_000_000_000])
def test_length_quantiles_fixed_across_seeds(seed):
    q = MIX["block"]
    rate = 0.5
    horizon = 4 * q / rate + 0.01     # four whole blocks
    ref = traffic.schedule(MIX, rate=rate, seed=0, horizon_s=horizon,
                           vocab=1000)
    got = traffic.schedule(MIX, rate=rate, seed=seed, horizon_s=horizon,
                           vocab=1000)
    assert len(got) == len(ref) == 4 * q
    for blk in range(4):
        part = lambda s, f: sorted(f(a) for a in s[blk * q:(blk + 1) * q])
        assert part(got, lambda a: len(a.prompt)) == \
            part(ref, lambda a: len(a.prompt))
        assert part(got, lambda a: a.max_new) == part(ref, lambda a: a.max_new)
    # every block lasts exactly q / rate: its last arrival is due at its end
    for blk in range(4):
        assert got[(blk + 1) * q - 1].due_s == pytest.approx((blk + 1) * q / rate)


def test_lengths_follow_the_mix():
    p = traffic.quantiles(MIX["prompt_len"], 16)
    o = traffic.quantiles(MIX["output_len"], 16)
    assert min(p) >= 8 and max(p) <= 128 and sorted(p)[7] <= 32 <= sorted(p)[8]
    assert min(o) >= 16 and max(o) <= 256 and sorted(o)[7] <= 96 <= sorted(o)[8]
    g = traffic.gap_quantiles(MIX["arrivals"], 0.5, 16)
    assert sum(g) == pytest.approx(32.0)


# ---------------------------------------------------------------------------
# work arithmetic
# ---------------------------------------------------------------------------


def test_work_counts():
    model = json.loads((ROOT / "bench" / "configs"
                        / "qwen1.5-4b-inplace.json").read_text())
    per_token = sum(2 * k * n * c for _, k, n, c in work.step_matmuls(model))
    # 2 x (40 layers x 2560 x (4 x 2560 + 3 x 6912) + 2560 x 151936)
    assert per_token == 2 * (40 * 2560 * (4 * 2560 + 3 * 6912)
                             + 2560 * 151936)
    f, b = work.attention_work(model, [10, 20])
    assert f == 4 * 20 * 128 * 30
    assert b == 30 * 2 * (20 * 128 + 4) + 2 * 2 * 2 * 20 * 128
    peak = {"bf16_flops": 1e12, "hbm_bytes_per_s": 1e9}
    assert work.least_time(2e12, 1e9, peak) == 2.0
    assert work.least_time(1e12, 3e9, peak) == 3.0


# ---------------------------------------------------------------------------
# trace reduction
# ---------------------------------------------------------------------------


def test_union_and_gaps_by_hand():
    ev = trace_reduce.Events(
        ops=[("fusion.1", 10, 20, None), ("x", 15, 30, "ecc_qmatmul"),
             ("y", 50, 60, "paged_attention"), ("z", 95, 120, None)],
        modules=[("jit_serve_step", 10, 60)],
        spans=[("bench.window", 0, 100), ("bench.frontend_step", 5, 70),
               ("bench.wait_arrival", 70, 100)])
    s = trace_reduce.reduce(ev)
    assert s.window_ns == 100 and s.busy_ns == 20 + 10 + 5
    assert s.idle_share == pytest.approx(0.65)
    assert s.kernel_ns == {"ecc_qmatmul": 15, "paged_attention": 10}
    assert s.gaps == [("wait_arrival", 35), ("frontend_step", 20),
                      ("frontend_step", 10)]
    assert s.steps == [(10, 60, 30.0)]


def test_reduction_on_a_recorded_chip_step():
    """One front-end step of ``qwen4b-chat`` as a TPU v5e traced it:
    the extracted events of a traced run, cut to one ``frontend_step``
    span (which stands in for the window) and operation names cut to 160
    characters. Each operation keeps the kernel that its full metadata
    gave it: the wrappers' own small XLA operations (an activation's
    absmax) count with their kernel."""
    import gzip
    with gzip.open(ROOT / "bench" / "testdata"
                   / "serve_step.events.json.gz", "rt") as f:
        d = json.load(f)
    ev = trace_reduce.Events(*(list(map(tuple, d[k]))
                               for k in ("ops", "modules", "spans")))
    calls = {"%ecc_qmatmul": "ecc_qmatmul", "%ecc_decode": "ecc_decode",
             "%fused_page_attention": "paged_attention"}
    seen = set()
    for name, _, _, kernel in ev.ops:
        call = calls.get(name.split(".")[0])
        if call:
            assert trace_reduce.classify(name, "") == kernel == call
            seen.add(call)
    assert seen == set(calls.values())
    s = trace_reduce.reduce(ev)
    assert len(s.steps) == 1
    assert set(s.kernel_ns) == {"ecc_qmatmul", "paged_attention",
                                "ecc_decode"}
    assert 0 < s.busy_ns <= s.window_ns
    assert s.steps[0][2] <= s.busy_ns
    assert sum(s.kernel_ns.values()) <= s.steps[0][2]
    assert not any(g.startswith("while") for g in s.op_ns)
    assert "copy u8[800,16,20,128]" in s.op_ns


# ---------------------------------------------------------------------------
# discovery: a new configuration, mix, cell and metric are files alone
# ---------------------------------------------------------------------------


def make_root(tmp_path, *, cell="tiny-cell", rate=3.0, fill=2.0,
              config="tiny.json"):
    root = tmp_path / "root"
    for sub in ("configs", "traffic", "cells", "metrics"):
        (root / "bench" / sub).mkdir(parents=True)
    shutil.copy(DATA / config, root / "bench" / "configs" / "tiny.json")
    shutil.copy(DATA / "tiny-mix.json",
                root / "bench" / "traffic" / "tiny-mix.json")
    (root / "bench" / "cells" / f"{cell}.json").write_text(
        json.dumps({"rate_per_s": rate, "fill_s": fill}))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    bench["configs"] = [{"name": "tiny", "source": "tests",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "tests"}]
    bench["workloads"] = [{"name": cell, "config": "tiny",
                           "traffic": "tiny-mix", "chips": 1,
                           "why": "tests"}]
    for m in bench["per_layer"]:
        m["workloads"] = [cell]
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    return root


def test_new_config_mix_and_metric_are_found_from_files(tmp_path):
    root = make_root(tmp_path)
    (root / "bench" / "metrics" / "steps_seen.py").write_text(
        "def read(run):\n    return float(len(run.steps))\n")
    bench = json.loads((root / "BENCHMARK.json").read_text())
    bench["per_layer"].append({
        "name": "steps_seen", "unit": "steps", "better": "higher",
        "source": "program_counter", "layer": "front-end",
        "moves": "output_tok_s", "workloads": ["tiny-cell"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    cell = spec.load_cell(root, "tiny-cell")
    assert cell.config["name"] == "tiny"
    assert cell.traffic["block"] == 8
    assert cell.params == {"rate_per_s": 3.0, "fill_s": 2.0}
    assert "steps_seen" in [m["name"] for m in cell.per_layer]
    assert spec.reader(root, "steps_seen")(synthetic_run()) == 100.0
    with pytest.raises(KeyError):
        spec.load_cell(root, "no-such-cell")


def test_every_benchmark_metric_has_a_reader():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(spec.reader(ROOT, m["name"])), m["name"]
    for w in bench["workloads"]:
        cell = spec.load_cell(ROOT, w["name"])
        assert cell.params["rate_per_s"] > 0


# ---------------------------------------------------------------------------
# the chip gate, and the check against broken and lower-precision paths
# ---------------------------------------------------------------------------


def test_exits_nonzero_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload",
         "qwen4b-chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_exits_nonzero_without_the_program(tmp_path):
    (tmp_path / "bench").mkdir()
    shutil.copytree(ROOT / "bench", tmp_path / "bench", dirs_exist_ok=True,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"), "--workload",
         "qwen4b-chat", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


@pytest.fixture(scope="module")
def own_cache(tmp_path_factory):
    """Keeps this process's compiled programs out of the checkout's cache."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(tmp_path_factory.mktemp("jax_cache")))


@pytest.fixture(scope="module")
def tiny_cell(tmp_path_factory, own_cache):
    return spec.load_cell(make_root(tmp_path_factory.mktemp("tiny")),
                          "tiny-cell")


def tiny_run(cell, **kw):
    from bench import run
    return run.run_cell(cell, seed=kw.pop("seed", 2**32 + 3),
                        seconds=kw.pop("seconds", 3.0), trace=False, **kw)


def test_sound_run_is_correct(tiny_cell):
    res = tiny_run(tiny_cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "ttft_p50_s", "itl_p95_ms",
                                   "output_tok_s"}
    assert list(res)[-1] == "checks"
    assert set(res["checks"]) == {"ecc_flags", "requests_checked",
                                  "max_logit_gap", "mean_logit_gap",
                                  "gap_excess"}
    assert res["device"]["platform"] == "cpu"


def test_lower_precision_control_reads_worse(tmp_path, own_cache):
    """At a size a test can hold, the int8-activation control reads
    worse than the program on each seed, but the two overlap across seeds
    in both the widest and the mean gap, so no limit at this size lies
    between them (PERF.md); the cell's limits are set at its own size.
    Here the control has to read worse than the program on the same
    seed."""
    root = make_root(tmp_path, rate=8.0, config="tiny-wide.json")
    cell = spec.load_cell(root, "tiny-cell")
    for seed in (2**32 + 11, 2**32 + 12):
        prog = tiny_run(cell, seed=seed, seconds=8.0)["readings"]
        ctrl = tiny_run(cell, seed=seed, seconds=8.0,
                        control="int8-act")["readings"]
        assert ctrl["mean_logit_gap"] > prog["mean_logit_gap"], (prog, ctrl)


def broken(fault: str):
    """A wrapper that breaks the compiled serve step underneath the
    front-end, one way for each fault a one-chip serving cell can have."""
    import jax.numpy as jnp

    def wrap(serve_step):
        def unchanged_state(enc, cache, tokens, pos):
            logits, _, flags = serve_step(enc, cache, tokens, pos)
            return logits, cache, flags

        def half_batch(enc, cache, tokens, pos):
            b = tokens.shape[0]
            return serve_step(enc, cache, tokens.at[b // 2:].set(0), pos)

        def altered_token(enc, cache, tokens, pos):
            logits, new_cache, flags = serve_step(enc, cache, tokens, pos)
            top = jnp.argmax(logits[:, -1, :], axis=-1)
            wrong = (top + 1) % logits.shape[-1]
            bump = jnp.zeros_like(logits[:, -1, :]).at[
                jnp.arange(logits.shape[0]), wrong].set(1e4)
            return (logits.at[:, -1, :].add(bump.astype(logits.dtype)),
                    new_cache, flags)

        return {"unchanged_state": unchanged_state, "half_batch": half_batch,
                "altered_token": altered_token}[fault]
    return wrap


@pytest.mark.parametrize("fault", ["unchanged_state", "half_batch",
                                   "altered_token"])
def test_broken_serve_step_fails(tiny_cell, fault):
    res = tiny_run(tiny_cell, wrap=broken(fault))
    assert not res["correct"], (fault, res["checks"])


def test_config_the_program_does_not_run_is_refused(tmp_path, own_cache):
    """A rotary base or norm epsilon that the program would not run is
    refused before anything is built, so the reference cannot drift from
    the program unseen."""
    root = make_root(tmp_path)
    conf = json.loads((root / "bench" / "configs" / "tiny.json").read_text())
    conf["rope_theta"] = 5e6                     # the program keeps 1e4
    (root / "bench" / "configs" / "tiny.json").write_text(json.dumps(conf))
    with pytest.raises(SystemExit, match="rope_theta"):
        tiny_run(spec.load_cell(root, "tiny-cell"))
