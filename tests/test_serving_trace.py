"""Spans and counters of the serving front-end's step.

Each ``step`` event carries the wall time of the step's phases, the
compile and GC time inside it, and the eager page programs it issued;
a profile of the same steps holds one ``serve.step`` span per event,
with the ``serve.*`` phase spans nested inside. The benchmark's readers
of these fields are checked on hand-made runs.
"""
import dataclasses
import gc
import glob
import pathlib
import sys

import jax
import pytest

from repro.serving import frontend, kvcache, protected, telemetry

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import spec, timeline  # noqa: E402

PHASES = ("admit", "heal", "inputs", "dispatch", "wait", "fetch", "advance",
          "finish")
WALL = tuple(f"{p}_ms" for p in PHASES) + (
    "pages_ms", "compile_ms", "gc_ms", "step_ms")


@pytest.fixture(scope="module")
def rig(plan_setup):
    cfg, plan, enc = plan_setup(arch="deepseek-7b", backend="xla")
    kvp = dataclasses.replace(kvcache.get_kv_policy("in-place"),
                              per_slot_flags=True)

    def fresh_step():
        return jax.jit(protected.make_serve_step(cfg, plan=plan,
                                                 with_flags=True,
                                                 kv_policy=kvp))

    return cfg, plan, enc, kvp, fresh_step, fresh_step()


def _waves(cfg, seed=11):
    return frontend.make_waves(seed=seed, n_waves=2, wave_size=3,
                               vocab=cfg.vocab, prompt_len=(3, 6),
                               max_new=(2, 4), gap_steps=4)


def _frontend(rig, serve_step=None, **kw):
    cfg, plan, enc, kvp, _, step = rig
    return frontend.ServingFrontend(cfg, enc, plan=plan, slots=2,
                                    max_len=32, kv_policy=kvp,
                                    serve_step=serve_step or step, **kw)


def _steps(events):
    return [e for e in events if e["event"] == "step"]


def test_every_step_event_carries_the_span_fields(rig):
    cfg, plan, enc, kvp, _, step = rig
    events, _, _ = frontend.run_burst(cfg, enc, plan=plan, waves=_waves(cfg),
                                      slots=2, max_len=32, kv_policy=kvp,
                                      serve_step=step)
    steps = _steps(events)
    assert steps
    for e in steps:
        for k in WALL:
            assert isinstance(e[k], float) and e[k] >= 0.0, (k, e)
        assert isinstance(e["page_ops"], int) and e["page_ops"] >= 0
        assert sum(e[f"{p}_ms"] for p in PHASES) <= e["step_ms"] + 1.0, e
        assert e["wait_ms"] <= e["step_ms"]
        assert e["pages_ms"] <= e["admit_ms"] + e["finish_ms"] + 1e-6
    # one table-row program per admission; a finish parks the row and
    # zeroes the released pages: two programs
    n_admit = sum(e["event"] == "admit" for e in events)
    n_finish = sum(e["event"] == "finish" for e in events)
    assert sum(e["page_ops"] for e in steps) == n_admit + 2 * n_finish
    view = telemetry.deterministic_view(steps)
    assert all("page_ops" in v and not any(k in v for k in WALL)
               for v in view)


def _profile_events(trace_dir):
    from jax.profiler import ProfileData
    path = sorted(glob.glob(str(trace_dir / "**" / "*.xplane.pb"),
                            recursive=True))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def test_profile_holds_one_step_span_per_step_event(rig, tmp_path):
    fe = _frontend(rig)
    for req in _waves(rig[0]):
        fe.submit(dataclasses.replace(req, arrival_step=0))
    fe.step()                                   # compile outside the trace
    n0 = len(fe.telemetry.events)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for _ in range(3):
            fe.step()
    finally:
        jax.profiler.stop_trace()
    spans = _profile_events(tmp_path)
    steps = sorted((s, e, st["step"]) for n, s, e, st in spans
                   if n == "serve.step")
    want = [e["step"] for e in _steps(fe.telemetry.events[n0:])]
    assert [n for _, _, n in steps] == want == [1, 2, 3]
    inner = [(n, s, e) for n, s, e, _ in spans if n != "serve.step"]
    names = {n for n, _, _ in inner}
    assert {f"serve.{p}" for p in ("admit", "inputs", "dispatch", "wait",
                                   "fetch", "advance", "finish")} <= names
    for n, s, e in inner:
        assert sum(s0 <= s and e <= e0 for s0, e0, _ in steps) == 1, n
    assert not any(n.startswith("bench.") for n in names)


def test_compile_counts_in_the_step_that_compiled(rig):
    fe = _frontend(rig, serve_step=rig[4]())       # a freshly jitted step
    fe.submit(frontend.Request(rid=0, prompt=(3, 1, 4), max_new=8))
    for _ in range(3):
        fe.step()
    first, _, warm = _steps(fe.telemetry.events)
    assert first["compile_ms"] > 0
    assert any(site.startswith("dispatch:")
               for site in first["compile_sites_ms"])
    assert warm["compile_ms"] == 0.0
    assert "compile_sites_ms" not in warm


def test_gc_pause_lands_in_its_step(rig):
    step = rig[5]
    collect = {"on": False}

    def serve_step(*args):
        if collect["on"]:
            gc.collect()
        return step(*args)

    fe = _frontend(rig, serve_step=serve_step)
    fe.step()
    collect["on"] = True
    fe.step()
    e = _steps(fe.telemetry.events)[-1]
    assert 0.0 < e["gc_ms"] <= e["dispatch_ms"]


def test_listeners_are_registered_once_per_process(rig):
    from jax._src import monitoring

    def count():
        return (len(monitoring.get_event_time_span_listeners()),
                gc.callbacks.count(telemetry._on_gc))

    _frontend(rig).step()
    before = count()
    assert before[1] == 1
    for _ in range(30):
        fe = _frontend(rig)
        fe.step()
        fe.telemetry.close()
    assert count() == before


def test_span_outside_a_step_is_only_a_trace_span():
    col = telemetry.TelemetryCollector()
    with col.span("pages"):
        pass
    col.count("page_ops")
    with col.step(7) as timing:
        with col.span("pages"):
            pass
        col.count("page_ops")
        col.count("page_ops")
    assert timing["page_ops"] == 2 and timing["pages_ms"] >= 0.0
    assert col.events == []


# ---------------------------------------------------------------------------
# the benchmark's readers of the step fields
# ---------------------------------------------------------------------------


def _run(step_fields, w=(0.0, 10.0)):
    """Steps 0..n-1 one second apart with the given step-event fields;
    step 0 starts before the window and is not counted."""
    steps, events = {}, []
    for n, f in enumerate(step_fields):
        steps[n] = (n - 0.5, n + 0.4)
        events.append({"event": "step", "step": n, **f})
    return timeline.Run(steps=steps, events=events, due={}, w0=w[0],
                        w1=w[1], slots=2, allocatable=4, model={})


def _fields(step_ms=200.0, wait_ms=193.0, pages_ms=0.0, compile_ms=0.0,
            finish_ms=0.1):
    return dict(step_ms=step_ms, wait_ms=wait_ms, pages_ms=pages_ms,
                compile_ms=compile_ms, finish_ms=finish_ms, page_ops=0)


def read(metric, run):
    return spec.reader(ROOT, metric)(run)


@pytest.mark.parametrize("metric,want", [
    ("step_host_ms", (7.0 + 7.0 + 2507.0 + 7.0) / 4),
    ("page_ops_ms", (4.0 + 0.0 + 2500.0 + 0.0) / 4),
    ("window_compile_s", 0.0),
])
def test_readers_count_a_stall_inside_finish(metric, want):
    fields = [_fields(compile_ms=900.0, pages_ms=50.0),   # before window
              _fields(pages_ms=4.0), _fields(),
              # a 2.5 s stall in the page programs of a finish
              _fields(step_ms=2700.0, finish_ms=2500.2, pages_ms=2500.0),
              _fields()]
    assert read(metric, _run(fields, w=(0.0, 4.5))) == pytest.approx(want)


def test_window_compile_reads_the_compiling_step():
    fields = [_fields(), _fields(compile_ms=1500.0), _fields()]
    assert read("window_compile_s", _run(fields, w=(0.0, 3.0))) == \
        pytest.approx(1.5)


@pytest.mark.parametrize("metric", ["step_host_ms", "page_ops_ms",
                                    "window_compile_s"])
def test_readers_find_nothing_in_a_program_without_the_fields(metric):
    """A program whose step events predate these fields reads as nothing,
    and does not raise."""
    fields = [{"step_ms": 200.0}] * 4
    assert read(metric, _run(fields, w=(0.0, 4.0))) is None
