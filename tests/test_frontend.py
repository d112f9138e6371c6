"""Request-level serving front-end: lifecycle, page-pool accounting,
telemetry, and bit-determinism.

Two layers of coverage:

* hypothesis property tests over the HOST-side state machine (queue +
  allocator + lifecycle accounting, no model) — random admission/finish
  interleavings can never leak pages, evicted slots are re-usable. These
  skip cleanly where hypothesis isn't installed (CI has it).
* deterministic real-model tests through one jitted serve step — a
  seeded burst replay is bit-identical across two runs, pool accounting
  is exact after drain, and per-request KV fault attribution surfaces in
  the telemetry.
"""
import dataclasses
import json

import jax
import pytest

from repro.serving import frontend, kvcache, protected, telemetry


# ---------------------------------------------------------------------------
# host-side unit tests (no model)
# ---------------------------------------------------------------------------


def test_request_validation_and_queue_rejects():
    with pytest.raises(ValueError, match="empty"):
        frontend.Request(rid=0, prompt=(), max_new=2)
    with pytest.raises(ValueError, match="max_new"):
        frontend.Request(rid=0, prompt=(1,), max_new=0)
    q = frontend.RequestQueue(max_total_tokens=32, max_pages=2,
                              page_size=16)
    ok = frontend.Request(rid=1, prompt=(1, 2, 3), max_new=4)
    assert q.push(ok) is None and len(q) == 1
    too_long = frontend.Request(rid=2, prompt=tuple(range(1, 31)),
                                max_new=8)
    assert "max_len" in q.push(too_long)
    q2 = frontend.RequestQueue(max_total_tokens=64, max_pages=2,
                               page_size=16)
    too_wide = frontend.Request(rid=3, prompt=tuple(range(1, 41)),
                                max_new=20)
    assert "allocatable" in q2.push(too_wide)
    assert len(q2) == 0 and q.pop() is ok


def test_percentile_and_deterministic_view():
    assert telemetry.percentile([], 99) is None
    assert telemetry.percentile([5.0], 50) == 5.0
    xs = list(range(1, 101))
    assert telemetry.percentile(xs, 50) == 50
    assert telemetry.percentile(xs, 99) == 99
    assert telemetry.percentile(xs, 100) == 100
    ev = [{"event": "step", "step": 0, "step_ms": 1.23, "ttft_s": 9.9,
           "pool_free": 4}]
    assert telemetry.deterministic_view(ev) == [
        {"event": "step", "step": 0, "pool_free": 4}]


def test_collector_streams_jsonl(tmp_path):
    path = tmp_path / "t.jsonl"
    with telemetry.TelemetryCollector(str(path)) as col:
        col.emit("enqueue", rid=0, step=0, prompt_len=3, max_new=2)
        col.emit("step", step=0, pool_free=4, step_ms=0.5)
    lines = [json.loads(l) for l in path.read_text().splitlines()]
    assert lines == col.events and len(lines) == 2
    assert lines[0]["event"] == "enqueue"


# ---------------------------------------------------------------------------
# hypothesis: the lifecycle state machine never leaks pages
# ---------------------------------------------------------------------------

try:
    import hypothesis as hyp
    import hypothesis.strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:     # local images may lack it; CI installs it
    HAVE_HYPOTHESIS = False


class _LifecycleSim:
    """Host-side mirror of the front-end's accounting: FIFO queue,
    slot admission, page alloc at admit, free+park at finish. No model —
    'decode' just counts steps, so hypothesis can hammer interleavings."""

    def __init__(self, slots, n_pages, page_size, max_len):
        self.alloc = kvcache.PageAllocator(n_pages, reserved=slots)
        self.queue = frontend.RequestQueue(max_len,
                                           self.alloc.free_count,
                                           page_size)
        self.page_size = page_size
        self.slots = [None] * slots
        self.slot_history = [0] * slots
        self.finished = []

    def submit(self, req):
        return self.queue.push(req)

    def admit(self):
        while self.queue.peek() is not None:
            free = next((i for i, s in enumerate(self.slots)
                         if s is None), None)
            if free is None:
                return
            need = kvcache.pages_needed(self.queue.peek().total_tokens,
                                        self.page_size)
            if not self.alloc.can(need):
                return
            req = self.queue.pop()
            self.slots[free] = (req, self.alloc.alloc(need))
            self.slot_history[free] += 1

    def finish(self, slot):
        req, pages = self.slots[slot]
        self.alloc.free(pages)
        self.slots[slot] = None
        self.finished.append(req.rid)


def _never_leak_body(lengths, rnd):
    """Property body: for ANY request mix and ANY finish order, after the
    last request drains the allocator's free count equals its initial
    value, and no admission ever double-books a page."""
    sim = _LifecycleSim(slots=3, n_pages=9, page_size=8, max_len=32)
    initial_free = sim.alloc.free_count
    reqs = [frontend.Request(rid=i, prompt=tuple(range(1, pl + 1)),
                             max_new=mn)
            for i, (pl, mn) in enumerate(lengths)]
    submitted = [r for r in reqs if sim.submit(r) is None]
    n_done = 0
    while n_done < len(submitted):
        sim.admit()
        live = [i for i, s in enumerate(sim.slots) if s is not None]
        assert live or sim.queue.peek() is None, "deadlock with work queued"
        # occupancy never exceeds the pool, reserved pages never leave
        in_flight = [p for i in live for p in sim.slots[i][1]]
        assert len(in_flight) == len(set(in_flight)), "double-booked page"
        assert all(p >= 3 for p in in_flight), "parking page allocated"
        assert sim.alloc.free_count == initial_free - len(in_flight)
        sim.finish(rnd.choice(live))
        n_done += 1
    assert sim.alloc.free_count == initial_free        # nothing leaked
    assert sorted(sim.finished) == sorted(r.rid for r in submitted)


def _slot_reuse_body(rnd):
    """Property body: slots cycle — with more requests than slots and
    random finish order, every evicted slot takes the next queued request
    (a slot can keep one long tenant while the other cycles, so the
    property is per eviction, not per slot)."""
    sim = _LifecycleSim(slots=2, n_pages=8, page_size=8, max_len=32)
    for i in range(8):
        assert sim.submit(frontend.Request(
            rid=i, prompt=(1, 2, 3), max_new=2)) is None
    sim.admit()
    for _ in range(8):
        live = [i for i, s in enumerate(sim.slots) if s is not None]
        slot = rnd.choice(live)
        queued = sim.queue.peek() is not None
        before = sim.slot_history[slot]
        sim.finish(slot)
        sim.admit()
        assert sim.slot_history[slot] == before + queued, sim.slot_history
    assert sum(sim.slot_history) == 8 and len(sim.finished) == 8
    assert sim.alloc.free_count == 6


if HAVE_HYPOTHESIS:

    @hyp.given(
        st.lists(st.tuples(st.integers(1, 24), st.integers(1, 12)),
                 min_size=1, max_size=24),
        st.randoms(use_true_random=False))
    @hyp.settings(max_examples=60, deadline=None)
    def test_random_interleavings_never_leak_pages(lengths, rnd):
        _never_leak_body(lengths, rnd)

    @hyp.given(st.randoms(use_true_random=False))
    @hyp.settings(max_examples=25, deadline=None)
    def test_evicted_slots_are_reusable(rnd):
        _slot_reuse_body(rnd)

else:   # keep one seeded spot-check of each invariant without hypothesis

    def test_random_interleavings_never_leak_pages():
        import random
        rnd = random.Random(7)
        lengths = [(rnd.randint(1, 24), rnd.randint(1, 12))
                   for _ in range(16)]
        _never_leak_body(lengths, rnd)

    def test_evicted_slots_are_reusable():
        import random
        _slot_reuse_body(random.Random(13))


# ---------------------------------------------------------------------------
# real-model: one jitted step, burst replay, fault attribution
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def burst_rig(plan_setup):
    cfg, plan, enc = plan_setup(arch="deepseek-7b", backend="xla")
    kvp = dataclasses.replace(kvcache.get_kv_policy("in-place"),
                              per_slot_flags=True)
    step = jax.jit(protected.make_serve_step(cfg, plan=plan,
                                             with_flags=True,
                                             kv_policy=kvp))
    return cfg, plan, enc, kvp, step


def _small_waves(cfg, seed=11):
    return frontend.make_waves(seed=seed, n_waves=2, wave_size=3,
                               vocab=cfg.vocab, prompt_len=(3, 6),
                               max_new=(2, 4), gap_steps=4)


def test_burst_drains_with_exact_pool_accounting(burst_rig):
    cfg, plan, enc, kvp, step = burst_rig
    events, summ, results = frontend.run_burst(
        cfg, enc, plan=plan, waves=_small_waves(cfg), slots=2,
        max_len=32, kv_policy=kvp, serve_step=step)
    assert summ["requests"]["finished"] == summ["requests"]["submitted"] == 6
    assert summ["pool"]["leaked_pages"] == 0
    assert summ["pool"]["final_free"] == summ["pool"]["initial_free"]
    assert summ["due"]["total"] == 0                  # no faults injected
    assert summ["gen_tokens"] == sum(len(v) for v in results.values())
    # lifecycle ordering per request: enqueue <= admit < first <= finish
    by_rid = {}
    for e in events:
        if "rid" in e:
            by_rid.setdefault(e["rid"], {})[e["event"]] = e
    assert len(by_rid) == 6
    for rid, evs in by_rid.items():
        assert set(evs) == {"enqueue", "admit", "first_token", "finish"}
        assert (evs["enqueue"]["step"] <= evs["admit"]["step"]
                < evs["first_token"]["step"] <= evs["finish"]["step"])
        assert len(results[rid]) == evs["enqueue"]["max_new"]
        assert isinstance(evs["first_token"]["ttft_steps"], int)
        assert evs["first_token"]["ttft_steps"] >= 0


def test_seeded_burst_replay_is_bit_deterministic(burst_rig):
    """The acceptance: same seed, same compiled step -> identical token
    streams AND identical deterministic telemetry views, twice."""
    cfg, plan, enc, kvp, step = burst_rig
    runs = [frontend.run_burst(cfg, enc, plan=plan,
                               waves=_small_waves(cfg), slots=2,
                               max_len=32, kv_policy=kvp, serve_step=step)
            for _ in range(2)]
    (ev1, s1, r1), (ev2, s2, r2) = runs
    assert r1 == r2
    assert telemetry.deterministic_view(ev1) == \
        telemetry.deterministic_view(ev2)
    # and the workload itself is seed-stable
    w1 = _small_waves(cfg)
    w2 = _small_waves(cfg)
    assert w1 == w2
    assert _small_waves(cfg, seed=12) != w1


def test_faulty_burst_attributes_due_per_request(burst_rig):
    """Injected KV faults surface as per-request (corrected, DUE) counts
    in finish events — and the faulted replay is ALSO deterministic."""
    cfg, plan, enc, kvp, step = burst_rig
    kw = dict(plan=plan, waves=_small_waves(cfg), slots=2, max_len=32,
              kv_policy=kvp, serve_step=step, fault_rate=2e-3,
              fault_seed=3)
    ev1, s1, r1 = frontend.run_burst(cfg, enc, **kw)
    ev2, s2, r2 = frontend.run_burst(cfg, enc, **kw)
    assert r1 == r2
    assert telemetry.deterministic_view(ev1) == \
        telemetry.deterministic_view(ev2)
    assert s1["due"]["corrected_total"] > 0   # in-place corrects singles
    assert s1["pool"]["leaked_pages"] == 0    # faults never leak pages
    fin = [e for e in ev1 if e["event"] == "finish"]
    assert sum(f["kv_corrected"] for f in fin) == s1["due"]["corrected_total"]


def test_summary_and_csv_roundtrip(burst_rig, tmp_path):
    cfg, plan, enc, kvp, step = burst_rig
    tpath = tmp_path / "telemetry.jsonl"
    events, summ, _ = frontend.run_burst(
        cfg, enc, plan=plan, waves=_small_waves(cfg), slots=2, max_len=32,
        kv_policy=kvp, serve_step=step, telemetry_path=str(tpath))
    streamed = [json.loads(l) for l in tpath.read_text().splitlines()]
    assert streamed == events
    assert summ["schema"] == telemetry.SUMMARY_SCHEMA
    for k in ("p50", "p95", "p99"):
        assert summ["ttft_steps"][k] is not None
        assert summ["per_token_ms"][k] is not None
    csv_path = tmp_path / "requests.csv"
    telemetry.write_requests_csv(events, str(csv_path))
    rows = csv_path.read_text().splitlines()
    assert len(rows) == 1 + summ["requests"]["submitted"]
    assert rows[0].startswith("rid,enqueue_step,prompt_len")
    jpath = tmp_path / "summary.json"
    telemetry.write_summary(summ, str(jpath))
    assert json.loads(jpath.read_text()) == summ


def test_per_slot_flags_supported_on_every_attention_path():
    """PR 7 forced per-slot attribution onto the reference path only (the
    fused kernel reduced flags to scalars in-grid); the kernels now emit
    per-row flags, so every policy accepts — and the front-end forces —
    ``per_slot_flags``."""
    for name in ("in-place", "in-place-fused", "in-place-chunked"):
        p = dataclasses.replace(kvcache.get_kv_policy(name),
                                per_slot_flags=True)
        assert p.per_slot_flags


# ---------------------------------------------------------------------------
# prefix sharing: refcount state machine + real-model CoW
# ---------------------------------------------------------------------------


class _SharingSim:
    """Host-side mirror of the sharing accounting: slots hold page
    references, a prefix index holds its OWN references, and random
    fork (retain) / publish / evict / finish interleavings must keep the
    allocator conserved — no leaks, no double frees."""

    def __init__(self, slots, n_pages, reserved):
        self.alloc = kvcache.PageAllocator(n_pages, reserved=reserved)
        self.allocatable = self.alloc.free_count
        self.slots = [None] * slots         # slot -> list of held pids
        self.index = []                     # pids the cache holds a ref on

    def check(self):
        # conservation + exact refcounts: each page's count equals the
        # number of mappings (slot holdings + index pins) that exist
        assert self.alloc.free_count + self.alloc.live_count \
            == self.allocatable
        held: dict = {}
        for pages in self.slots:
            for p in pages or ():
                held[p] = held.get(p, 0) + 1
        for p in self.index:
            held[p] = held.get(p, 0) + 1
        for p, n in held.items():
            assert self.alloc.refcount(p) == n, (p, n)
        assert self.alloc.live_count == len(held)

    def admit(self, free_slot, n_fresh, n_shared):
        shared = self.index[:n_shared]
        if not self.alloc.can(n_fresh):
            return
        fresh = self.alloc.alloc(n_fresh)
        self.alloc.retain(shared)
        self.slots[free_slot] = list(shared) + list(fresh)

    def publish(self, slot, j):
        pid = self.slots[slot][j]
        if pid in self.index:
            return
        self.alloc.retain([pid])
        self.index.append(pid)

    def evict(self, j):
        pid = self.index.pop(j)
        self.alloc.free([pid])

    def finish(self, slot):
        self.alloc.free(self.slots[slot])
        self.slots[slot] = None


def _sharing_refcount_body(rnd):
    sim = _SharingSim(slots=3, n_pages=12, reserved=2)
    for _ in range(60):
        ops = []
        free_slots = [i for i, s in enumerate(sim.slots) if s is None]
        live = [i for i, s in enumerate(sim.slots) if s is not None]
        if free_slots:
            ops.append(("admit", free_slots))
        if live:
            ops.append(("publish", live))
            ops.append(("finish", live))
        if sim.index:
            ops.append(("evict", None))
        op, arg = rnd.choice(ops)
        if op == "admit":
            sim.admit(rnd.choice(arg), rnd.randint(1, 3),
                      rnd.randint(0, len(sim.index)))
        elif op == "publish":
            slot = rnd.choice(arg)
            sim.publish(slot, rnd.randrange(len(sim.slots[slot])))
        elif op == "evict":
            sim.evict(rnd.randrange(len(sim.index)))
        elif op == "finish":
            sim.finish(rnd.choice(arg))
        sim.check()
    # drain: finish every slot, drop the cache -> everything comes back
    for i, s in enumerate(sim.slots):
        if s is not None:
            sim.finish(i)
    while sim.index:
        sim.evict(0)
    sim.check()
    assert sim.alloc.free_count == sim.allocatable
    assert sim.alloc.live_count == 0
    # and the pool rejects a stale free explicitly
    with pytest.raises(ValueError, match="double free"):
        sim.alloc.free([2])


if HAVE_HYPOTHESIS:

    @hyp.given(st.randoms(use_true_random=False))
    @hyp.settings(max_examples=40, deadline=None)
    def test_sharing_interleavings_never_leak_or_double_free(rnd):
        _sharing_refcount_body(rnd)

else:

    def test_sharing_interleavings_never_leak_or_double_free():
        import random
        _sharing_refcount_body(random.Random(29))


def _cow_waves(cfg, seed=11):
    """Three staggered single-request waves over ONE 16-token prompt
    (page_size 16 -> one full shared page, prompt ends exactly on the
    page boundary so every sharer takes the CoW path). The gap outlasts
    the first request's prefill, so its published page is in the index
    before the next admission."""
    return frontend.make_waves(seed=seed, n_waves=3, wave_size=1,
                               vocab=cfg.vocab, prompt_len=(0, 0),
                               max_new=(2, 4), gap_steps=20,
                               shared_prefix_len=16)


def _savings_waves(cfg, seed=11):
    """One publisher, then TWO concurrent sharers over a 32-token (two
    full pages) shared prefix plus a 1-2 token per-request suffix — the
    suffix keeps the first write off the shared pages (no CoW), so each
    sharer's budget is 1 fresh page instead of 3."""
    reqs = frontend.make_waves(seed=seed, n_waves=3, wave_size=1,
                               vocab=cfg.vocab, prompt_len=(1, 2),
                               max_new=(2, 4), gap_steps=40,
                               shared_prefix_len=32)
    # rebase into publisher @0 + a simultaneous sharer pair @40
    return [reqs[0]] + [dataclasses.replace(r, arrival_step=40)
                        for r in reqs[1:]]


def test_prefix_sharing_is_bit_identical_and_saves_pages(burst_rig):
    """The sharing acceptance: identical token streams with sharing on
    vs off, measured page savings for concurrent shared-prefix requests,
    zero leaked pages, and a bit-deterministic replay."""
    cfg, plan, enc, kvp, step = burst_rig
    waves = _savings_waves(cfg)
    kw = dict(plan=plan, waves=waves, slots=2, max_len=48, kv_policy=kvp,
              serve_step=step)
    ev_solo, s_solo, r_solo = frontend.run_burst(cfg, enc, **kw)
    ev_sh, s_sh, r_sh = frontend.run_burst(cfg, enc, prefix_sharing=True,
                                           **kw)
    assert r_sh == r_solo                  # sharing never changes tokens
    assert s_sh["pool"]["leaked_pages"] == 0
    assert s_solo["sharing"]["pages_shared"] == 0
    sh = s_sh["sharing"]
    assert sh["pages_shared"] == 4         # 2 sharers x 2 full pages
    assert sh["tokens_reused"] == 64
    assert sh["cow_copies"] == 0           # suffix starts off-page
    assert sh["pages_allocated_total"] < sh["solo_pages_total"]
    # the headline: two concurrent sharers peak below the solo twin
    assert (s_sh["pool"]["peak_pages_in_use"]
            < s_solo["pool"]["peak_pages_in_use"])
    assert s_sh["steps"] < s_solo["steps"]  # reused prefill = fewer steps
    # cached pages are pinned on purpose, not leaked
    assert s_sh["pool"]["cached_pages"] > 0
    ev2, s2, r2 = frontend.run_burst(cfg, enc, prefix_sharing=True, **kw)
    assert r2 == r_sh
    assert telemetry.deterministic_view(ev2) == \
        telemetry.deterministic_view(ev_sh)
    admits = [e for e in ev_sh if e["event"] == "admit"]
    assert admits[0]["pages_shared"] == 0
    assert all(a["pages_shared"] == 2 and a["cow_copied"] == 0
               for a in admits[1:])


def test_cow_on_fully_shared_prompt(burst_rig, tmp_path):
    """A prompt that IS a published prefix (ends on the page boundary)
    re-consumes its last token, so the last shared page gets a private
    CoW clone — tokens still bit-identical to the no-sharing run; the
    sharing fields survive the JSONL stream and the per-request CSV."""
    cfg, plan, enc, kvp, step = burst_rig
    waves = _cow_waves(cfg)
    kw = dict(plan=plan, waves=waves, slots=2, max_len=32, kv_policy=kvp,
              serve_step=step)
    _, s_solo, r_solo = frontend.run_burst(cfg, enc, **kw)
    tpath = tmp_path / "telemetry.jsonl"
    ev_sh, s_sh, r_sh = frontend.run_burst(cfg, enc, prefix_sharing=True,
                                           telemetry_path=str(tpath),
                                           **kw)
    assert [json.loads(l) for l in tpath.read_text().splitlines()] == ev_sh
    csv_path = tmp_path / "requests.csv"
    telemetry.write_requests_csv(ev_sh, str(csv_path))
    rows = csv_path.read_text().splitlines()
    header = rows[0].split(",")
    for col in ("pages_shared", "tokens_reused", "cow_copied"):
        assert col in header
    shared_col = [r.split(",")[header.index("pages_shared")]
                  for r in rows[1:]]
    assert shared_col == ["0", "1", "1"]
    assert r_sh == r_solo
    assert s_sh["pool"]["leaked_pages"] == 0
    admits = [e for e in ev_sh if e["event"] == "admit"]
    assert admits[0]["pages_shared"] == 0
    assert all(a["pages_shared"] == 1 and a["cow_copied"] == 1
               for a in admits[1:])
    cows = [e for e in ev_sh if e["event"] == "cow"]
    assert len(cows) == len(admits) - 1 == s_sh["sharing"]["cow_copies"]
    # the clone is a PRIVATE page: src is the cached page, dst fresh
    assert all(c["src"] != c["dst"] for c in cows)
    assert s_sh["sharing"]["tokens_reused"] == 15 * (len(admits) - 1)


def test_prefix_cache_evicts_lru_by_hit_keeping_hot_prefix(burst_rig):
    """Eviction is LRU-by-*hit*: under pool pressure the prefix that was
    published first but hit most recently SURVIVES, while the
    never-re-hit one is evicted — publication order alone must not decide
    (the regression: an insertion-order eviction would drop the hot
    prefix here)."""
    cfg, plan, enc, kvp, step = burst_rig
    ps = kvp.page_size
    fe = frontend.ServingFrontend(cfg, enc, plan=plan, slots=2,
                                  max_len=2 * ps, n_pages=5,
                                  kv_policy=kvp, serve_step=step,
                                  prefix_sharing=True)
    hot = tuple(range(1, ps + 1))          # published FIRST (oldest)
    cold = tuple(range(101, 101 + ps))     # published second
    for rid, prompt in ((0, hot), (1, cold)):
        fe.submit(frontend.Request(rid=rid, prompt=prompt, max_new=2))
        fe.run()
    assert set(fe._prefix_index) == {hot, cold}
    # re-hit the old prefix: a sharer maps its cached page
    fe.submit(frontend.Request(rid=2, prompt=hot + (7, 8, 9), max_new=2))
    fe.run()
    admit = [e for e in fe.telemetry.events
             if e["event"] == "admit" and e["rid"] == 2]
    assert admit[0]["pages_shared"] == 1
    # now force pressure: 2 fresh pages wanted, 1 free -> one eviction
    assert fe.allocator.free_count == 1
    # 15-token prompt + 4 generated spans 2 pages but never completes a
    # page inside the prompt, so it cannot publish a prefix of its own
    fe.submit(frontend.Request(rid=3, prompt=tuple(range(200, 200 + ps - 1)),
                               max_new=4))
    fe.run()
    assert hot in fe._prefix_index         # recently hit -> survives
    assert cold not in fe._prefix_index    # least recently hit -> evicted
    assert len(fe._prefix_index) == 1
    # eviction released exactly the cold page; accounting stays exact
    assert fe.drop_prefix_cache() == 1
    assert fe.allocator.live_count == 0


def test_prefix_cache_drop_releases_pages(burst_rig):
    cfg, plan, enc, kvp, step = burst_rig
    fe = frontend.ServingFrontend(cfg, enc, plan=plan, slots=2,
                                  max_len=32, kv_policy=kvp,
                                  serve_step=step, prefix_sharing=True)
    for req in _cow_waves(cfg):
        fe.submit(dataclasses.replace(req, arrival_step=0))
    fe.run()
    free_with_cache = fe.allocator.free_count
    dropped = fe.drop_prefix_cache()
    assert dropped > 0
    assert fe.allocator.free_count == free_with_cache + dropped
    assert fe.allocator.live_count == 0
    assert fe.drop_prefix_cache() == 0     # idempotent
