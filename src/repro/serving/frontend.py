"""Request-level serving front-end: slot-based continuous batching over
the paged protected KV cache.

Everything below ``make_serve_step`` was batch-shaped until now; this
module adds the request layer — a :class:`RequestQueue` with admission
control, a per-slot lifecycle (prefill -> decode -> finish/evict, pages
freed back to the pool), and a seeded burst-load driver — all driven by
ONE compiled serve step over a churning request mix.

Design points
-------------
* **One compiled step.** Prefill is fed token-by-token through the same
  jitted ``serve_step`` as decode: an active slot's next input token is
  ``prompt[consumed]`` while the prompt lasts, then its own last sampled
  token; ``pos = consumed``. The step that consumes the LAST prompt token
  yields the request's first generated token. No separate prefill
  executable, no recompiles as the mix churns.
* **Parking pages.** Pool pages ``0..slots-1`` are reserved, one per
  slot (:func:`~repro.serving.kvcache.init_paged_cache` with
  ``n_pages``). An idle slot's page-table row points wholly at its own
  parking page, so the keep-alive token it writes each step (pos 0) can
  never scribble on a live request's pages. The
  :class:`~repro.serving.kvcache.PageAllocator` never hands them out.
* **Determinism.** Sampling is greedy argmax; admission is FIFO;
  page allocation is lowest-id-first; fault injection keys fold in the
  logical step. A seeded burst replay is bit-deterministic — asserted via
  :func:`~repro.serving.telemetry.deterministic_view`.
* **Per-request fault attribution.** The front-end forces
  ``per_slot_flags`` on EVERY KV policy — the fused and chunked Pallas
  kernels reduce (corrected, DUE) per batch row in-grid — so
  ``flags["layers_kv"]`` is (n_layers, 2, B) and each finish event
  carries the counts *that request's* cached tokens saw. When the plan
  guards matmuls (``plan.with_abft`` / activation clamps) the same
  per-slot routing applies to the compute channel: a decode step's
  output rows ARE the batch slots, so ``flags["layers_abft"]`` comes
  back (n_layers, 2, B) and finish events carry ``abft_mismatches`` /
  ``clamp_hits`` per request.
* **Prefix sharing + copy-on-write.** With ``prefix_sharing=True`` the
  front-end keeps an index of published full-page prompt prefixes
  (key = the ENTIRE token prefix through that page, since cached K/V at
  any position depends on every token before it). Admission maps index
  hits into the new slot's table via allocator refcounts and skips their
  prefill steps; the index holds its own reference, so cached pages
  survive their publisher. A prompt ending exactly on a shared page
  boundary re-consumes its last token (that step yields the first
  sampled token) and therefore writes into the last shared page — that
  page gets a private copy-on-write clone instead of a reference. Pages
  re-enter the pool (and are zeroed) only when their LAST reference
  drops; under pool pressure admission evicts cached pages LRU-by-hit
  (least recently *hit* prefix first, publication order as tiebreak).
* **Self-healing.** With ``scrub_every > 0`` each matching step runs a
  budgeted scrub pass (``repro.serving.scrubber``) over the encoded
  weights and live KV pages BEFORE the serve compute, so corrected bits
  land before anything decodes them; weight leaves that scrub refuses to
  write back (DUE) go to MILR repair/quarantine when a ``repair_kit`` is
  attached. :meth:`start_migration` drains a plan diff shard-by-shard
  between steps. All of it emits ``scrub`` / ``migrate`` / ``repair``
  telemetry and stays inside the determinism contract.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.config import ArchConfig
from repro.serving import kvcache, scrubber, telemetry
from repro.serving import protected as sp

__all__ = [
    "Request", "RequestQueue", "ServingFrontend",
    "make_waves", "run_burst",
]


@dataclasses.dataclass(frozen=True)
class Request:
    """One generation request: generate up to ``max_new`` tokens after
    ``prompt``. ``arrival_step`` is the logical step the burst driver
    submits it at (0 = immediately)."""
    rid: int
    prompt: tuple
    max_new: int
    arrival_step: int = 0

    def __post_init__(self):
        if len(self.prompt) == 0:
            raise ValueError("empty prompt")
        if self.max_new < 1:
            raise ValueError("max_new must be >= 1")

    @property
    def total_tokens(self) -> int:
        return len(self.prompt) + self.max_new


class RequestQueue:
    """FIFO admission queue. ``push`` validates that the request can EVER
    be served (fits the per-slot table and the allocatable pool) — those
    are rejected outright; transient exhaustion just queues."""

    def __init__(self, max_total_tokens: int, max_pages: int,
                 page_size: int):
        self.max_total_tokens = max_total_tokens
        self.max_pages = max_pages
        self.page_size = page_size
        self._q: deque = deque()

    def __len__(self) -> int:
        return len(self._q)

    def reject_reason(self, req: Request) -> Optional[str]:
        if req.total_tokens > self.max_total_tokens:
            return (f"prompt+max_new {req.total_tokens} exceeds max_len "
                    f"{self.max_total_tokens}")
        need = kvcache.pages_needed(req.total_tokens, self.page_size)
        if need > self.max_pages:
            return (f"needs {need} pages, pool only has "
                    f"{self.max_pages} allocatable")
        return None

    def push(self, req: Request) -> Optional[str]:
        """Queue ``req``; returns a rejection reason instead if it can
        never be admitted."""
        reason = self.reject_reason(req)
        if reason is None:
            self._q.append(req)
        return reason

    def peek(self) -> Optional[Request]:
        return self._q[0] if self._q else None

    def pop(self) -> Request:
        return self._q.popleft()


class _Slot:
    """Mutable per-slot lifecycle state (host side)."""

    __slots__ = ("req", "consumed", "generated", "pages", "enqueue_step",
                 "admit_step", "first_step", "enqueue_s", "first_s",
                 "kv_corrected", "kv_due", "abft_mismatches", "clamp_hits")

    def __init__(self, req: Request, pages, step: int,
                 enqueue_step: int, enqueue_s: float):
        self.req = req
        self.consumed = 0
        self.generated: list = []
        self.pages = pages
        self.enqueue_step = enqueue_step
        self.admit_step = step
        self.first_step: Optional[int] = None
        self.enqueue_s = enqueue_s
        self.first_s: Optional[float] = None
        self.kv_corrected = 0
        self.kv_due = 0
        self.abft_mismatches = 0
        self.clamp_hits = 0


class ServingFrontend:
    """Continuous-batching loop: ``submit`` requests, call :meth:`step`
    (or :meth:`run`) until drained. Emits telemetry throughout; finished
    requests land in :attr:`results` as ``{rid: [token, ...]}``."""

    def __init__(self, cfg: ArchConfig, enc_params, *, plan=None,
                 slots: int = 4, max_len: int = 128,
                 n_pages: Optional[int] = None, kv_policy="in-place",
                 serve_step=None, collector=None, dtype=jnp.bfloat16,
                 act_quant: Optional[str] = None,
                 prefix_sharing: bool = False,
                 scrub_every: int = 0, scrub_weight_leaves: int = 1,
                 scrub_kv_pages: int = 4, repair_kit=None):
        kvp = kvcache.get_kv_policy(kv_policy)
        # per-request attribution on every path (see module docstring)
        kvp = dataclasses.replace(kvp, per_slot_flags=True)
        self.cfg, self.policy, self.slots_n = cfg, kvp, slots
        self.plan = plan
        self.prefix_sharing = bool(prefix_sharing)
        self._prefix_index: dict = {}   # full-prefix tokens -> page id
        self._published: dict = {}      # page id -> its index key
        self._prefix_meta: dict = {}    # index key -> [last_hit, seq]
        self._prefix_seq = 0
        npg = -(-max_len // kvp.page_size)
        self.max_len = npg * kvp.page_size
        if n_pages is None:
            n_pages = slots + slots * npg      # parking + full occupancy
        self.cache = kvcache.init_paged_cache(cfg, batch=slots,
                                              max_len=self.max_len,
                                              policy=kvp, n_pages=n_pages)
        self.allocator = kvcache.PageAllocator(n_pages, reserved=slots)
        self.queue = RequestQueue(self.max_len,
                                  self.allocator.free_count,
                                  kvp.page_size)
        if serve_step is None:
            serve_step = jax.jit(sp.make_serve_step(
                cfg, plan=plan, with_flags=True, kv_policy=kvp,
                dtype=dtype, act_quant=act_quant))
        self.serve_step = serve_step
        self.enc_params = enc_params
        self.telemetry = collector or telemetry.TelemetryCollector()
        self.step_no = 0
        self.results: dict = {}
        self._slots: list = [None] * slots
        self._pending_meta: dict = {}   # rid -> (enqueue_step, enqueue_s)
        if scrub_every < 0:
            raise ValueError("scrub_every must be >= 0")
        self.scrub_every = scrub_every
        self.repair_kit = repair_kit
        self.scrubber = scrubber.Scrubber(
            leaves_per_step=scrub_weight_leaves,
            pages_per_step=scrub_kv_pages)
        self._migrator: Optional[scrubber.Migrator] = None
        self._migrate_every = 1
        self.telemetry.emit("init", slots=slots, n_pages=n_pages,
                            pool_free=self.allocator.free_count,
                            page_size=kvp.page_size, max_len=self.max_len,
                            scheme=kvp.scheme, fused=kvp.fused,
                            attention_impl=kvp.attention_impl,
                            per_slot_flags=kvp.per_slot_flags,
                            prefix_sharing=self.prefix_sharing,
                            scrub_every=scrub_every,
                            repair=repair_kit is not None)

    # -- request intake ----------------------------------------------------

    def submit(self, req: Request):
        now = time.perf_counter()
        reason = self.queue.push(req)
        if reason is not None:
            self.telemetry.emit("reject", rid=req.rid, step=self.step_no,
                                reason=reason)
            return
        self._pending_meta[req.rid] = (self.step_no, now)
        self.telemetry.emit("enqueue", rid=req.rid, step=self.step_no,
                            prompt_len=len(req.prompt),
                            max_new=req.max_new, t_s=now)

    # -- prefix sharing ----------------------------------------------------

    def _lookup_shared(self, prompt) -> tuple:
        """Longest run of published full-page prefixes of ``prompt``.
        Matching is on the ENTIRE token prefix through each page — cached
        K/V at any position depends on every token before it, so a page
        is reusable only when everything upstream of it matches too."""
        ps = self.policy.page_size
        pids, j = [], 1
        while j * ps <= len(prompt):
            key = tuple(prompt[:j * ps])
            pid = self._prefix_index.get(key)
            if pid is None:
                break
            self._prefix_meta[key][0] = self.step_no   # LRU touch
            pids.append(pid)
            j += 1
        return tuple(pids)

    def _evict_prefix_cache(self, need: int, keep=()):
        """Drop cached prefix pages LRU-by-hit (least recently *hit*
        first — publication counts as the first hit, publication order
        breaks ties — never the ones the in-flight admission is about to
        map) until the allocator can serve ``need`` fresh pages. Evicting
        an entry only releases the page if no live slot still maps it."""
        keep = set(keep)
        order = sorted(self._prefix_index,
                       key=lambda k: tuple(self._prefix_meta[k]))
        for key in order:
            if self.allocator.can(need):
                return
            pid = self._prefix_index[key]
            if pid in keep:
                continue
            del self._prefix_index[key]
            del self._prefix_meta[key]
            del self._published[pid]
            released = self.allocator.free((pid,))
            if released:
                self._page_op(kvcache.zero_pages, released)

    def drop_prefix_cache(self) -> int:
        """Release every cached prefix page (the index's own references);
        pages still mapped by live slots survive until those finish.
        Returns the number of entries dropped."""
        n = len(self._prefix_index)
        self._evict_prefix_cache(self.allocator.n_pages + 1)
        return n

    def _maybe_publish(self, s: "_Slot"):
        """After ``s.consumed`` advanced: if it just crossed a page
        boundary inside the prompt, that page now holds a complete,
        final prefix — publish it (the index takes its own reference)."""
        ps = self.policy.page_size
        if s.consumed % ps != 0 or s.consumed > len(s.req.prompt):
            return
        key = tuple(s.req.prompt[:s.consumed])
        if key in self._prefix_index:
            return
        pid = s.pages[s.consumed // ps - 1]
        self._prefix_index[key] = pid
        self._prefix_meta[key] = [self.step_no, self._prefix_seq]
        self._prefix_seq += 1
        self._published[pid] = key
        self.allocator.retain((pid,))

    # -- admission ---------------------------------------------------------

    def _admit(self):
        """FIFO head-of-line admission: admit while a slot is free AND the
        pool can serve the head request's page budget up front. With
        prefix sharing the budget shrinks by the cached full-page prefix
        (mapped via refcounts), plus one CoW target when the prompt ends
        exactly on a shared page boundary."""
        while self.queue.peek() is not None:
            free_slot = next((i for i, s in enumerate(self._slots)
                              if s is None), None)
            if free_slot is None:
                return
            req = self.queue.peek()
            ps = self.policy.page_size
            npg = kvcache.pages_needed(req.total_tokens, ps)
            shared = (self._lookup_shared(req.prompt)
                      if self.prefix_sharing else ())
            plen = len(req.prompt)
            # a fully-shared prompt still re-consumes its last token
            # (that step yields the first sampled token) and therefore
            # WRITES into the last shared page -> private CoW clone
            cow = bool(shared) and len(shared) * ps == plen
            need = npg - len(shared) + (1 if cow else 0)
            if not self.allocator.can(need) and self.prefix_sharing:
                self._evict_prefix_cache(need, keep=shared)
            if not self.allocator.can(need):
                return                      # transient exhaustion: wait
            self.queue.pop()
            fresh = self.allocator.alloc(need)
            if cow:
                src, dst = shared[-1], fresh[0]
                self.allocator.retain(shared[:-1])
                self._page_op(kvcache.copy_page, src, dst)
                pages = shared[:-1] + (dst,) + fresh[1:]
            else:
                self.allocator.retain(shared)
                pages = shared + fresh
            self._page_op(kvcache.set_slot_pages, free_slot, pages)
            enq_step, enq_s = self._pending_meta.pop(req.rid)
            slot = _Slot(req, pages, self.step_no, enq_step, enq_s)
            # shared pages' K/V is already in the pool: skip straight
            # past those prompt tokens
            slot.consumed = min(len(shared) * ps, plen - 1)
            self._slots[free_slot] = slot
            ev = dict(rid=req.rid, step=self.step_no, slot=free_slot,
                      n_pages=need, queue_depth=len(self.queue),
                      pool_free=self.allocator.free_count)
            if self.prefix_sharing:
                ev.update(n_pages_solo=npg, pages_shared=len(shared),
                          tokens_reused=slot.consumed,
                          cow_copied=int(cow))
            self.telemetry.emit("admit", **ev)
            if cow:
                self.telemetry.emit("cow", rid=req.rid, step=self.step_no,
                                    slot=free_slot, src=shared[-1],
                                    dst=fresh[0])

    # -- self-healing: scrub, repair, migrate ------------------------------

    def start_migration(self, target_plan, *, leaves_per_step: int = 1,
                        every: int = 1) -> "scrubber.Migrator":
        """Begin a rolling migration to ``target_plan``: every ``every``
        steps the next ``leaves_per_step`` scheme-changed leaves are
        transcoded in place and the front-end's plan is swapped for the
        promoted one. Serving continues throughout — decode dispatches on
        each leaf's own scheme id."""
        if self.plan is None:
            raise ValueError("front-end was built without a plan — "
                             "nothing to diff a migration against")
        if self._migrator is not None and not self._migrator.done:
            raise RuntimeError("a migration is already in flight")
        self._migrator = scrubber.Migrator(self.plan, target_plan,
                                           leaves_per_step=leaves_per_step)
        self._migrate_every = max(1, every)
        self.telemetry.emit("migrate", step=self.step_no, phase="start",
                            pending=len(self._migrator.pending))
        return self._migrator

    @property
    def migration_done(self) -> bool:
        return self._migrator is None or self._migrator.done

    def _busy_pages(self) -> set:
        """Each active slot's current write-target page — the one page per
        slot this step's serve compute will scribble into."""
        ps = self.policy.page_size
        busy = set()
        for s in self._slots:
            if s is not None:
                busy.add(s.pages[min(s.consumed // ps,
                                     len(s.pages) - 1)])
        return busy

    def _repair(self, due_paths):
        """Hand scrub-detected DUE leaves to MILR repair/quarantine."""
        from repro.protection import repair as repair_mod
        self.enc_params, reports = repair_mod.repair_tree(
            self.enc_params, self.repair_kit, paths=due_paths)
        for r in reports:
            self.telemetry.emit("repair", step=self.step_no, **r)
        return reports

    def _heal(self):
        """The per-step maintenance slice, run AFTER admission and BEFORE
        the serve compute so written-back corrections land before anything
        decodes them."""
        mig = self._migrator
        migrate = (mig is not None and not mig.done
                   and self.step_no % self._migrate_every == 0)
        scrub = bool(self.scrub_every) and \
            self.step_no % self.scrub_every == 0
        if not (migrate or scrub):
            return
        with self.telemetry.span("heal"):
            if migrate:
                self.enc_params, recs = mig.step(self.enc_params)
                self.plan = mig.plan
                for r in recs:
                    self.telemetry.emit("migrate", step=self.step_no,
                                        phase="promote",
                                        pending=len(mig.pending), **r)
            if scrub:
                self._scrub()

    def _scrub(self):
        self.enc_params, wst = self.scrubber.scrub_weights(self.enc_params)
        if wst["due_paths"] and self.repair_kit is not None:
            self._repair(wst["due_paths"])
        self.cache, kst = self.scrubber.scrub_kv(
            self.cache, self.policy, occupied=self.allocator.live_pages(),
            busy=self._busy_pages())
        self.telemetry.emit(
            "scrub", step=self.step_no,
            w_scanned=wst["scanned"], w_corrected=wst["corrected"],
            w_due=wst["due"], kv_scanned=kst["scanned"],
            kv_corrected=kst["corrected"], kv_due=kst["due"])

    def final_scrub(self) -> dict:
        """One full at-rest pass, meant for after the loop drains: every
        protected weight leaf (with repair/quarantine for DUE leaves, then
        a recount), every live KV page, and an unconditional re-zero of
        free + parking pages. Emits ``scrub_final`` and returns its
        fields — ``w_due`` / ``kv_due`` are the *residual* uncorrectable
        state, the quantity CI pins to zero."""
        tree, wst = self.scrubber.scrub_weights(self.enc_params, n=-1)
        self.enc_params = tree
        repaired = 0
        if wst["due_paths"] and self.repair_kit is not None:
            repaired = len(self._repair(wst["due_paths"]))
            tree, wst2 = self.scrubber.scrub_weights(self.enc_params, n=-1)
            self.enc_params = tree
        else:
            wst2 = wst
        self.cache, kst = self.scrubber.scrub_kv(
            self.cache, self.policy,
            occupied=self.allocator.live_pages(), n=-1)
        self.cache = self.scrubber.scrub_free(self.cache, self.allocator)
        out = {"w_scanned": wst["scanned"], "w_corrected": wst["corrected"],
               "w_repaired": repaired, "w_due": wst2["due"],
               "kv_scanned": kst["scanned"],
               "kv_corrected": kst["corrected"], "kv_due": kst["due"]}
        self.telemetry.emit("scrub_final", step=self.step_no, **out)
        return out

    # -- the serving loop --------------------------------------------------

    @property
    def active(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    def _finish(self, idx: int):
        s = self._slots[idx]
        now = time.perf_counter()
        n_gen = len(s.generated)
        self.results[s.req.rid] = list(s.generated)
        # park the row, then drop this slot's references; only pages whose
        # LAST reference died re-enter the pool — zero exactly those
        # before anything can re-allocate them (pages still mapped by
        # other slots or the prefix cache must keep their bytes)
        self._page_op(kvcache.set_slot_pages, idx, ())
        released = self.allocator.free(s.pages)
        if released:
            self._page_op(kvcache.zero_pages, released)
        self._slots[idx] = None
        ev = {"rid": s.req.rid, "step": self.step_no, "slot": idx,
              "n_generated": n_gen, "kv_corrected": int(s.kv_corrected),
              "kv_due": int(s.kv_due),
              "pool_free": self.allocator.free_count}
        if s.abft_mismatches or s.clamp_hits:
            ev["abft_mismatches"] = int(s.abft_mismatches)
            ev["clamp_hits"] = int(s.clamp_hits)
        if s.first_s is not None:
            ev["ttft_s"] = s.first_s - s.enqueue_s
            ev["tpot_ms"] = ((now - s.first_s) / max(1, n_gen - 1)) * 1e3
        self.telemetry.emit("finish", **ev)

    def _page_op(self, fn, *args):
        """Apply one eager ``kvcache`` page program to the cache: a
        ``serve.pages`` span, counted in the step's ``page_ops``."""
        with self.telemetry.span("pages"):
            self.cache = fn(self.cache, *args)
        self.telemetry.count("page_ops")

    def step(self):
        """One loop iteration: admit, run the compiled step over all
        slots (idle slots feed a keep-alive token into their parking
        page), sample greedily, advance lifecycles, emit telemetry. Each
        phase is a ``serve.*`` span inside ``serve.step`` (module
        :mod:`~repro.serving.telemetry`)."""
        tel = self.telemetry
        with tel.step(self.step_no) as timing:
            with tel.span("admit"):
                self._admit()
            self._heal()
            with tel.span("inputs"):
                tokens = np.zeros((self.slots_n, 1), np.int32)
                pos = np.zeros((self.slots_n,), np.int32)
                for i, s in enumerate(self._slots):
                    if s is None:
                        continue
                    if s.consumed < len(s.req.prompt):
                        tokens[i, 0] = s.req.prompt[s.consumed]
                    else:
                        tokens[i, 0] = s.generated[-1]
                    pos[i] = s.consumed
                tokens, pos = jnp.asarray(tokens), jnp.asarray(pos)
            # both calls only dispatch; the wait is on the sampled tokens
            with tel.span("dispatch"):
                logits, self.cache, flags = self.serve_step(
                    self.enc_params, self.cache, tokens, pos)
                top = jnp.argmax(logits[:, -1, :], axis=-1)
            with tel.span("wait"):
                sampled = np.asarray(top)
            with tel.span("fetch"):
                kv = np.asarray(flags["layers_kv"]).sum(axis=0)  # (2,)|(2, B)
                w = np.asarray(flags["top"]) + \
                    np.asarray(flags["layers"]).sum(0)
                # ABFT channel (only present when the plan guards some
                # leaves): layer rows (L, 2) or per-slot (L, 2, B), plus
                # the top row — the decode step's output rows ARE the
                # batch slots, so per-slot rows attribute compute faults
                # to requests exactly
                ab = flags.get("layers_abft")
                if ab is not None:
                    ab = np.asarray(ab).sum(axis=0) + \
                        np.asarray(flags["top_abft"])
            t1 = time.perf_counter()
            per_slot = kv.ndim == 2
            with tel.span("advance"):
                self._advance(sampled, kv, ab, per_slot, t1)
            with tel.span("finish"):
                for i, s in enumerate(self._slots):
                    if s is not None and len(s.generated) >= s.req.max_new:
                        self._finish(i)
            # emitted after finishes so pool_free reflects this step's
            # frees — summarize() reads the last step's pool_free as the
            # leak check
            ev = dict(
                step=self.step_no, active=self.active,
                queue_depth=len(self.queue),
                pool_free=self.allocator.free_count,
                pool_cached=len(self._prefix_index),
                kv_corrected=int(kv.sum(axis=-1)[0] if per_slot else kv[0]),
                kv_due=int(kv.sum(axis=-1)[1] if per_slot else kv[1]),
                w_corrected=int(w[0]), w_due=int(w[1]))
            if ab is not None:
                ev["abft_mismatches"] = int(ab[0].sum())
                ev["clamp_hits"] = int(ab[1].sum())
        tel.emit("step", **ev, **timing)
        self.step_no += 1

    def _advance(self, sampled, kv, ab, per_slot: bool, t1: float):
        """Per-slot bookkeeping after a step: fault counts, the consumed
        prompt, the sampled token, and ``first_token`` events."""
        for i, s in enumerate(self._slots):
            if s is None:
                continue
            if per_slot:
                s.kv_corrected += int(kv[0, i])
                s.kv_due += int(kv[1, i])
            else:                # fused: batch totals as upper bound
                s.kv_corrected += int(kv[0])
                s.kv_due += int(kv[1])
            if ab is not None:
                if ab.ndim == 2:
                    s.abft_mismatches += int(ab[0, i])
                    s.clamp_hits += int(ab[1, i])
                else:            # scalar channel: batch totals
                    s.abft_mismatches += int(ab[0])
                    s.clamp_hits += int(ab[1])
            s.consumed += 1
            if self.prefix_sharing:
                self._maybe_publish(s)
            if s.consumed >= len(s.req.prompt):
                s.generated.append(int(sampled[i]))
                if s.first_step is None:
                    s.first_step, s.first_s = self.step_no, t1
                    self.telemetry.emit(
                        "first_token", rid=s.req.rid, step=self.step_no,
                        slot=i, ttft_steps=self.step_no - s.enqueue_step,
                        ttft_s=t1 - s.enqueue_s)

    def run(self, max_steps: int = 10_000):
        """Step until queue and slots drain (or ``max_steps``)."""
        for _ in range(max_steps):
            if not self.queue.peek() and self.active == 0:
                return
            self.step()
        if self.queue.peek() or self.active:
            raise RuntimeError(f"not drained after {max_steps} steps: "
                               f"{len(self.queue)} queued, "
                               f"{self.active} active")


# ---------------------------------------------------------------------------
# burst-load driver
# ---------------------------------------------------------------------------


def make_waves(*, seed: int, n_waves: int, wave_size: int, vocab: int,
               prompt_len=(4, 12), max_new=(4, 8),
               gap_steps: int = 8, shared_prefix_len: int = 0) -> list:
    """Deterministic burst workload: ``n_waves`` waves of ``wave_size``
    requests each, wave *w* arriving at step ``w * gap_steps``. Prompt
    tokens and per-request lengths draw from a ``numpy`` generator seeded
    with ``seed`` only — same seed, same workload, bit for bit.

    ``shared_prefix_len > 0`` draws ONE common prefix of that many tokens
    and prepends it to every prompt (``prompt_len`` then ranges over the
    per-request suffix, which may be 0) — the shared-prefix serving
    scenario the front-end's prefix cache exists for."""
    rng = np.random.default_rng(seed)
    shared = tuple(int(t) for t in
                   rng.integers(1, vocab, size=shared_prefix_len))
    lo_p, hi_p = prompt_len
    lo_n, hi_n = max_new
    reqs, rid = [], 0
    for w in range(n_waves):
        for _ in range(wave_size):
            plen = int(rng.integers(lo_p, hi_p + 1))
            reqs.append(Request(
                rid=rid,
                prompt=shared + tuple(int(t) for t in
                                      rng.integers(1, vocab, size=plen)),
                max_new=int(rng.integers(lo_n, hi_n + 1)),
                arrival_step=w * gap_steps))
            rid += 1
    return reqs


def run_burst(cfg: ArchConfig, enc_params, *, plan=None, waves: Sequence,
              slots: int = 4, max_len: int = 128,
              n_pages: Optional[int] = None, kv_policy="in-place",
              fault_rate: float = 0.0, fault_seed: int = 0,
              inject_every: int = 4, telemetry_path: Optional[str] = None,
              serve_step=None, max_steps: int = 10_000,
              dtype=jnp.bfloat16, prefix_sharing: bool = False,
              scrub_every: int = 0, scrub_weight_leaves: int = 1,
              scrub_kv_pages: int = 4, repair: bool = False,
              repair_kit=None, weight_fault_rate: float = 0.0):
    """Replay a seeded wave workload through the front-end, optionally
    injecting faults into the live KV pools every ``inject_every`` steps
    at per-bit ``fault_rate`` (keys fold in the logical step, so a replay
    injects the identical bits). ``weight_fault_rate`` additionally
    injects into the encoded weight tree on the same cadence (its own key
    stream — KV and weight injections never alias). Returns ``(events,
    summary, results)``.

    ``scrub_every > 0`` turns on the budgeted self-healing slice
    (``scrub_weight_leaves`` / ``scrub_kv_pages`` per pass) and ends the
    run with :meth:`ServingFrontend.final_scrub`, so the summary's
    ``healing`` roll-up reports the residual at-rest DUE state;
    ``repair=True`` pins a MILR repair kit from the (clean) entry tree
    first — or pass a prebuilt ``repair_kit`` when the entry tree already
    carries faults.

    Pass a prebuilt jitted ``serve_step`` to share the compiled executable
    across runs (the protected/unprotected twin comparison and
    bit-determinism replays rely on this to avoid recompiles)."""
    col = telemetry.TelemetryCollector(telemetry_path)
    kit = repair_kit
    if repair and kit is None:
        from repro.protection import repair as repair_mod
        kit = repair_mod.build_repair_kit(enc_params, seed=fault_seed)
    fe = ServingFrontend(cfg, enc_params, plan=plan, slots=slots,
                         max_len=max_len, n_pages=n_pages,
                         kv_policy=kv_policy, serve_step=serve_step,
                         collector=col, dtype=dtype,
                         prefix_sharing=prefix_sharing,
                         scrub_every=scrub_every,
                         scrub_weight_leaves=scrub_weight_leaves,
                         scrub_kv_pages=scrub_kv_pages, repair_kit=kit)
    pending = sorted(waves, key=lambda r: (r.arrival_step, r.rid))
    i = 0
    base_key = jax.random.PRNGKey(fault_seed)
    wkey = jax.random.PRNGKey(fault_seed + 1_000_003)
    for _ in range(max_steps):
        while i < len(pending) and pending[i].arrival_step <= fe.step_no:
            fe.submit(pending[i])
            i += 1
        if i >= len(pending) and not fe.queue.peek() and fe.active == 0:
            break
        if (fault_rate > 0 and fe.active > 0
                and fe.step_no % inject_every == 0):
            from repro import protection
            tree = kvcache.as_protected_tree(fe.cache, fe.policy)
            dirty = protection.inject_tree_device(
                tree, fault_rate, jax.random.fold_in(base_key, fe.step_no))
            fe.cache = kvcache.from_protected_tree(fe.cache, dirty)
        if (weight_fault_rate > 0 and fe.active > 0
                and fe.step_no % inject_every == 0):
            from repro import protection
            fe.enc_params = protection.inject_tree_device(
                fe.enc_params, weight_fault_rate,
                jax.random.fold_in(wkey, fe.step_no))
        fe.step()
    else:
        raise RuntimeError(f"burst not drained after {max_steps} steps")
    if scrub_every > 0:
        fe.final_scrub()
    col.close()
    return col.events, telemetry.summarize(col.events), fe.results
