"""Protected paged KV cache — zero-space ECC over serving *state*.

Weights are protected end-to-end (schemes/policy/serving); at production
batch x context the KV cache dominates HBM and sits in the same fault
domain completely unprotected — one flipped bit in a cached key silently
corrupts every later token of that sequence. The paper's trick applies
directly because the cache is quantizable: keys/values are int8-quantized
per token (absmax over the token's ``(kv_heads, head_dim)`` slab, the
scale riding the page like the fused matmul's ``a_scale``), and the freed
bit space carries the (64,57,1) SEC-DED check bits.

Layout: fixed-size pages ``(page_size, kv_heads, head_dim)`` — head_dim a
multiple of 8, so ECC blocks run along head_dim and every page is
block-aligned — live in a global pool ``(n_pages, page_size, kv_heads,
head_dim)`` uint8. Each sequence owns a page-table row mapping logical
page ``j`` to its pool slot; the pool is statically partitioned today
(sequence ``b`` owns rows ``b*np .. (b+1)*np``) but every access goes
through the table, which is what continuous batching needs next.

Attention decodes pages **at use**: the XLA reference path here gathers
the sequence's encoded strips, block-decodes them (per-token flags),
dequantizes, and runs the stock :func:`layers.decode_attention`; the
fused path (:mod:`repro.kernels.paged_attention`) does decode +
dequantize + attention in VMEM and must match the reference
bit-identically. Per-token (corrected, DUE) flags are masked to valid
(``<= pos``) tokens and recorded into the layers-module KV flags sink, so
``decode_step(collect_flags=True)`` reports them per layer alongside the
weight flags.

The pools round-trip through :func:`as_protected_tree` /
:func:`from_protected_tree` as same-shape :class:`ProtectedTensor` leaves,
so the generic campaign machinery (``inject_tree_device``,
``decode_tree_with_flags``, ``due_campaign(target="kv")``) drives KV fault
campaigns unchanged.
"""
from __future__ import annotations

import dataclasses
import heapq
import math
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import ecc, quant, wot
from repro.models import layers as L
from repro.models.config import ArchConfig
from repro.protection.backends import get_backend
from repro.protection.schemes import ALIASES, get_scheme
from repro.protection.tensor import ProtectedTensor

__all__ = ["KVProtectionPolicy", "KV_POLICY_PRESETS", "get_kv_policy",
           "supports_paged", "pages_per_seq", "pages_needed",
           "init_paged_cache", "init_cache", "paged_gqa_decode",
           "paged_gqa_prefill", "as_protected_tree", "from_protected_tree",
           "tree_layer_flags", "kv_bytes", "dense_kv_bytes",
           "PageAllocator", "set_slot_pages", "zero_pages", "copy_page"]

# the paper's serving-state menu: parity detects+zeroes, in-place corrects
# singles / detects doubles at zero space. secded72 is excluded on purpose —
# its out-of-place check bytes would change the page stride, and the paper's
# claim under test here is the zero-space one.
KV_SCHEMES = ("faulty", "parity-zero", "in-place")


@dataclasses.dataclass(frozen=True)
class KVProtectionPolicy:
    """Static (hashable) KV protection knobs — the cache-side analogue of
    ``protection.ProtectionPolicy``.

    scheme:    "faulty" (unprotected int8 baseline) | "parity-zero" |
               "in-place". All three store int8 pages + per-token scales,
               so protection deltas measure the *codec*, not quantization.
    backend:   block-codec route for the reference path ("xla" | "pallas").
    fused:     decode-at-use attention through the fused Pallas kernel
               (``kernels.paged_attention``) instead of the XLA
               decode-then-attend reference. Bit-identical by construction.
    page_size: tokens per page.
    per_slot_flags: report KV (corrected, DUE) flags per BATCH SLOT
               instead of batch-summed scalars — ``flags["layers_kv"]``
               becomes (n_layers, 2, B) so the request front-end can
               attribute state faults to the request occupying each slot
               (MILR-style recovery needs to know WHICH request a DUE
               hit). Supported on every attention path: the reference
               masks per-token flags per row, the fused kernels reduce
               their in-grid (B, KV, 2) flag cells per batch row.
    attention_impl: decode-attention kernel choice for the Pallas path.
               "strip" (default) holds the whole gathered strip in VMEM
               and is bit-identical to the XLA reference — a hard VMEM
               wall at a few k tokens (``paged_attention.
               strip_vmem_bytes``). "chunked" streams fixed-size page
               chunks through a running online-softmax — VMEM bounded by
               ``chunk_pages``, context bounded by HBM — but FORFEITS
               the bit-identity contract: it is validated against an
               fp64 oracle (``paged_attention.oracle_page_attention``)
               within tolerance instead, which is why it must be asked
               for explicitly.
    chunk_pages: pages per chunk for ``attention_impl="chunked"``
               (chunk_tokens = chunk_pages * page_size).
    """

    scheme: str = "in-place"
    backend: str = "xla"
    fused: bool = False
    page_size: int = 16
    per_slot_flags: bool = False
    attention_impl: str = "strip"
    chunk_pages: int = 16

    def __post_init__(self):
        sid = ALIASES.get(self.scheme, self.scheme)
        if sid not in KV_SCHEMES:
            raise ValueError(f"KV scheme {self.scheme!r}; one of {KV_SCHEMES}")
        object.__setattr__(self, "scheme", sid)
        if self.page_size <= 0:
            raise ValueError(f"page_size must be positive, got {self.page_size}")
        if self.attention_impl not in ("strip", "chunked"):
            raise ValueError(f"attention_impl {self.attention_impl!r}; one "
                             f"of ('strip', 'chunked')")
        if self.chunk_pages <= 0:
            raise ValueError(f"chunk_pages must be positive, "
                             f"got {self.chunk_pages}")

    @property
    def scheme_obj(self):
        return get_scheme(self.scheme)

    @property
    def has_checks(self) -> bool:
        return self.scheme == "parity-zero"


KV_POLICY_PRESETS = {
    "unprotected": KVProtectionPolicy(scheme="faulty"),
    "parity-zero": KVProtectionPolicy(scheme="parity-zero"),
    "in-place": KVProtectionPolicy(scheme="in-place"),
    "unprotected-fused": KVProtectionPolicy(scheme="faulty", fused=True),
    "parity-zero-fused": KVProtectionPolicy(scheme="parity-zero", fused=True),
    "in-place-fused": KVProtectionPolicy(scheme="in-place", fused=True),
    # long-context fast path: page-chunked online-softmax Pallas attention.
    # NOT bit-identical to the reference (fp64-oracle tolerance gated) —
    # which is why it only runs when named explicitly.
    "unprotected-chunked": KVProtectionPolicy(scheme="faulty", fused=True,
                                              attention_impl="chunked"),
    "parity-zero-chunked": KVProtectionPolicy(scheme="parity-zero",
                                              fused=True,
                                              attention_impl="chunked"),
    "in-place-chunked": KVProtectionPolicy(scheme="in-place", fused=True,
                                           attention_impl="chunked"),
}


def get_kv_policy(policy) -> Optional[KVProtectionPolicy]:
    """Resolve a preset name (scheme aliases + optional "-fused" /
    "-chunked" suffix) or pass a :class:`KVProtectionPolicy` / None
    through."""
    if policy is None or isinstance(policy, KVProtectionPolicy):
        return policy
    name = str(policy)
    suffix = next((s for s in ("-fused", "-chunked")
                   if name.endswith(s)), "")
    base = name[: -len(suffix)] if suffix else name
    base = ALIASES.get(base, base)
    base = "unprotected" if base == "faulty" else base
    key = base + suffix
    try:
        return KV_POLICY_PRESETS[key]
    except KeyError:
        raise ValueError(f"unknown KV policy {policy!r}; one of "
                         f"{sorted(KV_POLICY_PRESETS)} (or a "
                         f"KVProtectionPolicy)") from None


# ---------------------------------------------------------------------------
# cache construction
# ---------------------------------------------------------------------------


def supports_paged(cfg: ArchConfig) -> bool:
    """Families whose decode KV state is the dense (B, S, kv, hd) GQA cache
    the paged pool replaces. MLA's compressed latents and the SSM/RG-LRU
    recurrent states are different objects (open item)."""
    return cfg.family in ("dense", "vlm") or \
        (cfg.family == "moe" and not cfg.use_mla)


def pages_per_seq(max_len: int, page_size: int) -> int:
    return -(-max_len // page_size)


def pages_needed(n_tokens: int, page_size: int) -> int:
    """Pool pages a request writing ``n_tokens`` positions needs."""
    return -(-n_tokens // page_size)


def init_paged_cache(cfg: ArchConfig, batch: int, max_len: int,
                     policy, *, n_pages: Optional[int] = None) -> dict:
    """Paged replacement for ``lm.init_cache``'s dense k/v buffers.

    Keys (all with a leading stacked-layer axis so ``lax.scan`` slices them
    like the dense cache):

      k_pages/v_pages   (nl, P, page_size, kv, hd) uint8 encoded pools
      k_checks/v_checks (nl, P, page_size, kv, hd // 8) uint8 (parity only)
      k_scale/v_scale   (nl, P, page_size) f32 per-token scales
      kv_table          (nl, B, pages_per_seq) int32 page tables

    By default the pool is statically partitioned (sequence ``b`` owns rows
    ``b*np .. (b+1)*np`` via an identity table). With ``n_pages`` the pool
    is sized independently of ``batch`` for the request front-end: pages
    ``0..batch-1`` are per-slot PARKING pages (an idle slot's table points
    wholly at its own parking page, so its keep-alive writes can never
    scribble on a page owned by a live request) and pages ``batch..`` are
    the allocatable pool a :class:`PageAllocator` hands to admitted
    requests via :func:`set_slot_pages`.

    Zero pages are codec-clean for every scheme (zero blocks have syndrome
    0), so untouched pool slots decode without phantom flags.
    """
    policy = get_kv_policy(policy)
    if policy is None:
        raise ValueError("init_paged_cache needs a KV policy")
    if not supports_paged(cfg):
        raise ValueError(f"paged KV cache supports dense/vlm/moe-gqa decode "
                         f"caches, not family {cfg.family!r}"
                         + (" with MLA" if cfg.use_mla else ""))
    if cfg.head_dim % ecc.BLOCK_BYTES:
        raise ValueError(f"head_dim {cfg.head_dim} must be a multiple of "
                         f"{ecc.BLOCK_BYTES} (ECC blocks run along head_dim)")
    from repro.models import lm  # deferred: lm routes back into this module
    nl = lm.n_scan_layers(cfg)
    kv, hd = cfg.n_kv_heads, cfg.head_dim
    ps = policy.page_size
    npg = pages_per_seq(max_len, ps)
    if n_pages is None:
        pool = batch * npg
        table = jnp.tile(
            jnp.arange(pool, dtype=jnp.int32).reshape(1, batch, npg),
            (nl, 1, 1))
    else:
        if n_pages <= batch:
            raise ValueError(f"n_pages={n_pages} leaves no allocatable pages "
                             f"beyond the {batch} per-slot parking pages")
        pool = n_pages
        table = jnp.tile(                         # slot b parks on page b
            jnp.arange(batch, dtype=jnp.int32).reshape(1, batch, 1),
            (nl, 1, npg))
    cache = {
        "k_pages": jnp.zeros((nl, pool, ps, kv, hd), jnp.uint8),
        "v_pages": jnp.zeros((nl, pool, ps, kv, hd), jnp.uint8),
        "k_scale": jnp.zeros((nl, pool, ps), jnp.float32),
        "v_scale": jnp.zeros((nl, pool, ps), jnp.float32),
        "kv_table": table,
    }
    if policy.has_checks:
        cache["k_checks"] = jnp.zeros((nl, pool, ps, kv, hd // 8), jnp.uint8)
        cache["v_checks"] = jnp.zeros((nl, pool, ps, kv, hd // 8), jnp.uint8)
    return cache


def init_cache(cfg: ArchConfig, batch: int, max_len: int, *, kv_policy=None,
               dtype=jnp.bfloat16) -> dict:
    """``lm.init_cache`` with a KV-policy switch: paged+protected when a
    policy is given, the stock dense cache otherwise."""
    if kv_policy is None:
        from repro.models import lm
        return lm.init_cache(cfg, batch, max_len, dtype)
    return init_paged_cache(cfg, batch, max_len, kv_policy)


# ---------------------------------------------------------------------------
# codec: per-token quantize (+WOT throttle) -> scheme encode; block decode
# with per-token flags
# ---------------------------------------------------------------------------


def _encode_kv(kf: jnp.ndarray, policy: KVProtectionPolicy):
    """float (..., kv, hd) -> (enc uint8, checks | None, scale (...,) f32).

    Per-token absmax scale over the (kv, hd) slab. The in-place scheme
    additionally WOT-throttles the quantized slab (positions 0..6 of each
    8-value block clamp to [-64, 63]) so bit 6 is free for check bits —
    the serving-state analogue of QATT's weight constraint.
    """
    kf32 = kf.astype(jnp.float32)
    scale = quant.compute_scale(kf32, axis=(-2, -1))         # (..., 1, 1)
    q = jnp.clip(jnp.round(kf32 / scale), -quant.QMAX,
                 quant.QMAX).astype(jnp.int8)
    scheme = policy.scheme_obj
    if scheme.requires_wot:
        q = wot.throttle_q(q)
    enc, checks = scheme.encode(q, policy.backend)
    return enc, checks, scale[..., 0, 0]


def _decode_kv(enc: jnp.ndarray, checks, scheme_id: str, backend="xla"):
    """uint8 (..., kv, hd) -> (q int8, corrected (...,), due (...,)).

    Flags are per-TOKEN int32 counts (summed over the token's blocks/bytes)
    so callers can mask them by token validity — the scalar counts of
    ``Scheme.decode_with_flags`` cannot tell a live token's fault from a
    stale slot's.
    """
    if scheme_id == "faulty":
        q = jax.lax.bitcast_convert_type(enc, jnp.int8)
        z = jnp.zeros(enc.shape[:-2], jnp.int32)
        return q, z, z
    if scheme_id == "parity-zero":
        data, bad = ecc.decode_parity8(enc, checks)
        q = jax.lax.bitcast_convert_type(data, jnp.int8)
        # zeroing a detected-faulty byte IS this scheme's repair action
        cor = jnp.sum(bad.astype(jnp.int32), axis=(-2, -1))
        return q, cor, jnp.zeros_like(cor)
    if scheme_id != "in-place":
        raise ValueError(f"KV scheme {scheme_id!r}; one of {KV_SCHEMES}")
    be = get_backend(backend)
    blocks = enc.reshape(*enc.shape[:-1], enc.shape[-1] // 8, 8)
    dec, single, double = be.decode64(blocks)
    q = jax.lax.bitcast_convert_type(dec.reshape(enc.shape), jnp.int8)
    cor = jnp.sum(single.astype(jnp.int32), axis=(-2, -1))
    due = jnp.sum(double.astype(jnp.int32), axis=(-2, -1))
    return q, cor, due


# ---------------------------------------------------------------------------
# page-pool plumbing: scatter writes, table gathers
# ---------------------------------------------------------------------------


def _write_token(pages, checks, scales, table, enc, ch, sc, pos):
    """Scatter one decode token into its page. enc (B, kv, hd); sc/pos (B,)."""
    ps = pages.shape[1]
    page = pos // ps
    phys = jnp.take_along_axis(table, page[:, None], axis=1)[:, 0]   # (B,)
    slot = pos % ps
    pages = pages.at[phys, slot].set(enc)
    if checks is not None:
        checks = checks.at[phys, slot].set(ch)
    scales = scales.at[phys, slot].set(sc)
    return pages, checks, scales


def _write_pages(pages, checks, scales, table, enc, ch, sc):
    """Scatter whole prefill pages. enc (B, npg*ps, kv, hd); sc (B, npg*ps)."""
    b = table.shape[0]
    ps = pages.shape[1]
    npg = enc.shape[1] // ps
    idx = table[:, :npg].reshape(-1)                         # (B*npg,)
    pages = pages.at[idx].set(
        enc.reshape(b * npg, ps, *enc.shape[2:]))
    if checks is not None:
        checks = checks.at[idx].set(ch.reshape(b * npg, ps, *ch.shape[2:]))
    scales = scales.at[idx].set(sc.reshape(b * npg, ps))
    return pages, checks, scales


def _gather_seq(pages, checks, scales, table):
    """Pool -> per-sequence encoded strips: (enc (B, S, kv, hd), checks |
    None, scale (B, S)) with S = pages_per_seq * page_size."""
    b, npg = table.shape
    ps = pages.shape[1]
    enc = pages[table].reshape(b, npg * ps, *pages.shape[2:])
    ch = None
    if checks is not None:
        ch = checks[table].reshape(b, npg * ps, *checks.shape[2:])
    sc = scales[table].reshape(b, npg * ps)
    return enc, ch, sc


# ---------------------------------------------------------------------------
# page free/reuse: the allocator and table-rewrite API continuous batching
# runs on (see repro.serving.frontend)
# ---------------------------------------------------------------------------


class PageAllocator:
    """Host-side REFCOUNTED free-list over the pool's allocatable pages.

    Page ids ``0..reserved-1`` are per-slot parking pages (see
    :func:`init_paged_cache` with ``n_pages``) and are never handed out.
    Allocation is deterministic — lowest ids first via a heap — so a seeded
    request replay reuses the exact same physical pages run-to-run (the
    burst trace's bit-determinism contract depends on this).

    Prefix sharing maps one physical page into several slots' tables, so
    every live page carries a reference count: :meth:`alloc` hands pages
    out at refcount 1, :meth:`retain` adds a reference (a sharer's
    read-only mapping, or the front-end's prefix index), and :meth:`free`
    drops ONE reference per page — a page re-enters the heap only when
    its count hits zero, and :meth:`free` returns exactly those released
    pages so the caller knows which ones to zero. Freeing a page with no
    live reference is an accounting bug ("double free") and raises
    explicitly rather than silently re-heapifying a page some other slot
    still reads — the invariant the hypothesis suite hammers:
    ``free_count + live_count == n_pages - reserved`` always.
    """

    def __init__(self, n_pages: int, reserved: int = 0):
        if not 0 <= reserved < n_pages:
            raise ValueError(f"reserved={reserved} outside pool of "
                             f"{n_pages} pages")
        self.n_pages = n_pages
        self.reserved = reserved
        self._free = list(range(reserved, n_pages))
        heapq.heapify(self._free)
        self._refs: dict = {}       # page id -> live reference count

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def live_count(self) -> int:
        """Distinct pages currently out of the pool (any refcount)."""
        return len(self._refs)

    def refcount(self, pid: int) -> int:
        return self._refs.get(pid, 0)

    def can(self, n: int) -> bool:
        return n <= len(self._free)

    def alloc(self, n: int) -> tuple:
        """Pop the ``n`` lowest free page ids (each at refcount 1); raises
        if the pool cannot serve the request (admission control checks
        :meth:`can` first)."""
        if not self.can(n):
            raise ValueError(f"page pool exhausted: need {n}, "
                             f"free {len(self._free)}")
        ids = tuple(heapq.heappop(self._free) for _ in range(n))
        for pid in ids:
            self._refs[pid] = 1
        return ids

    def retain(self, page_ids: Sequence[int]) -> None:
        """Add one reference per page (prefix sharing / index pin). Only
        live pages can be retained — retaining a free page would resurrect
        content the pool may already have handed to someone else."""
        for pid in page_ids:
            if self._refs.get(pid, 0) < 1:
                raise ValueError(f"retain of page {pid} with no live "
                                 f"reference")
            self._refs[pid] += 1

    def free(self, page_ids: Sequence[int]) -> tuple:
        """Drop one reference per page; returns the pages whose count hit
        zero and re-entered the pool (the caller zeroes exactly those).
        Double-frees and parking-page frees are accounting bugs — fail
        loudly instead of corrupting the refcount invariant."""
        released = []
        for pid in page_ids:
            if pid < self.reserved or pid >= self.n_pages:
                raise ValueError(f"page {pid} is not allocatable "
                                 f"(reserved < {self.reserved}, "
                                 f"pool {self.n_pages})")
            refs = self._refs.get(pid, 0)
            if refs < 1:
                raise ValueError(f"double free of page {pid}")
            if refs == 1:
                del self._refs[pid]
                heapq.heappush(self._free, pid)
                released.append(pid)
            else:
                self._refs[pid] = refs - 1
        return tuple(released)

    def live_pages(self) -> tuple:
        """Sorted ids of pages currently out of the pool (refcount > 0) —
        the scrubber's worklist: only these hold content worth decoding."""
        return tuple(sorted(self._refs))

    def free_pages(self) -> tuple:
        """Sorted ids of free (allocatable, unreferenced) pages. Their
        content is known — all-zero after the free-time zeroing — so a
        scrubber restores them by re-zeroing, clearing even uncorrectable
        patterns that injection may have left behind."""
        return tuple(sorted(self._free))


def set_slot_pages(cache: dict, slot: int, page_ids: Sequence[int],
                   *, fill: Optional[int] = None) -> dict:
    """Point ``slot``'s page-table row at ``page_ids`` (logical order),
    padding the unallocated tail with ``fill`` (default: the slot's parking
    page). Tail entries are only ever gathered — never written, and masked
    by token validity — so parking is safe. Returns the updated cache."""
    npg = cache["kv_table"].shape[2]
    if len(page_ids) > npg:
        raise ValueError(f"{len(page_ids)} pages > pages_per_seq {npg}")
    row = np.full((npg,), slot if fill is None else fill, np.int32)
    row[:len(page_ids)] = page_ids
    return {**cache,
            "kv_table": cache["kv_table"].at[:, slot, :].set(
                jnp.asarray(row))}


def copy_page(cache: dict, src: int, dst: int) -> dict:
    """Copy one pool page (encoded bytes, parity planes AND per-token
    scales) across all layers — the copy-on-write primitive: when a slot
    first appends into a page it only holds a shared read-only mapping to,
    the front-end copies the page into a private one it owns, repoints its
    table entry, and drops the shared reference."""
    new = dict(cache)
    for key in ("k_pages", "v_pages", "k_scale", "v_scale",
                "k_checks", "v_checks"):
        if key in new:
            new[key] = new[key].at[:, dst].set(new[key][:, src])
    return new


def zero_pages(cache: dict, page_ids: Sequence[int]) -> dict:
    """Zero the given pool pages (encoded bytes, parity planes, AND
    per-token scales) across all layers. Zero pages are codec-clean for
    every scheme, so a freed page re-enters the pool with no stale-scale or
    stale-parity carryover — the free-side half of page reuse hygiene."""
    if len(page_ids) == 0:
        return cache
    ids = jnp.asarray(tuple(page_ids), jnp.int32)
    new = dict(cache)
    for key in ("k_pages", "v_pages", "k_scale", "v_scale",
                "k_checks", "v_checks"):
        if key in new:
            new[key] = new[key].at[:, ids].set(0)
    return new


# ---------------------------------------------------------------------------
# decode-at-use attention
# ---------------------------------------------------------------------------


def _reference_paged_attention(q, ke, kch, ksc, ve, vch, vsc, pos,
                               policy: KVProtectionPolicy):
    """XLA decode-then-attend reference over gathered strips: block decode
    -> dequantize -> stock ``layers.decode_attention``. Returns
    (o (B, H, 1, hd), corrected, due) with flags counted over valid
    (``<= pos``) tokens only — the fused kernel must match ``o``
    bit-for-bit."""
    dtype = q.dtype
    kq, kcor, kdue = _decode_kv(ke, kch, policy.scheme, policy.backend)
    vq, vcor, vdue = _decode_kv(ve, vch, policy.scheme, policy.backend)
    kf = (kq.astype(jnp.float32) * ksc[..., None, None]).astype(dtype)
    vf = (vq.astype(jnp.float32) * vsc[..., None, None]).astype(dtype)
    s = ke.shape[1]
    rep = q.shape[1] // kf.shape[2]
    kh = jnp.repeat(kf, rep, axis=2).transpose(0, 2, 1, 3)   # (B, H, S, hd)
    vh = jnp.repeat(vf, rep, axis=2).transpose(0, 2, 1, 3)
    valid = jnp.arange(s)[None, :] <= pos[:, None]
    o = L.decode_attention(q, kh, vh, valid)
    vm = valid.astype(jnp.int32)
    if policy.per_slot_flags:  # (B,) rows — per-request fault attribution
        return (o, jnp.sum((kcor + vcor) * vm, axis=1),
                jnp.sum((kdue + vdue) * vm, axis=1))
    return o, jnp.sum((kcor + vcor) * vm), jnp.sum((kdue + vdue) * vm)


def paged_gqa_decode(p, x, cfg: ArchConfig, lc, *, pos, wt=L.Identity,
                     policy: KVProtectionPolicy):
    """Paged, protected drop-in for ``layers.gqa_decode``. x: (B, 1, D);
    ``lc`` is this layer's slice of the paged cache (see
    :func:`init_paged_cache`). Encodes the new token into its page, then
    attends over the decoded-at-use pool. Returns (out, new_lc) and records
    the masked (corrected, DUE) counts into the KV flags sink."""
    b = x.shape[0]
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = L._proj(x, p["wq"], p.get("bq"), wt).reshape(b, 1, h, hd)
    k = L._proj(x, p["wk"], p.get("bk"), wt).reshape(b, 1, kv, hd)
    v = L._proj(x, p["wv"], p.get("bv"), wt).reshape(b, 1, kv, hd)
    q = L.apply_rope(q, pos[:, None], cfg.rope_theta)
    k = L.apply_rope(k, pos[:, None], cfg.rope_theta)
    table = lc["kv_table"]
    ke1, kch1, ksc1 = _encode_kv(k[:, 0], policy)            # (B, kv, hd)
    ve1, vch1, vsc1 = _encode_kv(v[:, 0], policy)
    kp, kc, ks = _write_token(lc["k_pages"], lc.get("k_checks"),
                              lc["k_scale"], table, ke1, kch1, ksc1, pos)
    vp, vc, vs = _write_token(lc["v_pages"], lc.get("v_checks"),
                              lc["v_scale"], table, ve1, vch1, vsc1, pos)
    new_lc = {"k_pages": kp, "v_pages": vp, "k_scale": ks, "v_scale": vs,
              "kv_table": table}
    if kc is not None:
        new_lc["k_checks"], new_lc["v_checks"] = kc, vc

    ke, kch, ksc = _gather_seq(kp, kc, ks, table)
    ve, vch, vsc = _gather_seq(vp, vc, vs, table)
    qh = q.transpose(0, 2, 1, 3)                             # (B, H, 1, hd)
    if policy.attention_impl == "chunked":
        # page-chunked online-softmax fast path: VMEM bounded by the chunk,
        # tolerance-gated against the fp64 oracle (NOT bit-identical)
        from repro.kernels import paged_attention
        o, flags = paged_attention.chunked_page_attention(
            qh, ke, kch, ksc, ve, vch, vsc, pos,
            scheme=policy.scheme,
            chunk_tokens=policy.chunk_pages * policy.page_size,
            per_slot=policy.per_slot_flags)
        L.record_kv_flags(flags[0], flags[1])
    elif policy.fused:
        from repro.kernels import paged_attention
        o, flags = paged_attention.fused_page_attention(
            qh, ke, kch, ksc, ve, vch, vsc, pos,
            scheme=policy.scheme, per_slot=policy.per_slot_flags)
        L.record_kv_flags(flags[0], flags[1])
    else:
        o, corrected, due = _reference_paged_attention(
            qh, ke, kch, ksc, ve, vch, vsc, pos, policy)
        L.record_kv_flags(corrected, due)
    o = o.transpose(0, 2, 1, 3).reshape(b, 1, h * hd)
    return L._proj(o, p["wo"], None, wt), new_lc


def paged_gqa_prefill(p, x, cfg: ArchConfig, lc, *, positions,
                      wt=L.Identity, policy: KVProtectionPolicy,
                      chunk: int = 2048):
    """Prefill counterpart: project/rope the whole sequence, encode it into
    pages, then attend over the **decoded** pages (chunked causal) — the
    logits reflect exactly the state later decode steps will read, and the
    at-rest -> at-use round trip is exercised from token 0. x: (B, S, D).
    Returns (out, new_lc)."""
    b, s, _ = x.shape
    h, kv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    q = L._proj(x, p["wq"], p.get("bq"), wt).reshape(b, s, h, hd)
    k = L._proj(x, p["wk"], p.get("bk"), wt).reshape(b, s, kv, hd)
    v = L._proj(x, p["wv"], p.get("bv"), wt).reshape(b, s, kv, hd)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)

    ps = lc["k_pages"].shape[1]
    pad = (-s) % ps
    if pad:  # zero-pad to whole pages; padded tokens are masked below
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    table = lc["kv_table"]
    ke, kch, ksc = _encode_kv(k, policy)                     # (B, S', kv, hd)
    ve, vch, vsc = _encode_kv(v, policy)
    kp, kc, ks = _write_pages(lc["k_pages"], lc.get("k_checks"),
                              lc["k_scale"], table, ke, kch, ksc)
    vp, vc, vs = _write_pages(lc["v_pages"], lc.get("v_checks"),
                              lc["v_scale"], table, ve, vch, vsc)
    new_lc = {"k_pages": kp, "v_pages": vp, "k_scale": ks, "v_scale": vs,
              "kv_table": table}
    if kc is not None:
        new_lc["k_checks"], new_lc["v_checks"] = kc, vc

    kq, kcor, kdue = _decode_kv(ke, kch, policy.scheme, policy.backend)
    vq, vcor, vdue = _decode_kv(ve, vch, policy.scheme, policy.backend)
    kf = (kq.astype(jnp.float32) * ksc[..., None, None]).astype(x.dtype)
    vf = (vq.astype(jnp.float32) * vsc[..., None, None]).astype(x.dtype)
    kf, vf = kf[:, :s], vf[:, :s]
    rep = h // kv
    qh = L.constrain_heads(q.transpose(0, 2, 1, 3))
    kh = L.constrain_heads(jnp.repeat(kf, rep, axis=2).transpose(0, 2, 1, 3))
    vh = L.constrain_heads(jnp.repeat(vf, rep, axis=2).transpose(0, 2, 1, 3))
    o = L.chunked_causal_attention(qh, kh, vh, chunk=chunk)
    live = (jnp.arange(ke.shape[1]) < s).astype(jnp.int32)[None, :]
    if policy.per_slot_flags:
        L.record_kv_flags(jnp.sum((kcor + vcor) * live, axis=1),
                          jnp.sum((kdue + vdue) * live, axis=1))
    else:
        L.record_kv_flags(jnp.sum((kcor + vcor) * live),
                          jnp.sum((kdue + vdue) * live))
    o = o.transpose(0, 2, 1, 3).reshape(b, s, h * hd)
    return L._proj(o, p["wo"], None, wt), new_lc


# ---------------------------------------------------------------------------
# campaign adapters: pools <-> ProtectedTensor trees
# ---------------------------------------------------------------------------


def as_protected_tree(cache: dict, policy) -> dict:
    """Wrap the k/v pools as same-shape :class:`ProtectedTensor` leaves so
    the generic protection machinery (``inject_tree_device``,
    ``decode_tree_with_flags``, the campaign engine) drives KV fault
    campaigns unchanged. The per-token scale broadcasts over (kv, hd)."""
    policy = get_kv_policy(policy)
    out = {}
    for name in ("k", "v"):
        pages = cache[f"{name}_pages"]
        out[name] = ProtectedTensor(
            enc=pages, checks=cache.get(f"{name}_checks"),
            scale=cache[f"{name}_scale"][..., None, None],
            scheme_id=policy.scheme, orig_shape=tuple(pages.shape))
    return out


def from_protected_tree(cache: dict, tree: dict) -> dict:
    """Write a (possibly fault-injected) ProtectedTensor pair back into a
    paged cache — the campaign's path from injected pools to live serving."""
    new = dict(cache)
    for name in ("k", "v"):
        pt = tree[name]
        new[f"{name}_pages"] = pt.enc
        if pt.checks is not None:
            new[f"{name}_checks"] = pt.checks
    return new


def tree_layer_flags(tree: dict, backend="xla") -> jnp.ndarray:
    """Per-layer (corrected, due) over a KV ProtectedTensor pair ->
    (n_layers, 2) int32 — the campaign-side view of the per-layer rows the
    serve step surfaces. Counts the whole pool (validity-blind: an injected
    fault in a stale slot still counts as detected)."""
    out = None
    for name in ("k", "v"):
        pt = tree[name]
        _, cor, due = _decode_kv(pt.enc, pt.checks, pt.scheme_id, backend)
        axes = tuple(range(1, cor.ndim))
        pair = jnp.stack([jnp.sum(cor, axis=axes),
                          jnp.sum(due, axis=axes)], axis=-1)
        out = pair if out is None else out + pair
    return out


def cache_layer_flags(cache: dict, policy, backend=None) -> jnp.ndarray:
    """:func:`tree_layer_flags` directly on a paged cache dict."""
    policy = get_kv_policy(policy)
    return tree_layer_flags(as_protected_tree(cache, policy),
                            backend or policy.backend)


# ---------------------------------------------------------------------------
# HBM accounting
# ---------------------------------------------------------------------------


def kv_bytes(cache: dict) -> dict:
    """Where the cache's HBM goes: {"stored": encoded page bytes, "checks":
    out-of-place check bytes, "scales": per-token scale bytes, "tables":
    page-table bytes, "total": all of it}. Works on both paged and dense
    caches (a dense cache is all "stored")."""
    out = {"stored": 0, "checks": 0, "scales": 0, "tables": 0}
    for key, a in cache.items():
        nb = int(math.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
        if key.endswith("_checks"):
            out["checks"] += nb
        elif key.endswith("_scale"):
            out["scales"] += nb
        elif key == "kv_table":
            out["tables"] += nb
        else:
            out["stored"] += nb
    out["total"] = sum(out.values())
    return out


def dense_kv_bytes(cfg: ArchConfig, batch: int, max_len: int,
                   dtype=jnp.bfloat16) -> int:
    """Bytes of the dense bf16 cache the paged pool replaces (per model)."""
    from repro.models import lm
    cache = jax.eval_shape(lambda: lm.init_cache(cfg, batch, max_len, dtype))
    return sum(int(math.prod(a.shape)) * jnp.dtype(a.dtype).itemsize
               for a in jax.tree_util.tree_leaves(cache))
