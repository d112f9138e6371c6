"""Backend dispatch: route codec compute through XLA or Pallas.

Every scheme op that touches 64-bit ECC blocks goes through a ``Backend``
object, selected by a single ``backend=`` switch anywhere in the public API:

* ``"xla"``    — the pure-jnp reference path (``core.ecc`` / ``kernels.ref``).
  Works everywhere, fuses into the surrounding XLA program; this is what the
  decode-on-read serving path compiles today.
* ``"pallas"`` — the fused TPU kernels (``kernels/ops.py``): tiled VMEM
  decode/encode and the decode+matmul ``ecc_qmatmul``. Compiled on a TPU
  backend and interpreted on CPU (``kernels.platform``), so the same switch
  validates on CPU and runs natively on the chip.

Backends only differ for the in-place (64,57,1) code — parity/secded72 have
no Pallas kernels and always take the jnp path inside their schemes.
"""
from __future__ import annotations

import json
import math

import jax
import jax.numpy as jnp

from repro.core import ecc

__all__ = ["Backend", "XlaBackend", "PallasBackend", "get_backend",
           "BACKENDS", "AutotuneTable", "BENCH_KERNELS_SCHEMA",
           "BENCH_KERNELS_SCHEMA_V1", "BENCH_KERNELS_SCHEMA_V2",
           "BENCH_KERNELS_SCHEMA_V3", "BENCH_KERNELS_SCHEMA_V4",
           "BENCH_KERNELS_SCHEMA_V5"]


class Backend:
    """Interface: in-place-code block ops + the fused protected matmul."""

    name = "abstract"

    def encode64(self, blocks: jnp.ndarray) -> jnp.ndarray:
        """(..., 8) uint8 WOT-compliant bytes -> encoded (..., 8)."""
        raise NotImplementedError

    def decode64(self, blocks: jnp.ndarray):
        """(..., 8) uint8 encoded -> (decoded (..., 8), single, double)."""
        raise NotImplementedError

    def qmatmul(self, a_q: jnp.ndarray, w_enc: jnp.ndarray, a_scale,
                w_scale) -> jnp.ndarray:
        """a_q (M,K) int8 @ decode(w_enc (K,N) uint8) * scales -> (M,N) f32."""
        raise NotImplementedError


class XlaBackend(Backend):
    name = "xla"

    def encode64(self, blocks):
        return ecc.encode64(blocks)

    def decode64(self, blocks):
        return ecc.decode64(blocks)

    def qmatmul(self, a_q, w_enc, a_scale, w_scale):
        from repro.kernels import ref
        acc = ref.ecc_qmatmul_ref(a_q, w_enc)
        return acc.astype(jnp.float32) * (a_scale * w_scale)


class PallasBackend(Backend):
    """Tiled VMEM kernels over the byte plane a block view comes from
    (``kernels.ecc_decode.plane``), any block shape; ``blk_n`` is the
    blocks per grid step."""

    name = "pallas"

    def __init__(self, *, blk_n: int = 32768):
        self.blk_n = blk_n

    def encode64(self, blocks):
        from repro.kernels import ecc_encode
        return ecc_encode.ecc_encode(blocks, blk_n=self.blk_n)

    def decode64(self, blocks):
        from repro.kernels import ecc_decode
        dec, flags = ecc_decode.ecc_decode(blocks.astype(jnp.uint8),
                                           blk_n=self.blk_n)
        return dec, (flags & 1) == 1, (flags & 2) == 2

    def qmatmul(self, a_q, w_enc, a_scale, w_scale):
        from repro.kernels import ops
        return ops.qmatmul_protected(a_q, w_enc, a_scale, w_scale)


BACKENDS = {"xla": XlaBackend, "pallas": PallasBackend}

BENCH_KERNELS_SCHEMA_V1 = "bench_kernels/v1"
BENCH_KERNELS_SCHEMA_V2 = "bench_kernels/v2"
BENCH_KERNELS_SCHEMA_V3 = "bench_kernels/v3"
BENCH_KERNELS_SCHEMA_V4 = "bench_kernels/v4"
BENCH_KERNELS_SCHEMA_V5 = "bench_kernels/v5"
BENCH_KERNELS_SCHEMA = "bench_kernels/v6"


class AutotuneTable:
    """Shape-keyed backend + tile choice, fed by
    ``benchmarks/kernel_bench.py``.

    Each entry is ``{"shape": [...], "nblocks": int, "xla_us": float,
    "pallas_us": float, "best": "xla"|"pallas"}``; ``bench_kernels/v2``
    entries additionally carry ``"tiles": [bm, bn, bk]`` (the fused
    decode+matmul kernel's best tile sweep result for that shape) and
    ``"fused_us"``; ``bench_kernels/v3`` entries add the int8-epilogue rows
    ``"int8_tiles": [bm, bn, 0]`` and ``"fused_int8_us"`` (the quantized
    serving path — the epilogue always runs full-K tiles, so bk is 0).
    ``bench_kernels/v4`` artifacts additionally carry a top-level
    ``"attention"`` list: fused page-attention (decode-at-use over the
    protected KV cache) vs decode-then-attend reference timings per
    ``(batch, seq, kv_heads, head_dim)`` shape and KV scheme — surfaced on
    :attr:`attention` for reporting, not consulted by the lookups.
    ``bench_kernels/v5`` adds the long-context rows: a top-level
    ``"attention_long"`` list (page-chunked online-softmax kernel vs the
    whole-strip kernel per sequence length, with each length's strip-VMEM
    footprint and chunked-vs-fp64-oracle error) and ``"crossover"`` (the
    structural strip-VMEM crossover: the first sequence length whose
    gathered strip no longer fits the per-core VMEM budget, where the
    chunked kernel becomes the only honest route). ``bench_kernels/v6``
    entries add the ABFT overhead rows ``"fused_abft_us"`` and
    ``"fused_int8_abft_us"``: the same winning tiles re-timed with
    in-kernel checksum verification on (see docs/abft.md) — reporting
    only, the lookups never consult them. v1–v5 artifacts still load —
    their entries simply have no (int8) tile opinion, no ABFT timings,
    and empty :attr:`attention` / :attr:`attention_long`.

    :meth:`lookup` (backend choice) resolves an exact shape match first,
    then the nearest entry by 64-bit-block count within a 4x factor, else
    ``None`` — so the policy's default backend still decides for shapes the
    benchmark never measured. :meth:`lookup_tiles` /
    :meth:`lookup_int8_tiles` are softer: tiles are a hint, not a route, so
    past the exact match they fall back to the nearest tile-bearing entry by
    block count with NO ratio cap (the old behaviour silently used the
    kernel's hardcoded defaults instead); :meth:`lookup_tiles_src` also
    reports where the answer came from (``"exact"`` | ``"nearest"`` | ``""``)
    so plans can surface extrapolated tile choices.
    """

    def __init__(self, entries=(), *, platform: str = "", source: str = "",
                 schema: str = BENCH_KERNELS_SCHEMA, attention=(),
                 attention_long=(), crossover=None):
        self.attention = [dict(a) for a in attention]
        self.attention_long = [dict(a) for a in attention_long]
        self.crossover = dict(crossover) if crossover else None
        self.entries = []
        for e in entries:
            e = dict(e)
            shape = tuple(int(s) for s in e.get("shape", ()))
            if e.get("best") not in BACKENDS:
                raise ValueError(f"autotune entry for shape {shape} has "
                                 f"unknown best backend {e.get('best')!r}")
            e["shape"] = shape
            e.setdefault("nblocks",
                         int(math.prod(shape)) // 8 if shape else 0)
            for key in ("tiles", "int8_tiles"):
                if e.get(key) is not None:
                    e[key] = tuple(int(t) for t in e[key])
            self.entries.append(e)
        self.platform = platform
        self.source = source
        self.schema = schema
        self._by_shape = {e["shape"]: e for e in self.entries}

    def __len__(self) -> int:
        return len(self.entries)

    def _nearest(self, shape) -> dict | None:
        """Exact shape entry, else nearest by block count within 4x."""
        shape = tuple(int(s) for s in shape)
        hit = self._by_shape.get(shape)
        if hit is not None:
            return hit
        nblk = int(math.prod(shape)) // 8 if shape else 0
        if nblk <= 0 or not self.entries:
            return None
        nearest = min(self.entries,
                      key=lambda e: abs(math.log(max(e["nblocks"], 1) / nblk)))
        ratio = max(nearest["nblocks"], 1) / nblk
        if ratio > 4 or ratio < 0.25:
            return None
        return nearest

    def lookup(self, shape) -> str | None:
        """Best backend name for a weight shape, or None when the table has
        nothing close enough to say."""
        e = self._nearest(shape)
        return e["best"] if e is not None else None

    def lookup_tiles_src(self, shape, *, key: str = "tiles") -> tuple:
        """-> ``(tiles | None, source)`` for a weight shape, with source
        ``"exact"`` (shape match), ``"nearest"`` (nearest tile-bearing entry
        by block count — tiles extrapolate, unlike backend choices, so no
        ratio cap), or ``""`` (no entry carries this tile key at all)."""
        shape = tuple(int(s) for s in shape)
        hit = self._by_shape.get(shape)
        if hit is not None and hit.get(key):
            return tuple(hit[key]), "exact"
        with_tiles = [e for e in self.entries if e.get(key)]
        nblk = int(math.prod(shape)) // 8 if shape else 0
        if nblk <= 0 or not with_tiles:
            return None, ""
        nearest = min(with_tiles,
                      key=lambda e: abs(math.log(max(e["nblocks"], 1) / nblk)))
        return tuple(nearest[key]), "nearest"

    def lookup_tiles(self, shape) -> tuple | None:
        """Best fused-kernel (bm, bn, bk) for a weight shape — exact match
        or nearest tile-bearing entry; None only when no entry has tiles
        (a v1 artifact)."""
        return self.lookup_tiles_src(shape)[0]

    def lookup_int8_tiles(self, shape) -> tuple | None:
        """Best int8-epilogue (bm, bn, 0) tiles — same resolution as
        :meth:`lookup_tiles`; None for pre-v3 artifacts."""
        return self.lookup_tiles_src(shape, key="int8_tiles")[0]

    def to_dict(self) -> dict:
        d = {"schema": self.schema, "platform": self.platform,
             "entries": [{**e, "shape": list(e["shape"]),
                          **{k: list(e[k]) for k in
                             ("tiles", "int8_tiles") if e.get(k)}}
                         for e in self.entries]}
        if self.attention:
            d["attention"] = [dict(a) for a in self.attention]
        if self.attention_long:
            d["attention_long"] = [dict(a) for a in self.attention_long]
        if self.crossover:
            d["crossover"] = dict(self.crossover)
        return d

    @classmethod
    def from_dict(cls, d: dict, *, source: str = "") -> "AutotuneTable":
        schema = d.get("schema", "")
        known = (BENCH_KERNELS_SCHEMA, BENCH_KERNELS_SCHEMA_V5,
                 BENCH_KERNELS_SCHEMA_V4, BENCH_KERNELS_SCHEMA_V3,
                 BENCH_KERNELS_SCHEMA_V2, BENCH_KERNELS_SCHEMA_V1)
        if schema and schema not in known:
            raise ValueError(
                f"unsupported autotune schema {schema!r} (expected one of "
                f"{known})")
        return cls(d.get("entries", ()), platform=d.get("platform", ""),
                   source=source, schema=schema or BENCH_KERNELS_SCHEMA_V1,
                   attention=d.get("attention", ()),
                   attention_long=d.get("attention_long", ()),
                   crossover=d.get("crossover"))

    @classmethod
    def from_json(cls, path) -> "AutotuneTable":
        with open(path) as f:
            return cls.from_dict(json.load(f), source=str(path))


def get_backend(backend, **kw) -> Backend:
    """Resolve a backend name or pass an instance through."""
    if isinstance(backend, Backend):
        return backend
    if backend is None:
        backend = "xla"
    try:
        return BACKENDS[backend](**kw)
    except KeyError:
        raise ValueError(
            f"unknown backend {backend!r}; one of {sorted(BACKENDS)}") from None
