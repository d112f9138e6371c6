"""Kernel micro-benchmarks. On a CPU host the Pallas kernels run in the
interpreter, so their timings say nothing about the chip; the structural
rooflines are then computed for ``peaks.TARGET_KIND``. On a TPU they use
the attached chip's published peaks (``benchmarks/peaks.py``; an unknown
chip is an error).

Reports, per kernel: reference-path us/call and the STRUCTURAL cost of the
kernel (bytes moved, flops, roofline-bound time).

``--json BENCH_kernels.json`` additionally times the in-place decode on BOTH
backends per weight shape, sweeps fused decode+matmul tiles for the float
path AND the int8 requantize-epilogue path, times fused page-attention
(decode-at-use over the protected KV cache) against its decode-then-attend
reference per KV scheme, times the page-chunked online-softmax kernel
against the whole-strip kernel at long contexts (with the strip kernel's
VMEM crossover and the chunked-vs-fp64-oracle error), re-times each path's
winning tiles with in-kernel ABFT checksums on (the overhead rows), and
writes the ``bench_kernels/v6`` artifact that
``protection.AutotuneTable`` consumes — per-leaf backend AND tile choices
(float ``tiles`` + ``int8_tiles``) are then reproducible from a checked-in
file instead of call-site defaults (``--tiles-smoke`` shrinks the sweep for
CI).  The artifact records the device and whether the kernels were
interpreted (``pallas_interpret``), so a TPU re-run can overwrite a CPU
table.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import protection
from repro.core import ecc
from repro.kernels import platform, ref
from repro.launch.compile_cache import setup_compile_cache

try:                      # run as a script or imported as benchmarks.*
    from . import peaks as _peaks
except ImportError:
    import peaks as _peaks


def _device_peaks() -> tuple:
    """(device kind the rooflines are for, its published peaks): the
    attached TPU, or the structural target on a host without one."""
    dev = jax.devices()[0]
    kind = dev.device_kind if dev.platform == "tpu" else _peaks.TARGET_KIND
    return kind, _peaks.peaks(kind)


_KIND, _P = _device_peaks()
PEAK_BW = _P["hbm_bytes_per_s"]
PEAK_INT8 = _P["int8_ops"]


def _time(f, *args, reps=5):
    jax.block_until_ready(f(*args))  # ONE warmup call (compile + execute)
    t0 = time.time()
    for _ in range(reps):
        jax.block_until_ready(f(*args))
    return (time.time() - t0) / reps * 1e6


def bench_decode(n_weights=2 ** 22):
    rng = np.random.default_rng(0)
    w = rng.integers(-64, 64, size=(n_weights // 8, 8)).astype(np.int8)
    enc = ecc.encode64(jnp.asarray(w.view(np.uint8)))
    f = jax.jit(ref.ecc_decode_ref)
    us = _time(f, enc)
    # structural: reads n bytes, writes n bytes + n/8 flags
    bytes_moved = 2 * n_weights + n_weights // 8
    roof_us = bytes_moved / PEAK_BW * 1e6
    return us, bytes_moved, roof_us


def bench_qmatmul(m=512, k=1024, n=1024):
    rng = np.random.default_rng(1)
    a = jnp.asarray(rng.integers(-127, 128, size=(m, k)).astype(np.int8))
    w = rng.integers(-64, 64, size=(k, n)).astype(np.int8)
    enc = jnp.asarray(np.asarray(ecc.encode64(jnp.asarray(
        w.view(np.uint8).reshape(k, n // 8, 8)))).reshape(k, n))
    f = jax.jit(ref.ecc_qmatmul_ref)
    us = _time(f, a, enc)
    flops = 2 * m * k * n
    bytes_moved = m * k + k * n + m * n * 4
    roof_us = max(flops / PEAK_INT8, bytes_moved / PEAK_BW) * 1e6
    return us, flops, roof_us


def bench_throttle(n=2 ** 22):
    rng = np.random.default_rng(2)
    q = jnp.asarray(rng.integers(-128, 128, size=(n // 8, 8)).astype(np.int8))
    f = jax.jit(ref.throttle_ref)
    us = _time(f, q)
    roof_us = 2 * n / PEAK_BW * 1e6
    return us, 2 * n, roof_us


# Weight shapes the autotune table covers: decode-serving projections from
# small attention heads up to MLP blocks. Keep the list short — Pallas
# interpret mode on CPU makes each cell cost real seconds.
AUTOTUNE_SHAPES = ((256, 256), (256, 1024), (1024, 1024), (2048, 4096))

# (bm, bn, bk) candidates for the fused decode+matmul sweep. bk=0 means
# full-K tiles (one dot per output tile — the serving default). The smoke
# grid keeps CI wall-clock tolerable in interpret mode.
TILE_SWEEP = ((128, 128, 0), (128, 128, 128), (128, 256, 128),
              (256, 128, 128), (64, 128, 256), (128, 512, 0))
TILE_SWEEP_SMOKE = ((128, 128, 0), (128, 128, 128))


def _enc_weight(rng, k, n):
    w = rng.integers(-64, 64, size=(k, n)).astype(np.int8)
    return jnp.asarray(np.asarray(ecc.encode64(jnp.asarray(
        w.view(np.uint8).reshape(k, n // 8, 8)))).reshape(k, n))


def bench_backend_decode(shapes=AUTOTUNE_SHAPES, reps=3):
    """Per-shape in-place decode timings on both backends -> autotune
    entries (without tile data; :func:`bench_fused_tiles` adds it)."""
    rng = np.random.default_rng(7)
    entries = []
    for k, n in shapes:
        enc = _enc_weight(rng, k, n)
        us = {}
        for name in ("xla", "pallas"):
            be = protection.get_backend(name)
            f = jax.jit(lambda e, be=be: be.decode64(
                e.reshape(k, n // 8, 8))[0])
            us[name] = _time(f, enc, reps=reps)
        entries.append({"shape": [k, n], "nblocks": k * n // 8,
                        "xla_us": round(us["xla"], 1),
                        "pallas_us": round(us["pallas"], 1),
                        "best": min(us, key=us.get)})
    return entries


def bench_fused_tiles(entries, m=128, tile_sweep=TILE_SWEEP, reps=3):
    """Sweep fused decode+matmul tiles per shape and record the winner into
    each entry (``tiles`` + ``fused_us``), plus the int8 requantize-epilogue
    sweep (``int8_tiles`` + ``fused_int8_us`` — the ``bench_kernels/v3``
    fields; the epilogue always runs full-K tiles, so only (bm, bn) sweep).
    Also times the XLA references: decode-then-matmul as ``fused_ref_us``
    and decode-then-matmul-then-requantize as ``int8_ref_us``; and the
    ABFT-on twins at each path's winning tiles (``fused_abft_us`` /
    ``fused_int8_abft_us`` — the ``bench_kernels/v6`` fields) so the
    in-kernel checksum overhead is priced next to the unguarded row."""
    from repro.kernels import ref
    from repro.kernels.ecc_qmatmul import ecc_qmatmul
    rng = np.random.default_rng(11)
    for e in entries:
        k, n = e["shape"]
        enc = _enc_weight(rng, k, n)
        a = jnp.asarray(rng.integers(-127, 128, size=(m, k)).astype(np.int8))
        a_scale = jnp.asarray(rng.uniform(0.005, 0.02, size=(m, 1))
                              .astype(np.float32))
        w_scale = jnp.float32(0.01)
        best_us, best_tiles = None, None
        for bm, bn, bk in tile_sweep:
            f = jax.jit(lambda a_, e_, t=(bm, bn, bk): ecc_qmatmul(
                a_, e_, bm=t[0], bn=t[1], bk=t[2]))
            us = _time(f, a, enc, reps=reps)
            if best_us is None or us < best_us:
                best_us, best_tiles = us, (bm, bn, bk)
        e["tiles"] = list(best_tiles)
        e["fused_us"] = round(best_us, 1)
        e["fused_ref_us"] = round(
            _time(jax.jit(ref.ecc_qmatmul_ref), a, enc, reps=reps), 1)
        # ABFT twin at the winning tiles: same call, checksum rows/cols
        # verified in-kernel. Return the full (out, (rows, col_mm)) tuple
        # so XLA can't dead-code the checksum outputs away.
        f_ab = jax.jit(lambda a_, e_, t=best_tiles: ecc_qmatmul(
            a_, e_, bm=t[0], bn=t[1], bk=t[2], with_abft=True))
        e["fused_abft_us"] = round(_time(f_ab, a, enc, reps=reps), 1)
        # int8 requantize epilogue: int32 acc * (a_scale*w_scale) -> bf16
        best_us, best_tiles = None, None
        for bm, bn in sorted({(t[0], t[1]) for t in tile_sweep}):
            f = jax.jit(lambda a_, e_, s_, t=(bm, bn): ecc_qmatmul(
                a_, e_, w_scale, a_scale=s_, bm=t[0], bn=t[1]))
            us = _time(f, a, enc, a_scale, reps=reps)
            if best_us is None or us < best_us:
                best_us, best_tiles = us, (bm, bn, 0)
        e["int8_tiles"] = list(best_tiles)
        e["fused_int8_us"] = round(best_us, 1)
        f_ab = jax.jit(lambda a_, e_, s_, t=best_tiles: ecc_qmatmul(
            a_, e_, w_scale, a_scale=s_, bm=t[0], bn=t[1], with_abft=True))
        e["fused_int8_abft_us"] = round(
            _time(f_ab, a, enc, a_scale, reps=reps), 1)
        ref_int8 = jax.jit(lambda a_, e_, s_: (
            ref.ecc_qmatmul_ref(a_, e_).astype(jnp.float32) *
            (s_ * w_scale)).astype(jnp.bfloat16))
        e["int8_ref_us"] = round(_time(ref_int8, a, enc, a_scale, reps=reps),
                                 1)
    return entries


# (batch, seq, kv_heads, head_dim) decode-attention shapes for the paged
# protected KV cache rows. Queries use 2x the kv heads (GQA rep=2).
ATTENTION_SHAPES = ((2, 128, 2, 32), (2, 256, 4, 64))


def bench_paged_attention(shapes=ATTENTION_SHAPES, reps=3):
    """Fused page-attention (decode-at-use over the protected KV cache) vs
    the XLA decode-then-attend reference, per shape and KV scheme — the
    ``bench_kernels/v4`` ``attention`` rows. Each row also records whether
    the two paths agreed bit-for-bit on this host (the kernel's contract)."""
    from repro.kernels import paged_attention
    from repro.serving import kvcache
    rng = np.random.default_rng(13)
    rows = []
    for b, s, kv, hd in shapes:
        h = 2 * kv
        q = jnp.asarray(rng.standard_normal((b, h, 1, hd)),
                        dtype=jnp.bfloat16)
        kf = jnp.asarray(rng.standard_normal((b, s, kv, hd)),
                         dtype=jnp.float32)
        vf = jnp.asarray(rng.standard_normal((b, s, kv, hd)),
                         dtype=jnp.float32)
        pos = jnp.full((b,), s - 1, jnp.int32)
        for scheme in kvcache.KV_SCHEMES:
            pol = kvcache.KVProtectionPolicy(scheme=scheme)
            ke, kch, ksc = kvcache._encode_kv(kf, pol)
            ve, vch, vsc = kvcache._encode_kv(vf, pol)

            def fused(q_, scheme=scheme, strips=(ke, kch, ksc, ve, vch, vsc)):
                return paged_attention.fused_page_attention(
                    q_, *strips, pos, scheme=scheme)[0]

            def ref(q_, pol=pol, strips=(ke, kch, ksc, ve, vch, vsc)):
                return kvcache._reference_paged_attention(
                    q_, *strips, pos, pol)[0]

            f, r = jax.jit(fused), jax.jit(ref)
            fused_us = _time(f, q, reps=reps)
            ref_us = _time(r, q, reps=reps)
            rows.append({"shape": [b, s, kv, hd], "scheme": scheme,
                         "fused_us": round(fused_us, 1),
                         "ref_us": round(ref_us, 1),
                         "bitexact": bool(np.array_equal(
                             np.asarray(f(q)), np.asarray(r(q))))})
    return rows


# Long-context single-sequence decode shapes (batch 1, one kv head, GQA
# rep 2, head_dim 128) for the chunked-vs-strip rows. The last length sits
# BEYOND the strip kernel's structural VMEM crossover (~8.1k tokens at
# head_dim 128), where the chunked kernel is the only honest TPU route.
ATTENTION_LONG_LENGTHS = (2048, 4096, 8192, 10240)
ATTENTION_LONG_LENGTHS_SMOKE = (512, 1024)


def bench_chunked_attention(lengths=ATTENTION_LONG_LENGTHS,
                            chunk_tokens=2048, hd=128, rep=2, reps=3):
    """Page-chunked online-softmax kernel vs the whole-strip kernel per
    sequence length and KV scheme — the ``bench_kernels/v5``
    ``attention_long`` rows. Each row records the strip kernel's VMEM
    working set against the per-core budget (``over_budget`` marks lengths
    where only the chunked kernel is deployable) and the chunked output's
    max abs error against the fp64 oracle with its tolerance gate.

    Returns ``(rows, crossover)`` where ``crossover`` pins the structural
    strip-VMEM crossover length per scheme for this (head_dim, rep)."""
    from repro.kernels import paged_attention
    from repro.serving import kvcache
    rng = np.random.default_rng(17)
    b, kv = 1, 1
    rows = []
    for s in lengths:
        q = jnp.asarray(rng.standard_normal((b, rep * kv, 1, hd)),
                        dtype=jnp.bfloat16)
        kf = jnp.asarray(rng.standard_normal((b, s, kv, hd)),
                         dtype=jnp.float32)
        vf = jnp.asarray(rng.standard_normal((b, s, kv, hd)),
                         dtype=jnp.float32)
        pos = jnp.full((b,), s - 1, jnp.int32)
        for scheme in kvcache.KV_SCHEMES:
            pol = kvcache.KVProtectionPolicy(scheme=scheme)
            ke, kch, ksc = kvcache._encode_kv(kf, pol)
            ve, vch, vsc = kvcache._encode_kv(vf, pol)

            def chunked(q_):
                return paged_attention.chunked_page_attention(
                    q_, ke, kch, ksc, ve, vch, vsc, pos, scheme=scheme,
                    chunk_tokens=chunk_tokens)[0]

            def strip(q_):
                return paged_attention.fused_page_attention(
                    q_, ke, kch, ksc, ve, vch, vsc, pos, scheme=scheme)[0]

            c, f = jax.jit(chunked), jax.jit(strip)
            chunked_us = _time(c, q, reps=reps)
            strip_us = _time(f, q, reps=reps)
            oracle = paged_attention.oracle_page_attention(
                q, ke, kch, ksc, ve, vch, vsc, pos, scheme=scheme)
            err = float(np.max(np.abs(
                np.asarray(c(q), np.float64) - oracle)))
            tol = 0.02 * (float(np.max(np.abs(oracle))) + 1e-6)
            vmem = paged_attention.strip_vmem_bytes(s, hd, rep, scheme)
            rows.append({
                "shape": [b, s, kv, hd], "scheme": scheme,
                "chunk_tokens": chunk_tokens,
                "chunked_us": round(chunked_us, 1),
                "strip_us": round(strip_us, 1),
                "strip_vmem_bytes": vmem,
                "chunked_vmem_bytes": paged_attention.chunked_vmem_bytes(
                    chunk_tokens, hd, rep, scheme),
                "over_budget":
                    vmem > paged_attention.VMEM_BUDGET_BYTES,
                "oracle_max_abs_err": err, "tol": tol,
                "within_tol": err <= tol,
            })
    crossover = {
        "head_dim": hd, "rep": rep,
        "vmem_budget_bytes": paged_attention.VMEM_BUDGET_BYTES,
        "chunk_tokens": chunk_tokens,
        "tokens_by_scheme": {
            scheme: paged_attention.strip_vmem_crossover(hd, rep, scheme)
            for scheme in kvcache.KV_SCHEMES},
    }
    return rows, crossover


def write_bench_kernels(path, entries=None, *, tile_sweep=TILE_SWEEP,
                        attention=None, attention_long=None,
                        crossover=None) -> dict:
    """Write BENCH_kernels.json in the ``bench_kernels/v6`` schema that
    ``protection.AutotuneTable`` loads (validated by round-tripping through
    it before writing)."""
    dev = jax.devices()[0]
    if entries is None:
        entries = bench_backend_decode()
        if tile_sweep:
            entries = bench_fused_tiles(entries, tile_sweep=tile_sweep)
    if attention is None:
        attention = bench_paged_attention()
    if attention_long is None:
        attention_long, crossover = bench_chunked_attention()
    payload = {"schema": protection.BENCH_KERNELS_SCHEMA,
               "platform": dev.platform,
               "device_kind": dev.device_kind,
               "pallas_interpret": platform.interpret(),
               "op": "in-place-decode64+fused-qmatmul",
               "entries": entries,
               "attention": attention,
               "attention_long": attention_long}
    if crossover:
        payload["crossover"] = crossover
    protection.AutotuneTable.from_dict(payload)  # schema self-check
    with open(path, "w") as f:
        json.dump(payload, f, indent=2)
        f.write("\n")
    return payload


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="also write the per-shape xla-vs-pallas decode + "
                         "fused-tile + paged-attention table "
                         "(BENCH_kernels.json, bench_kernels/v6)")
    ap.add_argument("--tiles-smoke", action="store_true",
                    help="tiny fused-tile sweep + short attention lengths "
                         "(CI smoke; interpret mode)")
    args = ap.parse_args(argv)
    setup_compile_cache()
    print(f"# rooflines for {_KIND}")
    us, b, r = bench_decode()
    print(f"kernel_ecc_decode,{us:.0f},tpu_roofline_us={r:.1f}_bytes={b}")
    us, fl, r = bench_qmatmul()
    print(f"kernel_ecc_qmatmul,{us:.0f},tpu_roofline_us={r:.1f}_flops={fl}")
    us, b, r = bench_throttle()
    print(f"kernel_throttle,{us:.0f},tpu_roofline_us={r:.1f}_bytes={b}")
    if args.json:
        sweep = TILE_SWEEP_SMOKE if args.tiles_smoke else TILE_SWEEP
        lengths = (ATTENTION_LONG_LENGTHS_SMOKE if args.tiles_smoke
                   else ATTENTION_LONG_LENGTHS)
        chunk = 256 if args.tiles_smoke else 2048
        attention_long, crossover = bench_chunked_attention(
            lengths=lengths, chunk_tokens=chunk)
        payload = write_bench_kernels(args.json, tile_sweep=sweep,
                                      attention_long=attention_long,
                                      crossover=crossover)
        for e in payload["entries"]:
            tiles = "x".join(str(t) for t in e.get("tiles", ()))
            i8 = "x".join(str(t) for t in e.get("int8_tiles", ()))
            print(f"autotune_decode_{e['shape'][0]}x{e['shape'][1]},"
                  f"xla={e['xla_us']:.0f}us,pallas={e['pallas_us']:.0f}us,"
                  f"best={e['best']},tiles={tiles},"
                  f"fused={e.get('fused_us', 0):.0f}us,"
                  f"abft={e.get('fused_abft_us', 0):.0f}us,int8_tiles={i8},"
                  f"fused_int8={e.get('fused_int8_us', 0):.0f}us,"
                  f"int8_abft={e.get('fused_int8_abft_us', 0):.0f}us")
        for r in payload.get("attention", ()):
            shp = "x".join(str(t) for t in r["shape"])
            print(f"paged_attention_{shp}_{r['scheme']},"
                  f"{r['fused_us']:.0f},ref_us={r['ref_us']:.0f}"
                  f"_bitexact={str(r['bitexact']).lower()}")
        for r in payload.get("attention_long", ()):
            shp = "x".join(str(t) for t in r["shape"])
            print(f"chunked_attention_{shp}_{r['scheme']},"
                  f"{r['chunked_us']:.0f},strip_us={r['strip_us']:.0f}"
                  f"_over_budget={str(r['over_budget']).lower()}"
                  f"_oracle_err={r['oracle_max_abs_err']:.2e}"
                  f"_within_tol={str(r['within_tol']).lower()}")
        if payload.get("crossover"):
            co = payload["crossover"]
            toks = ",".join(f"{k}={v}" for k, v in
                            sorted(co["tokens_by_scheme"].items()))
            print(f"# strip-VMEM crossover (hd={co['head_dim']} "
                  f"rep={co['rep']}): {toks} tokens "
                  f"@ {co['vmem_budget_bytes']} B budget")
        print(f"# wrote {args.json} ({payload['platform']}, "
              f"pallas_interpret={payload['pallas_interpret']})")


if __name__ == "__main__":
    main()
