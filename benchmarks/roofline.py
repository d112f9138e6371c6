"""Roofline analysis from the dry-run JSONL (EXPERIMENTS.md §Roofline),
structural, for the target chip of ``benchmarks/peaks.py``.

Per (arch x shape x mesh):
  compute    = HLO_FLOPs / (chips * 197 TFLOP/s bf16)
  memory     = HLO_bytes / (chips * 819 GB/s)
  collective = wire_bytes / (chips * 50 GB/s/link ... per-device program, so
               per-chip wire bytes / 50 GB/s)
plus MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE) and the useful-compute
ratio. HLO numbers come from the trip-count-aware HLO parser (per-device
program), so terms are already per-chip.
"""
from __future__ import annotations

import json
import sys

import numpy as np

from repro import configs
from repro.models.config import SHAPES

try:                      # run as a script or imported as benchmarks.roofline
    from . import peaks as _peaks
except ImportError:
    import peaks as _peaks

_P = _peaks.peaks(_peaks.TARGET_KIND)
PEAK_FLOPS = _P["bf16_flops"]
PEAK_INT8 = _P["int8_ops"]
HBM_BW = _P["hbm_bytes_per_s"]
LINK_BW = _P["ici_link_bytes_per_s"]


def param_counts(cfg):
    """(total_params, active_params) analytic."""
    d, v = cfg.d_model, cfg.vocab_padded
    emb = v * d * (1 if cfg.tie_embeddings else 2)
    per_layer_total = per_layer_active = 0
    f = cfg.family
    if f in ("dense", "vlm"):
        attn = d * cfg.n_heads * cfg.head_dim + 2 * d * cfg.n_kv_heads * cfg.head_dim \
            + cfg.n_heads * cfg.head_dim * d
        mlp = 3 * d * cfg.d_ff
        per_layer_total = per_layer_active = attn + mlp
        n_layers = cfg.n_layers
    elif f == "moe":
        r, qr, qn, vd, h = (cfg.kv_lora_rank, cfg.qk_rope_dim, cfg.qk_nope_dim,
                            cfg.v_head_dim, cfg.n_heads)
        attn = d * (r + qr) + r * h * qn + r * h * vd + h * vd * d
        attn += (d * cfg.q_lora_rank + cfg.q_lora_rank * h * (qn + qr)) \
            if cfg.q_lora_rank else d * h * (qn + qr)
        experts = cfg.n_experts * 3 * d * cfg.moe_d_ff
        active = cfg.top_k * 3 * d * cfg.moe_d_ff
        shared = cfg.n_shared_experts * 3 * d * cfg.moe_d_ff
        router = d * cfg.n_experts
        per_layer_total = attn + experts + shared + router
        per_layer_active = attn + active + shared + router
        n_layers = cfg.n_layers
    elif f == "ssm":
        di, n, hd = cfg.d_inner, cfg.ssm_state, cfg.ssm_head_dim
        h = di // hd
        per_layer_total = per_layer_active = \
            d * (2 * di + 2 * n + h) + di * d
        n_layers = cfg.n_layers
    elif f == "hybrid":
        w = cfg.lru_width or d
        rg = d * w * 2 + 2 * w * w + w * d
        attn = d * cfg.n_heads * cfg.head_dim + 2 * d * cfg.n_kv_heads * cfg.head_dim \
            + cfg.n_heads * cfg.head_dim * d
        mlp = 3 * d * cfg.d_ff
        per_layer_total = per_layer_active = \
            (2 * (rg + mlp) + attn + mlp) / 3  # per-layer average
        n_layers = cfg.n_layers
    elif f == "encdec":
        attn = 4 * d * d
        per_layer_total = per_layer_active = attn * 2 + 2 * d * cfg.d_ff
        n_layers = cfg.n_layers + cfg.enc_layers
    total = emb + n_layers * per_layer_total
    active = emb + n_layers * per_layer_active
    return total, active


def model_flops(cfg, shape):
    total, active = param_counts(cfg)
    non_emb = active - cfg.vocab_padded * cfg.d_model * \
        (1 if cfg.tie_embeddings else 2)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6 * active * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2 * active * tokens
    return 2 * active * shape.global_batch  # decode: one token per seq


def analyze(rec):
    cfg = configs.get(rec["arch"])
    shape = SHAPES[rec["shape"]]
    chips = rec.get("n_devices", 256)
    flops = rec.get("hlo_flops", 0.0)           # per-device program
    bytes_ = rec.get("hlo_buffer_bytes", 0.0)
    wire = rec.get("collectives", {}).get("total_wire_bytes", 0.0)
    t_compute = flops / PEAK_FLOPS
    t_memory = bytes_ / HBM_BW
    t_coll = wire / LINK_BW
    terms = {"compute_s": t_compute, "memory_s": t_memory,
             "collective_s": t_coll}
    dominant = max(terms, key=terms.get)
    mf = model_flops(cfg, shape)
    useful = mf / chips / flops if flops else 0.0
    bound_time = max(terms.values())
    frac = (mf / chips / PEAK_FLOPS) / bound_time if bound_time else 0.0
    return {
        "arch": rec["arch"], "shape": rec["shape"], "mesh": rec["mesh"],
        **{k: round(v, 6) for k, v in terms.items()},
        "dominant": dominant.replace("_s", ""),
        "model_flops": mf, "hlo_flops_per_chip": flops,
        "useful_flops_ratio": round(useful, 4),
        "roofline_fraction": round(frac, 4),
    }


def fused_vs_decode_rows(bench_path="BENCH_kernels.json", m=128):
    """Structural roofline bound for the fused decode+matmul vs the XLA
    decode-then-matmul path, per autotune shape — the bound the measured
    BENCH_kernels ``fused_us`` / ``fused_ref_us`` / ``fused_int8_us``
    numbers compare against.

    fused (raw int8): HBM traffic = a (M*K int8) + enc (K*N uint8) +
            out (M*N*4); decode never round-trips through HBM.
    decode-then-matmul: adds a full decoded-weight write + read (2*K*N),
            the exact per-step cost the decode-at-use serve step deletes.
    float serving path: bf16 activations (2*M*K) + f32 out, bf16 MXU peak.
    int8 fused epilogue: int8 activations (M*K — HALF the float path's
            activation traffic) + bf16 out (M*N*2 — half the f32 out),
            int8 MXU peak (2x the bf16 MACs/s).
    """
    shapes = [(1024, 1024), (2048, 4096)]
    try:
        with open(bench_path) as f:
            shapes = [tuple(e["shape"]) for e in json.load(f)["entries"]]
    except (OSError, KeyError, ValueError):
        pass
    rows = []
    for k, n in shapes:
        flops = 2 * m * k * n
        fused_bytes = m * k + k * n + m * n * 4
        split_bytes = fused_bytes + 2 * k * n
        t_fused = max(flops / PEAK_INT8, fused_bytes / HBM_BW) * 1e6
        t_split = max(flops / PEAK_INT8, split_bytes / HBM_BW) * 1e6
        # serving-path structural rows: float (bf16 a, f32 out, bf16 MXU)
        # vs the int8 epilogue (int8 a, bf16 out, int8 MXU)
        float_bytes = 2 * m * k + k * n + 4 * m * n
        int8_bytes = m * k + k * n + 2 * m * n
        t_float = max(flops / PEAK_FLOPS, float_bytes / HBM_BW) * 1e6
        t_int8 = max(flops / PEAK_INT8, int8_bytes / HBM_BW) * 1e6
        r = {"shape": [k, n], "fused_roof_us": round(t_fused, 2),
             "decode_then_matmul_roof_us": round(t_split, 2),
             "traffic_ratio": round(split_bytes / fused_bytes, 3),
             "float_fused_roof_us": round(t_float, 2),
             "int8_fused_roof_us": round(t_int8, 2),
             "int8_speedup": round(t_float / t_int8, 3),
             "int8_traffic_ratio": round(float_bytes / int8_bytes, 3)}
        rows.append(r)
        print(f"roofline_fused_qmatmul_{k}x{n},{t_fused:.1f},"
              f"decode_then_matmul_us={t_split:.1f}"
              f"_traffic_ratio={r['traffic_ratio']}")
        print(f"roofline_int8_fused_{k}x{n},{t_int8:.1f},"
              f"float_us={t_float:.1f}_speedup={r['int8_speedup']}"
              f"_traffic_ratio={r['int8_traffic_ratio']}")
    return rows


def kv_traffic_rows(arch="deepseek-7b", batch=8, seqs=(4096, 32768)):
    """Structural per-decode-step KV-cache HBM traffic for the paged
    protected cache, per KV scheme, vs the dense bf16 ring buffer.

    Every decode step reads the whole cached history once (decode-at-use:
    stored int8 pages + parity checks + per-token scales) and writes one
    token per layer. The dense baseline reads bf16 K/V — 2x the int8
    bytes — so every protected scheme is *less* HBM traffic than dense
    bf16 serving, and in-place's check overhead is exactly zero (the
    zero-space claim, as bytes on the wire per step).
    """
    import jax

    from repro.serving import kvcache
    cfg = configs.get_smoke(arch)
    nl, kv, hd = cfg.n_layers, cfg.n_kv_heads, cfg.head_dim
    rows = []
    for s in seqs:
        dense = 2 * 2 * batch * s * kv * hd * nl       # bf16 K+V read
        for scheme in kvcache.KV_SCHEMES:
            pol = kvcache.KVProtectionPolicy(scheme=scheme)
            cache = jax.eval_shape(
                lambda: kvcache.init_paged_cache(cfg, batch, s, pol))
            kb = kvcache.kv_bytes(cache)
            read = kb["stored"] + kb["checks"] + kb["scales"]
            r = {"arch": arch, "seq": s, "scheme": scheme,
                 "read_bytes_per_step": read,
                 "check_bytes": kb["checks"],
                 "dense_bf16_bytes": dense,
                 "vs_dense_ratio": round(read / dense, 4),
                 "kv_roof_us": round(read / HBM_BW * 1e6, 2)}
            rows.append(r)
            print(f"roofline_kv_{arch}_{s}_{scheme},{r['kv_roof_us']},"
                  f"read={read}_checks={kb['checks']}"
                  f"_vs_dense={r['vs_dense_ratio']}")
    return rows


def main():
    path = sys.argv[1] if len(sys.argv) > 1 else "results/dryrun_16x16.jsonl"
    rows = []
    with open(path) as f:
        for line in f:
            rec = json.loads(line)
            if rec.get("status") != "ok":
                if rec.get("status") == "skipped":
                    print(f"roofline_{rec['arch']}_{rec['shape']},0,skipped")
                continue
            r = analyze(rec)
            rows.append(r)
            print(f"roofline_{r['arch']}_{r['shape']}_{r['mesh']},"
                  f"{max(r['compute_s'], r['memory_s'], r['collective_s']) * 1e6:.0f},"
                  f"dom={r['dominant']}_frac={r['roofline_fraction']}"
                  f"_useful={r['useful_flops_ratio']}")
    fused_vs_decode_rows()
    kv_traffic_rows()
    return rows


if __name__ == "__main__":
    main()
