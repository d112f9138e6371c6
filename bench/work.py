"""The work a serve step requires, computed from a configuration's shapes.

Everything here reads the configuration file's published keys
(``hidden_size``, ``intermediate_size``, ...), never the program, so the
count stays the same whatever implementation a later change puts under
it. Work is what the algorithm needs, not what an implementation happens
to move: weight bytes as stored (one int8 byte per weight, ECC in place),
activations for the rows that hold a live request, and KV bytes for each
slot's live tokens only.
"""
from __future__ import annotations

from typing import Iterable


def dims(model: dict) -> dict:
    d = model["hidden_size"]
    h = model["num_attention_heads"]
    kv = model.get("num_key_value_heads", h)
    hd = model.get("head_dim", d // h)
    return {"d": d, "h": h, "kv": kv, "hd": hd,
            "ff": model["intermediate_size"],
            "layers": model["num_hidden_layers"],
            "vocab": model["vocab_size"]}


def layer_matmuls(model: dict) -> list:
    """(name, K, N) of each weight matmul in one decoder layer."""
    m = dims(model)
    d, h, kv, hd, ff = m["d"], m["h"], m["kv"], m["hd"], m["ff"]
    return [("wq", d, h * hd), ("wk", d, kv * hd), ("wv", d, kv * hd),
            ("wo", h * hd, d), ("w_gate", d, ff), ("w_up", d, ff),
            ("w_down", ff, d)]


def step_matmuls(model: dict) -> list:
    """(name, K, N, calls per step) of every weight matmul in one step."""
    m = dims(model)
    out = [(n, k, nn, m["layers"]) for n, k, nn in layer_matmuls(model)]
    out.append(("head", m["d"], m["vocab"], 1))
    return out


def qmatmul_work(rows: int, k: int, n: int, act_bytes: int = 2) -> tuple:
    """(flops, bytes) one decode-at-use matmul requires: ``rows`` live
    activation rows in and out, and the stored int8 weight read once."""
    flops = 2 * rows * k * n
    nbytes = k * n + rows * k * act_bytes + rows * n * act_bytes
    return flops, nbytes


def attention_work(model: dict, live_lens: Iterable[int]) -> tuple:
    """(flops, bytes) of single-token attention for one layer over slots
    whose caches hold ``live_lens`` tokens each: int8 K and V plus one f32
    scale per token each, the bf16 query in and output out."""
    m = dims(model)
    h, kv, hd = m["h"], m["kv"], m["hd"]
    flops = nbytes = 0
    for n_tok in live_lens:
        flops += 4 * h * hd * n_tok
        nbytes += n_tok * 2 * (kv * hd + 4) + 2 * 2 * h * hd
    return flops, nbytes


def least_time(flops: float, nbytes: float, peak: dict) -> float:
    """Seconds the chip needs at least: the larger of its two bounds."""
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


def step_flops(model: dict, live_lens: list) -> float:
    """Model FLOPs of one serve step: one token through every weight
    matmul for each live slot, plus attention at each slot's live length.
    The embedding is a gather and counts nothing."""
    active = len(live_lens)
    per_token = sum(2 * k * n * c for _, k, n, c in step_matmuls(model))
    attn, _ = attention_work(model, live_lens)
    return active * per_token + dims(model)["layers"] * attn


def step_qmatmul_least_s(model: dict, active: int, peak: dict) -> float:
    """Least seconds of all of one step's weight matmuls, call by call."""
    total = 0.0
    for _, k, n, calls in step_matmuls(model):
        f, b = qmatmul_work(active, k, n)
        total += calls * least_time(f, b, peak)
    return total


def step_attention_least_s(model: dict, live_lens: list,
                           peak: dict) -> float:
    """Least seconds of one step's attention calls, one call per layer."""
    f, b = attention_work(model, live_lens)
    return dims(model)["layers"] * least_time(f, b, peak)
