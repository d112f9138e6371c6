"""Plain float32 reference of the served models, and the comparison that
decides ``correct``.

A decoder of the Llama layout in straightforward ``jax.numpy`` under
``highest`` matmul precision: RMSNorm, rotary positions (half rotation),
multi-head causal attention, SwiGLU, untied output head; Qwen1.5 adds
biases to the q, k and v projections. It imports nothing of the program.
Its weights are regenerated from the seed (``bench.weights``) and stored
as the configuration states them: each weight tensor quantized to int8
with one scale per tensor (absmax / 127 over the whole stacked tensor,
in the bf16 the source is held in), then the in-place code's range
constraint applied (in every 8 consecutive values along the last axis, the
first seven clamp to [-64, 63]). Keys and values are stored the same way
per token (absmax / 127 over the token's heads, same range constraint).
All arithmetic after that is float32.

The comparison: every served token of a sample of finished requests is
scored by the reference, run once over the prompt and the served tokens,
as the gap by which its logit lies below the reference's best at that
position; the harness compares numbers drawn from those gaps (``check``
in the configuration).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights

QMAX = 127
WOT_LO, WOT_HI = -64, 63


def dims(model: dict) -> dict:
    d, h = model["hidden_size"], model["num_attention_heads"]
    return {"d": d, "h": h, "kv": model.get("num_key_value_heads", h),
            "hd": model.get("head_dim", d // h),
            "ff": model["intermediate_size"],
            "layers": model["num_hidden_layers"],
            "vocab": model["vocab_size"],
            "theta": float(model["rope_theta"]),
            "eps": float(model["rms_norm_eps"]),
            "bias": bool(model.get("qkv_bias", False))}


def expected_layout(model: dict) -> dict:
    """{path: shape} of the weights this reference reads."""
    m = dims(model)
    d, h, kv, hd, ff, nl, v = (m["d"], m["h"], m["kv"], m["hd"], m["ff"],
                               m["layers"], m["vocab"])
    out = {"embed": (v, d), "final_norm/w": (d,), "head": (d, v),
           "layers/attn/wq": (nl, d, h * hd), "layers/attn/wk": (nl, d, kv * hd),
           "layers/attn/wv": (nl, d, kv * hd), "layers/attn/wo": (nl, h * hd, d),
           "layers/mlp/w_gate": (nl, d, ff), "layers/mlp/w_up": (nl, d, ff),
           "layers/mlp/w_down": (nl, ff, d),
           "layers/ln1/w": (nl, d), "layers/ln2/w": (nl, d)}
    if m["bias"]:
        out.update({"layers/attn/bq": (nl, h * hd),
                    "layers/attn/bk": (nl, kv * hd),
                    "layers/attn/bv": (nl, kv * hd)})
    return out


QUANTIZED = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")


def constrain(q):
    """The in-place code's range constraint along the last axis."""
    pos = jax.lax.broadcasted_iota(jnp.int32, q.shape, q.ndim - 1)
    return jnp.where(pos % 8 == 7, q, jnp.clip(q, WOT_LO, WOT_HI))


def weight_scale(amax_bf16):
    """Per-tensor scale, computed in the bf16 the source is held in."""
    return jnp.maximum(amax_bf16, 1e-12) / QMAX


def stored(w_bf16, scale_bf16):
    """A weight as stored, back in float32."""
    q = jnp.clip(jnp.round(w_bf16 / scale_bf16), -QMAX, QMAX)
    return constrain(q.astype(jnp.int8)).astype(jnp.float32) \
        * scale_bf16.astype(jnp.float32)


def stored_kv(x):
    """Keys or values (..., kv, hd) as cached: int8 per token, f32 back."""
    amax = jnp.max(jnp.abs(x), axis=(-2, -1), keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / QMAX
    q = jnp.clip(jnp.round(x / scale), -QMAX, QMAX).astype(jnp.int8)
    return constrain(q).astype(jnp.float32) * scale


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, theta):
    """x (B, S, H, hd) at positions 0..S-1, half-rotation convention."""
    s, hd = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv      # (S, hd/2)
    cos, sin = jnp.cos(ang)[None, :, None], jnp.sin(ang)[None, :, None]
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _scores(model: dict, key, tokens, targets):
    m = dims(model)
    nl, h, kv, hd, eps = m["layers"], m["h"], m["kv"], m["hd"], m["eps"]
    lay = expected_layout(model)
    b, s = tokens.shape

    def layer_w(path, i):
        return weights.gen_layer(key, path, lay[path][1:], i)

    qpaths = [p for p in lay if p.startswith("layers/")
              and p.rsplit("/", 1)[-1] in QUANTIZED]

    def amax_body(carry, i):
        return {p: jnp.maximum(carry[p], jnp.max(jnp.abs(layer_w(p, i))))
                for p in qpaths}, None

    amax, _ = jax.lax.scan(
        amax_body, {p: jnp.zeros((), jnp.bfloat16) for p in qpaths},
        jnp.arange(nl))
    scales = {p: weight_scale(a) for p, a in amax.items()}

    def whole(path):
        w = weights.gen(weights.leaf_key(key, path), path, lay[path])
        return stored(w, weight_scale(jnp.max(jnp.abs(w))))

    x = whole("embed")[tokens]                                  # (B, S, d)
    causal = jnp.tril(jnp.ones((s, s), bool))

    def block(x, i):
        w = {p.rsplit("/", 1)[-1]: stored(layer_w(p, i), scales[p])
             for p in qpaths}
        ln1 = layer_w("layers/ln1/w", i).astype(jnp.float32)
        ln2 = layer_w("layers/ln2/w", i).astype(jnp.float32)
        hn = rms_norm(x, ln1, eps)
        q, k, v = hn @ w["wq"], hn @ w["wk"], hn @ w["wv"]
        if m["bias"]:
            q = q + layer_w("layers/attn/bq", i).astype(jnp.float32)
            k = k + layer_w("layers/attn/bk", i).astype(jnp.float32)
            v = v + layer_w("layers/attn/bv", i).astype(jnp.float32)
        q = rope(q.reshape(b, s, h, hd), m["theta"])
        k = stored_kv(rope(k.reshape(b, s, kv, hd), m["theta"]))
        v = stored_kv(v.reshape(b, s, kv, hd))
        k = jnp.repeat(k, h // kv, axis=2)
        v = jnp.repeat(v, h // kv, axis=2)
        sc = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd)
        p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
        o = jnp.einsum("bhqk,bkhd->bqhd", p, v).reshape(b, s, h * hd)
        x = x + o @ w["wo"]
        hn = rms_norm(x, ln2, eps)
        x = x + (jax.nn.silu(hn @ w["w_gate"]) * (hn @ w["w_up"])) @ w["w_down"]
        return x, None

    x, _ = jax.lax.scan(block, x, jnp.arange(nl))
    fn = weights.gen(weights.leaf_key(key, "final_norm/w"), "final_norm/w",
                     lay["final_norm/w"]).astype(jnp.float32)
    logits = rms_norm(x, fn, eps) @ whole("head")              # (B, S, V)
    best = jnp.max(logits, axis=-1)
    tgt = jnp.take_along_axis(logits, targets[..., None], -1)[..., 0]
    return best - tgt


def score(model: dict, key, seqs: list, rows: int, length: int) -> list:
    """Reference gaps of served tokens.

    ``seqs``: (prompt, served) pairs. The reference runs once over
    ``prompt + served[:-1]``; the gap of served token ``j`` is the
    reference's best logit minus its logit for that token, at the position
    that predicts it. Sequences go through in blocks of ``rows``, each
    padded to one (``rows``, ``length``) shape, so every block and every
    run use the same compiled program. Returns one array of gaps per
    sequence."""
    fn = jax.jit(_scores, static_argnums=0)
    out = []
    for b in range(0, len(seqs), rows):
        block = seqs[b: b + rows]
        tokens = np.zeros((rows, length), np.int32)
        targets = np.zeros((rows, length), np.int32)
        for r, (p, o) in enumerate(block):
            full = list(p) + list(o)
            tokens[r, : len(full) - 1] = full[:-1]
            targets[r, : len(full) - 1] = full[1:]
        with jax.default_matmul_precision("highest"):
            gaps = np.asarray(fn(_Frozen(model), key, jnp.asarray(tokens),
                                 jnp.asarray(targets)))
        out += [gaps[r, len(p) - 1: len(p) + len(o) - 1]
                for r, (p, o) in enumerate(block)]
    return out


class _Frozen(dict):
    """A configuration dict usable as a static jit argument."""

    def __hash__(self):
        return hash(repr(sorted((k, repr(v)) for k, v in self.items())))


def sample(finished: dict, seed: int, max_requests: int) -> list:
    """Requests to check: the one with the most served tokens, then the
    others in an order drawn from the seed, up to ``max_requests``.
    ``finished``: rid -> (prompt, served)."""
    if not finished:
        return []
    rids = sorted(finished)
    longest = max(rids, key=lambda r: (len(finished[r][0])
                                       + len(finished[r][1]), -r))
    rest = [r for r in rids if r != longest]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 7])
    rng.shuffle(rest)
    return ([longest] + rest)[:max_requests]
