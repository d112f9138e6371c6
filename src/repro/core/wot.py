"""WOT — Weight-distribution-Oriented Training (paper §4.1).

Constraint set S_l: in every 64-bit (8-byte) block of the flattened quantized
weight vector, the first seven values must lie in [-64, 63]; only the eighth
may be large. The QATT realisation: after each QAT/SGD update, *throttle* the
quantized weights (clamp offending values to 63 / -64) and push the change
back into the fp32 master weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from . import quant

WOT_LO = -64
WOT_HI = 63
BLOCK = 8


def _block_view(flat: jnp.ndarray) -> tuple[jnp.ndarray, int]:
    """Pad a flat vector to a block multiple -> ((nblk, 8), pad)."""
    n = flat.shape[0]
    pad = (-n) % BLOCK
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
    return flat.reshape(-1, BLOCK), pad


def throttle_q(q: jnp.ndarray) -> jnp.ndarray:
    """Clamp positions 0..6 of each 8-value block to [-64, 63] (int domain).

    Blocks run along the last axis when it holds whole blocks — then the
    array keeps its shape (and device layout) throughout; otherwise they
    run over the flattened values, as the flat-padded layout stores them."""
    if q.ndim == 0 or q.shape[-1] % BLOCK:
        flat = jnp.pad(jnp.reshape(q, -1), (0, (-q.size) % BLOCK))
        return throttle_q(flat)[: q.size].reshape(q.shape)
    pos = jax.lax.broadcasted_iota(jnp.int32, q.shape, q.ndim - 1)
    return jnp.where(pos % BLOCK == BLOCK - 1, q, jnp.clip(q, WOT_LO, WOT_HI))


def throttle_tensor(w: jnp.ndarray, scale=None) -> jnp.ndarray:
    """QATT throttling step on an fp32 weight tensor.

    Quantize -> clamp first-7-of-8 -> dequantize back into fp32 masters
    ("The float32 versions are updated accordingly", paper §4.1).
    """
    if scale is None:
        scale = quant.compute_scale(w)
    q = jnp.clip(jnp.round(w / scale), -quant.QMAX, quant.QMAX)
    qt = throttle_q(q)
    # only touch weights the throttle actually moved; keep fp32 precision elsewhere
    return jnp.where(q == qt, w, qt * scale)


_EXCLUDED_NAMES = {"b", "bq", "bk", "bv", "dt_bias", "A_log", "D", "a_param",
                   "scale", "bias", "mean", "var"}
_EXCLUDED_PATH_PARTS = ("ln", "norm", "bn")


def is_protected_weight(path, leaf) -> bool:
    """The paper protects *weights* (matmul/conv/embedding tensors), not
    norm scales or biases (biases are 32-bit, §3). Layer-stacked norm params
    are 2-D, so name/path rules are needed on top of ndim."""
    if not (hasattr(leaf, "ndim") and leaf.ndim >= 2 and
            jnp.issubdtype(leaf.dtype, jnp.floating)):
        return False
    names = [str(getattr(p, "key", getattr(p, "idx", ""))) for p in path]
    if not names:
        return True
    last = names[-1]
    if last in _EXCLUDED_NAMES or last.startswith("b_"):
        return False
    return not any(part in comp for comp in names
                   for part in _EXCLUDED_PATH_PARTS)


def throttle_tree(params, predicate=None):
    """Apply throttle_tensor to every protected weight tensor in a pytree.

    predicate(path, leaf) -> bool selects tensors to constrain (default:
    ``is_protected_weight``)."""
    pred = predicate or is_protected_weight
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = [throttle_tensor(leaf) if pred(path, leaf) else leaf
           for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, out)


# --------------------------- census / diagnostics ---------------------------


def count_large_in_protected(q_flat: jnp.ndarray) -> jnp.ndarray:
    """# of values outside [-64,63] in positions 0..6 (paper Fig. 3 metric)."""
    blocks, _ = _block_view(q_flat)
    large = jnp.logical_or(blocks > WOT_HI, blocks < WOT_LO)
    return jnp.sum(large[:, : BLOCK - 1])


def large_position_histogram(q_flat: jnp.ndarray) -> jnp.ndarray:
    """Per-byte-position histogram of large values (paper Fig. 1)."""
    blocks, _ = _block_view(q_flat)
    large = jnp.logical_or(blocks > WOT_HI, blocks < WOT_LO)
    return jnp.sum(large, axis=0)


def range_percentages(q_flat: np.ndarray) -> dict[str, float]:
    """% of |q| in [0,32), [32,64), [64,128] (paper Table 1 rows)."""
    a = np.abs(np.asarray(q_flat).astype(np.int32))
    n = max(a.size, 1)
    return {
        "[0,32)": float((a < 32).sum()) / n * 100,
        "[32,64)": float(((a >= 32) & (a < 64)).sum()) / n * 100,
        "[64,128]": float((a >= 64).sum()) / n * 100,
    }


def satisfies_constraint(q_flat: jnp.ndarray) -> bool:
    return int(count_large_in_protected(q_flat)) == 0
