"""Seeded weights, generated leaf by leaf and layer by layer.

The harness hands the program a bf16 tree made here, and the reference
regenerates the same values here, one layer at a time: each leaf has its
own key folded from its path, and each layer of a stacked leaf its own key
folded from the layer index, so a layer's values do not depend on how many
layers are generated together.

Values: norms 1; 1-D biases N(0, 0.02); the embedding N(0, 0.02); every
other matrix N(0, 1/fan_in) with fan_in its second-to-last dimension.
"""
from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np

STACKED = "layers/"


def seed_key(seed: int):
    """A PRNG key from a seed of any size (seeds may exceed 32 bits)."""
    lo, hi = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.fold_in(jax.random.PRNGKey(int(lo) & 0x7FFFFFFF),
                              int(hi) & 0x7FFFFFFF)


def leaf_std(path: str, shape: tuple):
    """Standard deviation of one leaf's values; ``None`` means all ones."""
    name = path.rsplit("/", 1)[-1]
    if name == "w" and ("ln" in path or "norm" in path):
        return None
    if path == "embed" or len(shape) == 1:
        return 0.02
    if len(shape) == 2:
        return 1.0 / np.sqrt(shape[0])
    raise ValueError(f"no rule for leaf {path!r} of shape {shape}")


def leaf_key(key, path: str):
    return jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def gen(key, path: str, shape: tuple, dtype=jnp.bfloat16):
    """One (unstacked) leaf's values."""
    std = leaf_std(path, shape)
    if std is None:
        return jnp.ones(shape, dtype)
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def gen_layer(key, path: str, layer_shape: tuple, i, dtype=jnp.bfloat16):
    """Layer ``i`` of the stacked leaf ``path``."""
    return gen(jax.random.fold_in(leaf_key(key, path), i), path,
               layer_shape, dtype)


def gen_leaf(key, path: str, shape: tuple, dtype=jnp.bfloat16):
    """A whole leaf; a stacked one is its layers generated one after
    another, so only one layer's float32 temporaries exist at a time."""
    if path.startswith(STACKED):
        return jax.lax.map(lambda i: gen_layer(key, path, shape[1:], i, dtype),
                           jnp.arange(shape[0]))
    return gen(leaf_key(key, path), path, shape, dtype)


def path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def layout(specs) -> dict:
    """{path: shape} of a tree of arrays or shape structs."""
    flat, _ = jax.tree_util.tree_flatten_with_path(specs)
    return {path_str(p): tuple(x.shape) for p, x in flat}


def _nest(path: str, value) -> dict:
    out = value
    for part in reversed(path.split("/")):
        out = {part: out}
    return out


def _get(tree: dict, path: str):
    for part in path.split("/"):
        tree = tree[part]
    return tree


def make_encoded(specs, key, encode, dtype=jnp.bfloat16) -> dict:
    """``encode`` applied to each leaf of a tree shaped like ``specs``, one
    leaf after another, largest first. Each leaf is generated only once the
    previous one is encoded, so inside one jitted program the float values
    of one leaf at a time are live. ``encode`` maps a tree of float leaves
    (any sub-tree of ``specs``, by path) to its encoded tree."""
    lay = layout(specs)
    out: dict = {}
    prev = None
    for path in sorted(lay, key=lambda p: (-int(np.prod(lay[p])), p)):
        k = key if prev is None else jax.lax.optimization_barrier(
            (key, prev))[0]
        leaf = _get(encode(_nest(path, gen_leaf(k, path, lay[path], dtype))),
                    path)
        prev = jax.tree.leaves(leaf)[0]
        node = out
        parts = path.split("/")
        for part in parts[:-1]:
            node = node.setdefault(part, {})
        node[parts[-1]] = leaf
    return out
