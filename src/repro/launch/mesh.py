"""Production mesh builders (functions, never module-level constants — the
dry-run must set XLA_FLAGS before any jax device initialization)."""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes):
    """``jax.make_mesh`` with every axis ``Auto``: the sharded steps place
    data with ``with_sharding_constraint`` and in/out shardings, which
    refer to Auto axes only (``make_mesh`` defaults to Explicit)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False, shape=None):
    """Default 16x16 (one pod) or 2x16x16; ``shape`` overrides the dims —
    a 2-tuple maps to ('data', 'model'), a 3-tuple to ('pod', 'data',
    'model') — so the dry-run grid can run micro-meshes on host devices."""
    if shape is None:
        shape = (2, 16, 16) if multi_pod else (16, 16)
    shape = tuple(int(s) for s in shape)
    if len(shape) not in (2, 3):
        raise ValueError(f"mesh shape must have 2 or 3 dims, got {shape}")
    axes = ("pod", "data", "model") if len(shape) == 3 else ("data", "model")
    return auto_mesh(shape, axes)


def make_local_mesh():
    """1-device mesh with the same axis names (smoke tests / examples)."""
    return auto_mesh((1, 1), ("data", "model"))


def axis_names(multi_pod: bool):
    return ("pod", "data", "model") if multi_pod else ("data", "model")


def dp_axes(multi_pod: bool):
    return ("pod", "data") if multi_pod else ("data",)
