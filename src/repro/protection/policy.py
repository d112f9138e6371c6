"""``ProtectionPolicy`` — per-layer scheme selection over pytrees.

The policy is the single entry point for protecting a model: it decides
*which* leaves get protected (predicate), *how* (string-keyed scheme registry
+ ordered per-layer rules, so one model can mix schemes), and *where the
bytes live* (same-shape images that inherit sharding, or flat-padded images
for tensors whose last dim is not a block multiple — the old silent
``last-dim % 8`` gate is gone: unaligned tensors are padded and protected by
default, and every decision is visible in the ``CoverageReport``).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import faults, quant, wot

from .backends import get_backend
from .schemes import Scheme, get_scheme
from .tensor import ProtectedTensor, is_protected_tensor

__all__ = ["ProtectionPolicy", "CoverageReport", "CoverageEntry",
           "decode_tree", "decode_leaf", "decode_leaf_with_flags",
           "decode_tree_with_flags", "inject_tree", "inject_tree_device",
           "spec_tree", "space_overhead", "path_str"]

BLOCK = 8


def path_str(path) -> str:
    """'layers/0/wq'-style name for a key path (dict/attr/index entries)."""
    out = []
    for p in path:
        for attr in ("key", "name", "idx"):
            if hasattr(p, attr):
                out.append(str(getattr(p, attr)))
                break
        else:
            out.append(str(p))
    return "/".join(out)


# ---------------------------------------------------------------------------
# coverage reporting
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CoverageEntry:
    path: str
    scheme_id: Optional[str]   # None => not protected
    reason: str                # "" | "predicate" | "rule" | "unaligned"
    n_weights: int             # element count of the leaf
    nbytes: int                # stored bytes if protected, raw bytes if not
    pad_bytes: int             # zero-padding added by the flat layout

    @property
    def protected(self) -> bool:
        return self.scheme_id is not None


@dataclasses.dataclass
class CoverageReport:
    """What a policy does (or did) to every leaf of a tree — the loud
    replacement for silently skipping unaligned tensors."""

    entries: list

    @property
    def protected(self) -> list:
        return [e for e in self.entries if e.protected]

    @property
    def unprotected(self) -> list:
        return [e for e in self.entries if not e.protected]

    @property
    def n_protected(self) -> int:
        return len(self.protected)

    @property
    def n_unprotected(self) -> int:
        return len(self.unprotected)

    @property
    def protected_bytes(self) -> int:
        return sum(e.nbytes for e in self.protected)

    @property
    def unprotected_bytes(self) -> int:
        return sum(e.nbytes for e in self.unprotected)

    @property
    def unprotected_weight_bytes(self) -> int:
        """Bytes of weight-like leaves the policy declined (reason
        'unaligned' under pad=False) — the gaps that used to be silent."""
        return sum(e.nbytes for e in self.unprotected
                   if e.reason == "unaligned")

    @property
    def pad_bytes(self) -> int:
        return sum(e.pad_bytes for e in self.protected)

    def by_scheme(self) -> dict:
        out: dict = {}
        for e in self.protected:
            out[e.scheme_id] = out.get(e.scheme_id, 0) + 1
        return out

    def summary(self) -> str:
        lines = [f"protection coverage: {self.n_protected} tensors protected "
                 f"({self.protected_bytes / 2**20:.2f} MiB stored), "
                 f"{self.n_unprotected} unprotected "
                 f"({self.unprotected_bytes / 2**20:.2f} MiB)"]
        for sid, n in sorted(self.by_scheme().items()):
            lines.append(f"  scheme {sid}: {n} tensors")
        if self.pad_bytes:
            lines.append(f"  flat-padded layout added {self.pad_bytes} "
                         f"pad bytes")
        gaps = [e for e in self.unprotected if e.reason == "unaligned"]
        if gaps:
            lines.append(f"  WARNING: {len(gaps)} weight tensors "
                         f"({self.unprotected_weight_bytes} bytes) left "
                         f"unprotected (unaligned, pad=False):")
            lines.extend(f"    {e.path} ({e.n_weights} elems)" for e in gaps)
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# the policy
# ---------------------------------------------------------------------------


class ProtectionPolicy:
    """Per-layer protection strategy.

    default_scheme: scheme id applied to every leaf the predicate selects.
    rules:          ordered ``(pattern, scheme_id_or_None)`` pairs; the first
                    regex that matches the leaf's path string wins. A scheme
                    of ``None`` (or ``"none"``) leaves that leaf unprotected.
    predicate:      ``(path, leaf) -> bool`` choosing protectable leaves
                    (default: ``wot.is_protected_weight`` — matmul/conv/
                    embedding weights, not norms or biases).
    pad:            True (default) pads tensors whose last dim is not a
                    multiple of 8 into the flat layout so they are protected
                    anyway; False records them as coverage gaps instead.
    throttle:       apply the WOT projection to the quantized weights before
                    encoding (idempotent on WOT-trained weights; required for
                    the in-place code's correctness).
    backend:        "xla" | "pallas" | a Backend instance — the *default*
                    route for 64-bit-block codec compute.
    backend_rules:  ordered ``(pattern, backend)`` pairs resolved per leaf
                    (first regex matching the leaf's path wins) — one model
                    can mix backends per layer; ``backend`` decides the
                    leaves no rule matches.
    """

    def __init__(self, default_scheme: str = "in-place",
                 rules: Sequence = (),
                 predicate: Optional[Callable] = None,
                 *, pad: bool = True, throttle: bool = True,
                 backend="xla", backend_rules: Sequence = ()):
        get_scheme(default_scheme)  # validate eagerly
        self.default_scheme = default_scheme
        self.rules = [(re.compile(pat), sid) for pat, sid in rules]
        for _, sid in self.rules:
            if sid not in (None, "none"):
                get_scheme(sid)
        self.predicate = predicate or wot.is_protected_weight
        self.pad = pad
        self.throttle = throttle
        self.backend = get_backend(backend)
        self.backend_rules = [(re.compile(pat), get_backend(be))
                              for pat, be in backend_rules]

    # -- selection -----------------------------------------------------------

    def scheme_for(self, path, leaf) -> Optional[Scheme]:
        """Scheme for one leaf, or None if it stays unprotected."""
        sid, _ = self._plan(path, leaf)
        return get_scheme(sid) if sid is not None else None

    def _plan(self, path, leaf) -> tuple:
        """-> (scheme_id | None, reason)."""
        if not self.predicate(path, leaf):
            return None, "predicate"
        sid = self.default_scheme
        p = path_str(path)
        for pat, rule_sid in self.rules:
            if pat.search(p):
                if rule_sid in (None, "none"):
                    return None, "rule"
                sid = rule_sid
                break
        aligned = leaf.ndim >= 1 and leaf.shape[-1] % BLOCK == 0
        if not aligned and not self.pad:
            return None, "unaligned"
        return sid, ""

    def resolve_backend(self, path: str) -> tuple:
        """Per-leaf backend: the first matching backend rule, else the
        policy default.

        -> (Backend, source) with source "rule" | "policy".
        """
        for pat, be in self.backend_rules:
            if pat.search(path):
                return be, "rule"
        return self.backend, "policy"

    # -- the plan ------------------------------------------------------------

    def plan(self, params, *, mesh=None, param_spec_fn=None):
        """Materialize every per-leaf decision ONCE — see
        :func:`repro.protection.plan.make_plan`.  ``encode_tree`` /
        ``decode_tree`` / ``coverage`` below are thin views over this."""
        from .plan import make_plan
        return make_plan(self, params, mesh=mesh, param_spec_fn=param_spec_fn)

    # -- leaf codec ----------------------------------------------------------

    def encode_leaf(self, w: jnp.ndarray, scheme,
                    backend=None) -> ProtectedTensor:
        """fp weight -> quantize (+WOT throttle) -> scheme-encode."""
        scheme = get_scheme(scheme)
        be = self.backend if backend is None else get_backend(backend)
        scale = quant.compute_scale(w)
        q = jnp.clip(jnp.round(w / scale), -quant.QMAX,
                     quant.QMAX).astype(jnp.int8)
        if self.throttle:
            q = wot.throttle_q(q)
        if w.ndim >= 1 and w.shape[-1] % BLOCK == 0:
            q_img = q                         # same-shape layout
        else:
            flat = q.reshape(-1)              # flat-padded layout
            pad = (-flat.shape[0]) % BLOCK
            if pad:
                flat = jnp.concatenate([flat, jnp.zeros((pad,), flat.dtype)])
            q_img = flat
        enc, checks = scheme.encode(q_img, be)
        return ProtectedTensor(enc=enc, checks=checks,
                               scale=scale.astype(jnp.float32),
                               scheme_id=scheme.scheme_id,
                               orig_shape=tuple(w.shape))

    def decode_leaf(self, pt: ProtectedTensor, dtype=jnp.bfloat16):
        return decode_leaf(pt, dtype, backend=self.backend)

    # -- tree codec (views over the plan) ------------------------------------

    def encode_tree(self, params):
        """fp params -> tree with ``ProtectedTensor`` leaves (rest unchanged)."""
        return self.plan(params).encode_tree(params)

    def decode_tree(self, enc_tree, dtype=jnp.bfloat16):
        """Decode with per-leaf backend resolution (rules, then default)."""
        if not self.backend_rules:
            return decode_tree(enc_tree, dtype, backend=self.backend)

        def dec(path, leaf):
            if not is_protected_tensor(leaf):
                return leaf
            be, _ = self.resolve_backend(path_str(path))
            return decode_leaf(leaf, dtype, backend=be)
        return jax.tree_util.tree_map_with_path(
            dec, enc_tree, is_leaf=is_protected_tensor)

    def coverage(self, params) -> CoverageReport:
        """Report what ``encode_tree`` does, without encoding anything."""
        return self.plan(params).coverage()


# ---------------------------------------------------------------------------
# policy-free tree ops (the scheme id travels inside each ProtectedTensor)
# ---------------------------------------------------------------------------


def decode_leaf(pt: ProtectedTensor, dtype=jnp.bfloat16, *, backend="xla"):
    """ProtectedTensor -> dequantized weight tensor (faults corrected)."""
    scheme = get_scheme(pt.scheme_id)
    q = scheme.decode(pt.enc, pt.checks, get_backend(backend))
    if pt.is_flat:
        q = q.reshape(-1)[: pt.n_weights].reshape(pt.orig_shape)
    return (q.astype(jnp.float32) * pt.scale).astype(dtype)


def decode_leaf_with_flags(pt: ProtectedTensor, dtype=jnp.bfloat16, *,
                           backend="xla"):
    """:func:`decode_leaf` plus fault accounting — returns
    ``(weight, corrected, due)`` with int32 scalar counts of repaired and
    detected-uncorrectable (double) errors in this leaf's stored image."""
    scheme = get_scheme(pt.scheme_id)
    q, corrected, due = scheme.decode_with_flags(pt.enc, pt.checks,
                                                 get_backend(backend))
    if pt.is_flat:
        q = q.reshape(-1)[: pt.n_weights].reshape(pt.orig_shape)
    return (q.astype(jnp.float32) * pt.scale).astype(dtype), corrected, due


def decode_tree_with_flags(enc_tree, dtype=jnp.bfloat16, *, backend="xla"):
    """Decode every ProtectedTensor leaf and aggregate fault flags:
    returns ``(decoded_tree, {path: (corrected, due)})`` — the per-leaf
    accounting that fault campaigns sum into DUE curves."""
    be = get_backend(backend)
    flags: dict = {}

    def dec(path, leaf):
        if not is_protected_tensor(leaf):
            return leaf
        w, corrected, due = decode_leaf_with_flags(leaf, dtype, backend=be)
        flags[path_str(path)] = (corrected, due)
        return w

    out = jax.tree_util.tree_map_with_path(dec, enc_tree,
                                           is_leaf=is_protected_tensor)
    return out, flags


def decode_tree(enc_tree, dtype=jnp.bfloat16, *, backend="xla"):
    """Decode every ProtectedTensor leaf; other leaves pass through."""
    be = get_backend(backend)
    return jax.tree.map(
        lambda x: decode_leaf(x, dtype, backend=be)
        if is_protected_tensor(x) else x,
        enc_tree, is_leaf=is_protected_tensor)


def inject_tree(enc_tree, rate: float, seed: int):
    """Host-side memory-fault injection: flip random bits across each leaf's
    full stored image (weight bytes AND check bytes — DRAM faults hit ECC
    bits too). Matches the paper's §5.3 fault model."""
    i = 0

    def inj(pt):
        nonlocal i
        if not is_protected_tensor(pt):
            return pt
        i += 1
        enc = np.asarray(pt.enc).reshape(-1)
        if pt.checks is not None:
            checks = np.asarray(pt.checks).reshape(-1)
            image = faults.inject(np.concatenate([enc, checks]), rate, seed + i)
            new_enc = image[: enc.size].reshape(pt.enc.shape)
            new_checks = image[enc.size:].reshape(pt.checks.shape)
            return dataclasses.replace(pt, enc=jnp.asarray(new_enc),
                                       checks=jnp.asarray(new_checks))
        flipped = faults.inject(enc, rate, seed + i).reshape(pt.enc.shape)
        return dataclasses.replace(pt, enc=jnp.asarray(flipped))

    return jax.tree.map(inj, enc_tree, is_leaf=is_protected_tensor)


def inject_tree_device(enc_tree, rate, key, *, max_rate=None):
    """Jit-safe on-device injection (``faults.inject_jax`` per leaf image).

    With ``max_rate=None`` (default) ``rate`` must be a static Python float.
    Passing ``max_rate`` switches to ``faults.inject_jax_rate``: the per-leaf
    sample budget is fixed by ``max_rate`` and ``rate`` may then be a traced
    scalar — the mechanism compiled fault campaigns use to sweep the whole
    rate grid inside one program.
    """
    if max_rate is None:
        inj = lambda image, k: faults.inject_jax(image, rate, k)
    else:
        inj = lambda image, k: faults.inject_jax_rate(image, rate, k, max_rate)
    leaves, treedef = jax.tree_util.tree_flatten(
        enc_tree, is_leaf=is_protected_tensor)
    keys = jax.random.split(key, max(len(leaves), 1))
    out = []
    for k, pt in zip(keys, leaves):
        if not is_protected_tensor(pt):
            out.append(pt)
            continue
        enc = pt.enc.reshape(-1)
        if pt.checks is not None:
            n = enc.shape[0]
            image = jnp.concatenate([enc, pt.checks.reshape(-1)])
            image = inj(image, k)
            pt = dataclasses.replace(
                pt, enc=image[:n].reshape(pt.enc.shape),
                checks=image[n:].reshape(pt.checks.shape))
        else:
            pt = dataclasses.replace(
                pt, enc=inj(enc, k).reshape(pt.enc.shape))
        out.append(pt)
    return jax.tree_util.tree_unflatten(treedef, out)


def spec_tree(enc_tree, param_spec_fn, *, mesh=None):
    """Sharding specs for an encoded tree: a same-shape image inherits the
    weight's spec byte-for-byte; check bytes and scales are replicated.
    Flat-padded images replicate by default; with ``mesh`` they get the
    1-D block-aligned sharded spec (see ``plan._flat_spec``) — prefer
    building a :class:`~repro.protection.plan.ProtectionPlan`, which
    materializes these specs once per leaf."""
    from jax.sharding import PartitionSpec as P

    from .plan import _flat_spec, _mesh_sizes

    sizes = _mesh_sizes(mesh)

    def spec(path, leaf):
        if is_protected_tensor(leaf):
            enc_spec = (_flat_spec(int(leaf.enc.shape[0]), sizes)
                        if leaf.is_flat else param_spec_fn(path, leaf.enc))
            checks_spec = None if leaf.checks is None else P()
            return ProtectedTensor(enc=enc_spec, checks=checks_spec,
                                   scale=P(), scheme_id=leaf.scheme_id,
                                   orig_shape=tuple(leaf.orig_shape))
        return param_spec_fn(path, leaf)

    return jax.tree_util.tree_map_with_path(spec, enc_tree,
                                            is_leaf=is_protected_tensor)


def space_overhead(enc_tree) -> float:
    """(stored - weight) / weight bytes over all protected leaves."""
    stored = weights = 0
    for leaf in jax.tree_util.tree_leaves(enc_tree,
                                          is_leaf=is_protected_tensor):
        if is_protected_tensor(leaf):
            stored += leaf.stored_bytes
            weights += leaf.n_weights
    return (stored - weights) / max(weights, 1)
