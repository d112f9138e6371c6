"""``ProtectionPlan`` — materialized per-leaf protection decisions.

The paper's zero-space guarantee is *per tensor*: each weight independently
earns (or is denied) the in-place (64,57,1) code.  A :class:`ProtectionPlan`
makes that concrete — it is built ONCE from ``(policy, abstract_params,
mesh?)`` and holds, for every leaf, the resolved :class:`LeafPlan`: scheme
id, storage layout (same-shape vs flat-padded), resolved backend (per-leaf
rules > policy default), stored-bytes accounting, and the sharding spec of
the stored image.  Every consumer —
``ProtectionPolicy.encode_tree/decode_tree/coverage``, the protected serving
step, the dry-run grid — is a view over the same plan, so "which protection,
where, on which backend" is one inspectable artifact instead of scattered
call-site defaults.

Lifecycle::

    policy = get_policy_preset("attn-inplace-mlp-secded")
    plan   = make_plan(policy, abstract_params, mesh=mesh,
                       param_spec_fn=param_spec)
    enc    = plan.encode_tree(params)       # mixed schemes per leaf
    espec  = plan.spec_tree(enc)            # sharded flat images included
    step   = make_serve_step(cfg, plan=plan)  # mixed backends per leaf
    plan.summary()                          # byte-exact vs CoverageReport

Flat-padded images get a real 1-D sharded spec over ``('data', 'model')``
when the mesh is known and shards stay 8-byte-block aligned — replicating
them (the old behaviour, still the fallback) silently blows HBM at
production scale.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from .backends import Backend, get_backend
from .schemes import get_scheme
from .tensor import ProtectedTensor, is_protected_tensor

__all__ = ["LeafPlan", "ProtectionPlan", "make_plan", "LeafDiff",
           "PlanDiff", "transcode_leaf",
           "POLICY_PRESETS", "get_policy_preset"]

BLOCK = 8
FLAT_SHARD_AXES = ("data", "model")


# ---------------------------------------------------------------------------
# per-leaf decision
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LeafPlan:
    """One leaf's fully-resolved protection decision.

    path:        'layers/0/wq'-style key path of the leaf.
    scheme_id:   codec id, or None when the leaf stays unprotected.
    reason:      why unprotected ("predicate" | "rule" | "unaligned"; "" when
                 protected).
    backend:     resolved backend *name* for this leaf's codec compute.
    backend_src: where the backend came from ("rule" | "policy").
    layout:      "same-shape" | "flat-padded" | "raw" (unprotected).
    shape:       logical weight shape.
    n_weights:   element count.
    enc_shape:   stored image shape (== shape for same-shape, 1-D for flat).
    pad_bytes:   zero padding added by the flat layout.
    check_bytes: out-of-place check bytes (secded72 / parity-zero).
    stored_bytes: bytes resident in fault-prone memory (raw bytes when
                 unprotected) — matches ``CoverageEntry.nbytes`` exactly.
    spec:        sharding spec of the stored image (a ``ProtectedTensor`` of
                 ``PartitionSpec`` for protected leaves) or None when the
                 plan was built without ``param_spec_fn``.
    act_quant:   activation-quantization decision for the serve step:
                 None (float activations) | "dynamic" (per-token absmax) |
                 "static" (calibrated ``a_scale``). Set via
                 :meth:`ProtectionPlan.with_act_quant`.
    a_scale:     calibrated static activation scale (float) or None.
    abft:        verify ABFT checksums on this leaf's matmuls (compute-fault
                 detection inside the epilogue). Set via
                 :meth:`ProtectionPlan.with_abft`.
    clamp:       per-leaf activation-range bound (absmax): the epilogue
                 output is clipped to ``[-clamp, +clamp]`` with out-of-range
                 hits counted. None disables (the default — bit-identical
                 to an unguarded epilogue).
    """

    path: str
    scheme_id: Optional[str]
    reason: str
    backend: str
    backend_src: str
    layout: str
    shape: tuple
    n_weights: int
    enc_shape: tuple
    pad_bytes: int
    check_bytes: int
    stored_bytes: int
    spec: Any = dataclasses.field(default=None, compare=False)
    backend_obj: Any = dataclasses.field(default=None, compare=False,
                                         repr=False)
    act_quant: Optional[str] = None
    a_scale: Optional[float] = None
    abft: bool = False
    clamp: Optional[float] = None

    @property
    def protected(self) -> bool:
        return self.scheme_id is not None

    @property
    def flat_sharded(self) -> bool:
        """True when a flat-padded image got a real (non-replicated) spec."""
        from jax.sharding import PartitionSpec as P
        return (self.layout == "flat-padded" and self.spec is not None
                and self.spec.enc != P())


# ---------------------------------------------------------------------------
# plan diffs + rolling migration (the serving-side promotion primitive)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LeafDiff:
    """One leaf whose protection decision differs between two plans."""

    path: str
    from_scheme: Optional[str]
    to_scheme: Optional[str]
    from_backend: str
    to_backend: str
    stored_bytes_delta: int

    @property
    def scheme_changed(self) -> bool:
        return self.from_scheme != self.to_scheme


@dataclasses.dataclass(frozen=True)
class PlanDiff:
    """Ordered per-leaf delta between two :class:`ProtectionPlan`\\ s built
    for the SAME tree. ``paths`` (the scheme changes, in plan order) is the
    migration work-list a :class:`~repro.serving.scrubber.Migrator` drains
    shard-by-shard — one planned leaf is one shard."""

    entries: tuple

    @property
    def paths(self) -> tuple:
        """Leaves whose *scheme* changes — the shards a rolling migration
        must transcode (backend-only changes need no byte rewrite)."""
        return tuple(e.path for e in self.entries if e.scheme_changed)

    @property
    def empty(self) -> bool:
        return not self.entries

    def __len__(self) -> int:
        return len(self.entries)

    def summary(self) -> dict:
        moves: dict = {}
        for e in self.entries:
            if e.scheme_changed:
                k = f"{e.from_scheme}->{e.to_scheme}"
                moves[k] = moves.get(k, 0) + 1
        return {
            "n_changed": len(self.entries),
            "n_scheme_changes": len(self.paths),
            "moves": moves,
            "stored_bytes_delta": sum(e.stored_bytes_delta
                                      for e in self.entries),
        }


def transcode_leaf(pt: ProtectedTensor, to_scheme, *, backend="xla"):
    """Re-encode one stored image under another scheme WITHOUT a float
    round-trip: decode to the int8 domain (correcting what the old code
    can), then encode those exact values under the new scheme. Quantized
    values — and therefore every decoded logit — are preserved bit for bit
    for any scheme pair whose source was WOT-throttled at original encode
    time (every plan encodes through ``ProtectionPolicy.encode_leaf``,
    which throttles whenever ANY in-place leaf may exist; re-throttling
    here is idempotent on compliant values, so promoting secded72 ->
    in-place is value-exact too).

    Returns ``(new_pt, corrected, due)`` — the decode flags observed while
    reading the old image (``due`` blocks transcode carrying whatever the
    old decode returned; repair is a separate pass)."""
    from repro.core import wot

    frm = get_scheme(pt.scheme_id)
    to = get_scheme(to_scheme)
    be = get_backend(backend)
    q, corrected, due = frm.decode_with_flags(pt.enc, pt.checks, be)
    if to.requires_wot:
        q = wot.throttle_q(q)
    enc, checks = to.encode(q, be)
    new = ProtectedTensor(enc=enc, checks=checks, scale=pt.scale,
                          scheme_id=to.scheme_id,
                          orig_shape=tuple(pt.orig_shape))
    return new, corrected, due


# ---------------------------------------------------------------------------
# the plan
# ---------------------------------------------------------------------------


class ProtectionPlan:
    """Materialized per-leaf decisions for one ``(policy, tree, mesh?)``.

    Holds an ordered ``{path: LeafPlan}`` map in tree-traversal order. All
    tree-shaped operations (:meth:`encode_tree`, :meth:`decode_tree`,
    :meth:`spec_tree`) look each leaf up by path and fail loudly on a tree
    that does not match the plan.
    """

    def __init__(self, policy, leaves: dict, *, mesh_axes=None,
                 kv_policy=None):
        self.policy = policy
        self.leaves = leaves
        self.mesh_axes = mesh_axes
        self.kv_policy = kv_policy

    # -- lookup --------------------------------------------------------------

    def __len__(self) -> int:
        return len(self.leaves)

    def __iter__(self):
        return iter(self.leaves.values())

    def __getitem__(self, path: str) -> LeafPlan:
        return self.leaves[path]

    def _leaf(self, path) -> LeafPlan:
        from .policy import path_str
        p = path_str(path)
        try:
            return self.leaves[p]
        except KeyError:
            raise KeyError(
                f"leaf {p!r} is not in this ProtectionPlan (plan built for a "
                f"different tree? {len(self.leaves)} planned leaves)") from None

    @property
    def protected(self) -> list:
        return [lp for lp in self if lp.protected]

    @property
    def unprotected(self) -> list:
        return [lp for lp in self if not lp.protected]

    # -- accounting ----------------------------------------------------------

    def by_scheme(self) -> dict:
        """Per-scheme accounting: ``{scheme_id: {n_tensors, weight_bytes,
        stored_bytes, check_bytes, pad_bytes}}``."""
        out: dict = {}
        for lp in self.protected:
            d = out.setdefault(lp.scheme_id, {"n_tensors": 0, "weight_bytes": 0,
                                              "stored_bytes": 0,
                                              "check_bytes": 0, "pad_bytes": 0})
            d["n_tensors"] += 1
            d["weight_bytes"] += lp.n_weights
            d["stored_bytes"] += lp.stored_bytes
            d["check_bytes"] += lp.check_bytes
            d["pad_bytes"] += lp.pad_bytes
        return out

    def by_backend(self) -> dict:
        out: dict = {}
        for lp in self.protected:
            out[lp.backend] = out.get(lp.backend, 0) + 1
        return out

    def summary(self) -> dict:
        """JSON-ready accounting of the whole plan. Byte-for-byte consistent
        with :class:`CoverageReport` (``protected_bytes`` etc. are sums of
        the same per-leaf ``stored_bytes``)."""
        prot, unprot = self.protected, self.unprotected
        return {
            "n_leaves": len(self.leaves),
            "n_protected": len(prot),
            "n_unprotected": len(unprot),
            "protected_bytes": sum(lp.stored_bytes for lp in prot),
            "unprotected_bytes": sum(lp.stored_bytes for lp in unprot),
            "weight_bytes": sum(lp.n_weights for lp in prot),
            "pad_bytes": sum(lp.pad_bytes for lp in prot),
            "check_bytes": sum(lp.check_bytes for lp in prot),
            "by_scheme": self.by_scheme(),
            "by_backend": self.by_backend(),
            "n_flat_padded": sum(lp.layout == "flat-padded" for lp in prot),
            "n_flat_sharded": sum(lp.flat_sharded for lp in prot),
            "act_quant": self._count(prot, "act_quant"),
            "n_abft": sum(lp.abft for lp in prot),
            "n_clamped": sum(lp.clamp is not None for lp in prot),
            "kv_policy": ({"scheme": self.kv_policy.scheme,
                           "fused": self.kv_policy.fused,
                           "attention_impl": self.kv_policy.attention_impl,
                           "page_size": self.kv_policy.page_size}
                          if self.kv_policy is not None else None),
        }

    @staticmethod
    def _count(leaves, field) -> dict:
        """{value: count} over truthy values of one LeafPlan field."""
        out: dict = {}
        for lp in leaves:
            v = getattr(lp, field)
            if v:
                out[v] = out.get(v, 0) + 1
        return out

    # -- activation quantization ---------------------------------------------

    def with_act_quant(self, mode: str = "dynamic",
                       scales: Optional[dict] = None, *,
                       clamp: bool = False) -> "ProtectionPlan":
        """A new plan whose protected matmul leaves carry activation-quant
        decisions for the int8 serve path.

        mode="dynamic":  every protected leaf with a matmul-shaped image
                         (ndim >= 2) quantizes its activations per token
                         (absmax) at use. Leaves consumed elementwise (conv
                         kernels, embeddings) ignore the marker.
        mode="static":   ``scales`` maps leaf paths to calibrated activation
                         scales (see ``serving.protected.calibrate_act_
                         scales``); exactly the calibrated leaves go static,
                         everything else keeps float activations — the
                         calibration run defines the quantized set.
        clamp=True:      (static mode only) additionally carry each
                         calibrated leaf's activation-range bound — the
                         absmax the scale was derived from
                         (``a_scale * quant.QMAX``) — so the epilogue clips
                         out-of-range outputs and counts hits
                         (Geissler-style range supervision). Off by
                         default: without it the epilogue is bit-identical
                         to the unguarded one.
        """
        from repro.core import quant
        if mode not in ("static", "dynamic"):
            raise ValueError(f"act-quant mode {mode!r}; one of "
                             f"('static', 'dynamic')")
        if mode == "static" and not scales:
            raise ValueError("static activation quantization needs calibrated"
                             " scales — run calibrate_act_scales() first")
        if clamp and mode != "static":
            raise ValueError("clamp ranges come from calibrated absmax — use "
                             "mode='static' with calibrate_act_scales()")
        scales = scales or {}
        leaves = {}
        for p, lp in self.leaves.items():
            if not lp.protected or len(lp.shape) < 2:
                leaves[p] = lp
            elif mode == "static":
                leaves[p] = dataclasses.replace(
                    lp, act_quant="static", a_scale=float(scales[p]),
                    clamp=(float(scales[p]) * quant.QMAX if clamp
                           else lp.clamp)) \
                    if p in scales else lp
            else:
                leaves[p] = dataclasses.replace(lp, act_quant="dynamic")
        return ProtectionPlan(self.policy, leaves, mesh_axes=self.mesh_axes,
                              kv_policy=self.kv_policy)

    # -- compute-fault detection (ABFT) ---------------------------------------

    def with_abft(self, enabled: bool = True, *,
                  clamps: Optional[dict] = None) -> "ProtectionPlan":
        """A new plan whose protected matmul leaves verify ABFT checksums
        at every use: the epilogue checks the accumulator's row/column sums
        against activation/weight checksums in the same kernel invocation
        (bit-exact on the int8 path), so MXU/SDC compute faults surface as
        a ``flags["layers_abft"]`` channel next to the memory-fault flags.

        ``clamps`` optionally maps leaf paths to activation-range bounds
        (absmax, e.g. ``{p: s * quant.QMAX for p, s in
        calibrate_act_scales(...).items()}``) fused into the same epilogue;
        leaves absent from the map keep their current clamp. Leaves
        consumed elementwise (conv kernels) ignore the marker."""
        clamps = clamps or {}
        leaves = {}
        for p, lp in self.leaves.items():
            if not lp.protected or len(lp.shape) < 2:
                leaves[p] = lp
            else:
                leaves[p] = dataclasses.replace(
                    lp, abft=bool(enabled),
                    clamp=(float(clamps[p]) if p in clamps else lp.clamp))
        return ProtectionPlan(self.policy, leaves, mesh_axes=self.mesh_axes,
                              kv_policy=self.kv_policy)

    # -- serving-state (KV cache) protection ----------------------------------

    def with_kv_policy(self, kv_policy) -> "ProtectionPlan":
        """A new plan that also carries a serving-state decision: the
        ``KVProtectionPolicy`` (or preset name) protecting the paged KV
        cache. Weight leaves are untouched — KV pages are protected
        per-token at write time, not planned per leaf — but serving
        entry points (``make_serve_step`` / ``make_prefill``) default
        their ``kv_policy`` from the plan, so one object routes both the
        weight and the serving-state protection story."""
        from repro.serving import kvcache  # deferred: serving builds on us
        return ProtectionPlan(self.policy, self.leaves,
                              mesh_axes=self.mesh_axes,
                              kv_policy=kvcache.get_kv_policy(kv_policy))

    # -- plan diff + rolling migration ---------------------------------------

    def diff(self, other: "ProtectionPlan") -> PlanDiff:
        """Per-leaf delta against ``other`` (the target plan). Both plans
        must be built for the same tree — same leaf paths — or the diff is
        meaningless and this raises. Entries keep this plan's traversal
        order, so a rolling migration promotes shards deterministically."""
        if set(self.leaves) != set(other.leaves):
            missing = set(self.leaves) ^ set(other.leaves)
            raise ValueError(
                f"plans cover different trees ({len(self.leaves)} vs "
                f"{len(other.leaves)} leaves; e.g. {sorted(missing)[:3]})")
        entries = []
        for p, lp in self.leaves.items():
            tp = other.leaves[p]
            if lp.scheme_id == tp.scheme_id and lp.backend == tp.backend:
                continue
            entries.append(LeafDiff(
                path=p, from_scheme=lp.scheme_id, to_scheme=tp.scheme_id,
                from_backend=lp.backend, to_backend=tp.backend,
                stored_bytes_delta=tp.stored_bytes - lp.stored_bytes))
        return PlanDiff(entries=tuple(entries))

    def with_leaves(self, leaves: dict) -> "ProtectionPlan":
        """A new plan with some leaves replaced (``{path: LeafPlan}``) —
        the post-promotion plan a migration step hands back."""
        unknown = set(leaves) - set(self.leaves)
        if unknown:
            raise KeyError(f"not in this plan: {sorted(unknown)[:3]}")
        return ProtectionPlan(self.policy, {**self.leaves, **leaves},
                              mesh_axes=self.mesh_axes,
                              kv_policy=self.kv_policy)

    def migrate_step(self, enc_tree, target: "ProtectionPlan",
                     paths) -> tuple:
        """Promote the given leaves to their ``target`` scheme IN the
        encoded tree: transcode each named leaf's stored image
        (:func:`transcode_leaf` — int8-domain, value-exact under the
        default throttled encode) and adopt the target's ``LeafPlan``.

        Returns ``(new_enc_tree, new_plan, records)`` where each record is
        ``{path, from, to, corrected, due}`` with the decode flags observed
        while reading the old image. The serve step keeps working across
        the swap — decode dispatches on each ``ProtectedTensor.scheme_id``,
        so the only cost is one planned retrace per promoted tree
        structure (a checks plane appears or disappears)."""
        from .policy import path_str

        want = set(paths)
        todo = [p for p in self.leaves if p in want]
        if len(todo) != len(want):
            raise KeyError(f"paths not in plan: "
                           f"{sorted(want - set(todo))[:3]}")
        todo_set = set(todo)
        for p in todo:
            if target.leaves[p].scheme_id is None:
                raise ValueError(f"target leaves {p!r} unprotected — "
                                 "migration only moves between schemes")
        records = []

        def mig(path, leaf):
            p = path_str(path)
            if p not in todo_set:
                return leaf
            if not is_protected_tensor(leaf):
                raise ValueError(f"{p!r} is not a ProtectedTensor "
                                 "in the encoded tree")
            tp = target.leaves[p]
            new, cor, due = transcode_leaf(
                leaf, tp.scheme_id,
                backend=tp.backend_obj or tp.backend or "xla")
            records.append({"path": p, "from": leaf.scheme_id,
                            "to": tp.scheme_id, "corrected": int(cor),
                            "due": int(due)})
            return new

        new_tree = jax.tree_util.tree_map_with_path(
            mig, enc_tree, is_leaf=is_protected_tensor)
        new_plan = self.with_leaves({p: target.leaves[p] for p in todo})
        return new_tree, new_plan, records

    def coverage(self):
        """The plan as a :class:`CoverageReport` (the legacy view)."""
        from .policy import CoverageEntry, CoverageReport
        return CoverageReport([
            CoverageEntry(lp.path, lp.scheme_id, lp.reason, lp.n_weights,
                          lp.stored_bytes, lp.pad_bytes) for lp in self])

    # -- tree ops ------------------------------------------------------------

    def encode_tree(self, params):
        """fp params -> tree with ``ProtectedTensor`` leaves, each encoded
        under its planned scheme *and* backend."""
        def enc(path, leaf):
            lp = self._leaf(path)
            if not lp.protected:
                return leaf
            return self.policy.encode_leaf(leaf, lp.scheme_id,
                                           backend=lp.backend_obj)
        return jax.tree_util.tree_map_with_path(enc, params)

    def decode_tree(self, enc_tree, dtype=jnp.bfloat16):
        """Decode with each leaf's planned backend — one tree may mix
        schemes AND backends."""
        from .policy import decode_leaf

        def dec(path, leaf):
            if not is_protected_tensor(leaf):
                return leaf
            lp = self._leaf(path)
            return decode_leaf(leaf, dtype,
                               backend=lp.backend_obj or lp.backend)
        return jax.tree_util.tree_map_with_path(
            dec, enc_tree, is_leaf=is_protected_tensor)

    def spec_tree(self, enc_tree):
        """Sharding specs for an encoded tree, from the plan's materialized
        per-leaf specs (flat-padded images sharded when block-aligned)."""
        def spec(path, leaf):
            lp = self._leaf(path)
            if lp.spec is None:
                raise ValueError(
                    f"plan has no spec for {lp.path!r} — build it with "
                    f"make_plan(..., param_spec_fn=...) to use spec_tree()")
            return lp.spec
        return jax.tree_util.tree_map_with_path(
            spec, enc_tree, is_leaf=is_protected_tensor)


# ---------------------------------------------------------------------------
# materialization
# ---------------------------------------------------------------------------


def _mesh_sizes(mesh) -> Optional[dict]:
    if mesh is None:
        return None
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _drop_nondividing(spec, shape, sizes):
    """Drop mesh axes from dims they don't divide (mirrors the dry-run's
    sanitize pass, applied at plan time when the mesh is known)."""
    from jax.sharding import PartitionSpec as P
    if sizes is None or not isinstance(spec, P):
        return spec
    dims = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim_size, entry in zip(shape, dims):
        if entry is None:
            out.append(None)
            continue
        names = entry if isinstance(entry, tuple) else (entry,)
        prod = int(np.prod([sizes.get(n, 0) for n in names]))
        out.append(entry if prod and dim_size % prod == 0 else None)
    return P(*out)


def _flat_spec(enc_len: int, sizes):
    """1-D sharded spec for a flat-padded image over ('data', 'model') when
    every shard keeps whole 8-byte ECC blocks; replicated otherwise."""
    from jax.sharding import PartitionSpec as P
    if sizes is None:
        return P()
    axes = tuple(a for a in FLAT_SHARD_AXES if a in sizes)
    if not axes:
        return P()
    n_shards = int(np.prod([sizes[a] for a in axes]))
    if n_shards <= 1 or enc_len % (BLOCK * n_shards) != 0:
        return P()
    return P(axes)


def make_plan(policy, params, *, mesh=None,
              param_spec_fn: Optional[Callable] = None) -> ProtectionPlan:
    """Materialize a :class:`ProtectionPlan` from ``(policy, params, mesh?)``.

    params:        a concrete or abstract (``jax.eval_shape``) parameter
                   tree — only shapes/dtypes/paths are read.
    mesh:          optional ``jax.sharding.Mesh``; enables sharded specs for
                   flat-padded images and sanitizes same-shape specs against
                   the actual axis sizes.
    param_spec_fn: ``(path, leaf) -> PartitionSpec`` for weight leaves (the
                   same rule table serving uses); without it the plan has no
                   specs and :meth:`ProtectionPlan.spec_tree` raises.
    """
    from jax.sharding import PartitionSpec as P

    from .policy import path_str

    sizes = _mesh_sizes(mesh)
    leaves: dict = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        p = path_str(path)
        sid, reason = policy._plan(path, leaf)
        shape = tuple(getattr(leaf, "shape", ()))
        n = int(np.prod(shape)) if shape else 1
        if sid is None:
            nbytes = n * getattr(getattr(leaf, "dtype", None), "itemsize", 4)
            spec = None
            if param_spec_fn is not None:
                spec = _drop_nondividing(param_spec_fn(path, leaf), shape,
                                         sizes)
            leaves[p] = LeafPlan(
                path=p, scheme_id=None, reason=reason, backend="",
                backend_src="", layout="raw", shape=shape, n_weights=n,
                enc_shape=(), pad_bytes=0, check_bytes=0, stored_bytes=nbytes,
                spec=spec)
            continue

        scheme = get_scheme(sid)
        aligned = len(shape) >= 1 and shape[-1] % BLOCK == 0
        pad = 0 if aligned else (-n) % BLOCK
        enc_shape = shape if aligned else (n + pad,)
        checks = int((n + pad) * scheme.check_ratio)
        stored = n + pad + checks
        be, be_src = policy.resolve_backend(p)
        spec = None
        if param_spec_fn is not None:
            if aligned:
                enc_sds = jax.ShapeDtypeStruct(enc_shape, jnp.uint8)
                enc_spec = _drop_nondividing(param_spec_fn(path, enc_sds),
                                             enc_shape, sizes)
            else:
                enc_spec = _flat_spec(n + pad, sizes)
            spec = ProtectedTensor(enc=enc_spec,
                                   checks=P() if checks else None,
                                   scale=P(), scheme_id=scheme.scheme_id,
                                   orig_shape=shape)
        leaves[p] = LeafPlan(
            path=p, scheme_id=scheme.scheme_id, reason="", backend=be.name,
            backend_src=be_src, layout="same-shape" if aligned
            else "flat-padded", shape=shape, n_weights=n, enc_shape=enc_shape,
            pad_bytes=pad, check_bytes=checks, stored_bytes=stored, spec=spec,
            backend_obj=be)
    return ProtectionPlan(policy, leaves,
                          mesh_axes=tuple(sizes) if sizes else None)


# ---------------------------------------------------------------------------
# named policy presets (the dry-run grid's --policy axis)
# ---------------------------------------------------------------------------

# MLP / FFN / expert projections — everything the attn-inplace-mlp-secded
# preset moves to standard SEC-DED(72,64).
_MLP_PAT = (r"(^|/)(mlp|ffn|w_gate|w_up|w_down|"
            r"we_gate|we_up|we_down|ws_gate|ws_up|ws_down)(/|$)")

# Preset name -> ProtectionPolicy kwargs. "unprotected" is the paper's
# "faulty" row: same int8 residency, zero checks — the HBM/traffic baseline
# the dry-run deltas are measured against.
POLICY_PRESETS: dict = {
    "all-in-place": {},
    "all-secded72": {"default_scheme": "secded72"},
    "attn-inplace-mlp-secded": {"default_scheme": "in-place",
                                "rules": [(_MLP_PAT, "secded72")]},
    "unprotected": {"default_scheme": "faulty"},
}


def get_policy_preset(name: str, **overrides):
    """Build a named preset ``ProtectionPolicy``; extra kwargs override the
    preset's (e.g. ``predicate=``, ``backend=``)."""
    from .policy import ProtectionPolicy
    try:
        kw = dict(POLICY_PRESETS[name])
    except KeyError:
        raise ValueError(f"unknown policy preset {name!r}; one of "
                         f"{sorted(POLICY_PRESETS)}") from None
    kw.update(overrides)
    return ProtectionPolicy(**kw)
