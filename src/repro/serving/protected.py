"""Protected serving on top of ``repro.protection``.

Weights live in memory as ``ProtectedTensor`` leaves — ECC-encoded int8
whose image (for the in-place scheme) has the SAME shape as the weight, so
it inherits the weight's sharding. The serve step decodes **at the point of
use**: each projection either routes through the fused Pallas
``kernels/ecc_qmatmul`` (decode in VMEM on the way to the MXU — no decoded
copy of any weight ever lands in HBM) or decodes just its own leaf inline
next to its matmul, per the :class:`~repro.protection.ProtectionPlan`.
The old whole-tree decode per step survives only as the
``decode_at_use=False`` ablation; ``decode_per_step=False`` is the
decode-once-outside baseline.

Per-layer fault accounting rides along: ``with_flags=True`` makes the step
also return the (corrected, DUE) counts each layer's decodes observed — the
double-error detections the fused kernel used to swallow.

This module is the LM-serving adapter; the protection API itself (schemes,
policy, coverage, injection) lives in ``repro.protection``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro import protection
from repro.models import layers as L
from repro.models import lm
from repro.models.config import ArchConfig
from repro.protection.fused import ProtectedWeight, is_matmul_weight
from repro.protection.policy import path_str
from repro.protection.tensor import ProtectedTensor, is_protected_tensor

STACKED_KEYS = ("layers", "tail", "enc_layers")


def encode_leaf(w: jnp.ndarray,
                policy: Optional[protection.ProtectionPolicy] = None
                ) -> protection.ProtectedTensor:
    policy = policy or protection.default_policy()
    return policy.encode_leaf(w, policy.default_scheme)


def decode_leaf(p: protection.ProtectedTensor, dtype=jnp.bfloat16,
                *, backend="xla") -> jnp.ndarray:
    return protection.decode_leaf(p, dtype, backend=backend)


def encode_tree(params,
                policy: Optional[protection.ProtectionPolicy] = None) -> Any:
    """fp32 params -> serving tree (protected leaves -> ProtectedTensor)."""
    return protection.encode_tree(params, policy)


def decode_tree(enc_params, dtype=jnp.bfloat16, *, backend="xla"):
    return protection.decode_tree(enc_params, dtype, backend=backend)


def coverage(params, policy: Optional[protection.ProtectionPolicy] = None
             ) -> protection.CoverageReport:
    """Per-tree protection coverage (count + bytes, no silent gaps)."""
    return protection.coverage(params, policy)


def make_plan(params, policy: Optional[protection.ProtectionPolicy] = None,
              *, mesh=None, param_spec_fn=None) -> protection.ProtectionPlan:
    """Materialize the serving :class:`~repro.protection.ProtectionPlan` for
    a (possibly abstract) parameter tree — resolve scheme, layout, backend,
    and sharding spec per leaf ONCE, then hand the plan to
    :func:`make_serve_step` / :func:`make_prefill` / the dry-run cells."""
    return protection.make_plan(policy or protection.default_policy(), params,
                                mesh=mesh, param_spec_fn=param_spec_fn)


def init_encoded(cfg: ArchConfig, plan: protection.ProtectionPlan, key,
                 dtype=jnp.bfloat16):
    """Random weights from ``key``, encoded under ``plan``, built as ONE
    compiled program: the float tree (a ``dtype`` source, bf16 by default)
    exists only inside it, so a model whose float32 tree would not fit the
    device still builds. Build ``plan`` from ``lm.param_specs(cfg,
    dtype)``."""
    return jax.jit(lambda k: plan.encode_tree(
        lm.init_params(cfg, k, dtype)))(key)


# ---------------------------------------------------------------------------
# decode-at-use routing
# ---------------------------------------------------------------------------


class _Router:
    """Per-leaf decode route: (backend, fused tiles, activation-quant mode)
    from the plan (leaf rules > autotune > policy default) or from the
    policy-wide ``backend`` when serving without a plan.

    act_quant: None (float activations) | "dynamic" | "static" (serve-step
    override applied to every capable leaf) | "plan" (follow each leaf's
    ``LeafPlan.act_quant`` decision). calibrate=True runs the float path
    but wires each matmul's activation absmax into the layers act sink.
    """

    def __init__(self, plan, backend, *, act_quant=None, calibrate=False,
                 abft_per_slot=False):
        if act_quant not in (None, "static", "dynamic", "plan"):
            raise ValueError(f"act_quant {act_quant!r}; one of "
                             f"(None, 'static', 'dynamic', 'plan')")
        self.plan = plan
        self.backend = protection.get_backend(backend)
        self.autotune = getattr(getattr(plan, "policy", None),
                                "autotune", None)
        self.act_quant = act_quant
        self.calibrate = calibrate
        self.abft_per_slot = abft_per_slot

    @property
    def any_abft(self) -> bool:
        """True when any planned leaf carries an ABFT or clamp decision —
        the serve step installs the ABFT sink only then, so guarded and
        unguarded plans trace to different (but each fixed) programs."""
        if self.plan is None:
            return False
        return any(lp.abft or lp.clamp is not None for lp in self.plan)

    def abft_for(self, path: str) -> tuple:
        """-> (abft enabled, clamp bound | None) for one leaf."""
        lp = self.plan.leaves.get(path) if self.plan is not None else None
        if lp is None:
            return False, None
        return bool(lp.abft), lp.clamp

    def backend_for(self, path: str):
        """Resolved backend for a leaf by its FULL plan path (the scoped
        layer transforms prefix their subtree key, so 'rg0/...' leaves in
        the hybrid decoder and its tail resolve independently)."""
        if self.plan is None:
            return self.backend
        lp = self.plan.leaves.get(path)
        if lp is not None and lp.protected:
            return lp.backend_obj or protection.get_backend(lp.backend)
        return self.backend

    def tiles_for(self, shape, *, key="tiles"):
        lookup = getattr(self.autotune, "lookup_tiles_src", None)
        return lookup(shape, key=key)[0] if lookup is not None else None

    def act_for(self, path: str) -> tuple:
        """-> (act_quant mode | None, a_scale | None) for one leaf."""
        lp = self.plan.leaves.get(path) if self.plan is not None else None
        if self.act_quant is None:
            return None, None
        if self.act_quant == "dynamic":
            return "dynamic", None
        if self.act_quant == "static":
            # the calibrated set defines what serves int8; uncalibrated
            # leaves keep float activations rather than guessing a scale
            if lp is not None and lp.a_scale is not None:
                return "static", lp.a_scale
            return None, None
        # "plan": follow the per-leaf decision
        if lp is not None:
            return lp.act_quant, lp.a_scale
        return None, None

    def wrap(self, path: str, pt: ProtectedTensor, dtype):
        """Decode-at-use view for a matmul-consumed leaf; leaves that are
        indexed elementwise (conv kernels) decode inline right here — still
        this leaf only, still at its point of use inside the layer."""
        be = self.backend_for(path)
        if not is_matmul_weight(path):
            w, corrected, due = protection.decode_leaf_with_flags(
                pt, dtype, backend=be)
            L.record_flags(corrected, due)
            return w
        lp = self.plan.leaves.get(path) if self.plan is not None else None
        shape = tuple(pt.orig_shape)
        tiles = (lp.tiles if lp is not None and lp.tiles is not None
                 else self.tiles_for(shape))
        int8_tiles = (lp.int8_tiles
                      if lp is not None and lp.int8_tiles is not None
                      else self.tiles_for(shape, key="int8_tiles"))
        aq, a_scale = self.act_for(path)
        abft, clamp = self.abft_for(path)
        return ProtectedWeight(
            pt, be, tiles=tiles, int8_tiles=int8_tiles,
            record=L.record_flags, act_quant=aq, a_scale=a_scale,
            abft=abft, clamp=clamp, record_abft=L.record_abft,
            abft_per_slot=self.abft_per_slot,
            observe=(functools.partial(L.record_act, path)
                     if self.calibrate else None))


def _scan_ready(subtree, prefix: str, router: _Router, dtype):
    """Make a stacked encoded subtree scannable: same-shape images keep
    their codec (scale broadcast over the layer dim so ``lax.scan`` can
    slice the ProtectedTensor); flat-padded images — whose 1-D byte image
    flattens *across* layers and cannot be sliced — decode here, per step
    but still per leaf (their flags land in the "top" row, not a layer
    row: the decode happens before the scan runs)."""

    def prep(path, leaf):
        if not is_protected_tensor(leaf):
            return leaf
        n_stack = int(leaf.orig_shape[0])
        if leaf.is_flat:
            w, corrected, due = protection.decode_leaf_with_flags(
                leaf, dtype, backend=router.backend_for(
                    f"{prefix}/{path_str(path)}"))
            L.record_flags(corrected, due)
            return w
        return dataclasses.replace(
            leaf, scale=jnp.broadcast_to(leaf.scale, (n_stack,)))

    return jax.tree_util.tree_map_with_path(prep, subtree,
                                            is_leaf=is_protected_tensor)


def _layer_transform(router: _Router, dtype):
    """Per-subtree ``{"layers"|"tail"|"enc_layers": fn}`` transforms for
    ``lm``'s scans: each fn fixes the sliced ProtectedTensor metadata (drop
    the stacked leading dim) and wraps each protected leaf in its
    decode-at-use view, resolving the route by the leaf's FULL plan path."""

    def scoped(prefix):
        def lt(lp):
            def wrap(path, leaf):
                if not is_protected_tensor(leaf):
                    return leaf
                pt = dataclasses.replace(leaf,
                                         orig_shape=leaf.orig_shape[1:])
                return router.wrap(f"{prefix}/{path_str(path)}", pt, dtype)
            return jax.tree_util.tree_map_with_path(
                wrap, lp, is_leaf=is_protected_tensor)
        return lt

    return {k: scoped(k) for k in STACKED_KEYS}


def _use_tree(enc_params, router: _Router, dtype):
    """enc tree -> params tree lm can run with decode at use: stacked
    subtrees stay encoded (scan-ready), top-level protected leaves become
    decode-at-use views (``embed`` decodes to a real array — it is indexed
    and transposed, not matmul'd)."""
    out = {}
    for key, sub in enc_params.items():
        if key in STACKED_KEYS:
            out[key] = _scan_ready(sub, key, router, dtype)
        elif is_protected_tensor(sub):
            if key == "embed":
                w, corrected, due = protection.decode_leaf_with_flags(
                    sub, dtype, backend=router.backend_for(key))
                L.record_flags(corrected, due)
                out[key] = w
            else:
                out[key] = router.wrap(key, sub, dtype)
        else:
            out[key] = sub
    return out


def _decoder(plan, dtype, backend):
    if plan is not None:
        return lambda enc_params: plan.decode_tree(enc_params, dtype)
    be = protection.get_backend(backend)
    return lambda enc_params: protection.decode_tree(enc_params, dtype,
                                                     backend=be)


def make_serve_step(cfg: ArchConfig, *, plan=None,
                    decode_per_step: bool = True,
                    decode_at_use: Optional[bool] = None,
                    dtype=jnp.bfloat16, backend="xla",
                    with_flags: bool = False,
                    act_quant: Optional[str] = None,
                    kv_policy=None, attention_impl: Optional[str] = None):
    """serve_step(enc_params, cache, tokens, pos) -> (logits, cache)
    (``+ flags`` with ``with_flags=True``).

    decode_at_use=True (the default) decodes each weight at its point of
    use — fused decode+matmul for Pallas-routed in-place leaves, per-leaf
    inline decode otherwise — so no decoded copy of the tree is ever
    resident. ``decode_at_use=False`` is the whole-tree decode-per-step
    ablation; ``decode_per_step=False`` the decode-once-outside baseline.
    ``plan`` (a :class:`~repro.protection.ProtectionPlan`) routes each leaf,
    so one model mixes schemes AND backends; without a plan, ``backend`` is
    the policy-wide route. ``with_flags=True`` (decode-at-use only) adds a
    flags dict: per-layer (corrected, DUE) int32 counts plus the "top" row
    for embed/head.

    ``act_quant`` switches projections onto the int8 MXU path (activations
    quantized at the point of use, served through the fused kernel's
    requantize epilogue on the Pallas route): "dynamic" (per-token absmax),
    "static" (calibrated per-leaf scales — see :func:`calibrate_act_scales`
    and ``plan.with_act_quant``), or "plan" (follow each leaf's plan
    decision). Decode-at-use only.

    When the plan marks leaves for ABFT / activation clamps
    (``plan.with_abft`` / ``with_act_quant(..., clamp=True)``) and
    ``with_flags=True``, the flags dict additionally carries the
    (checksum mismatches, clamp hits) channel: "layers_abft" /
    "tail_abft" / "top_abft" rows, shaped like the (corrected, DUE)
    rows — per-slot vectors instead of scalars when the KV policy has
    ``per_slot_flags`` so the front-end can attribute compute faults to
    requests.

    ``kv_policy`` (a :class:`~repro.serving.kvcache.KVProtectionPolicy` or
    preset name) serves against a paged protected KV cache from
    :func:`~repro.serving.kvcache.init_paged_cache`; with ``with_flags`` the
    flags dict then also carries the per-layer "layers_kv" KV rows. Works in
    every decode mode — KV protection is orthogonal to how the weights
    decode. When ``kv_policy`` is not given it defaults from
    ``plan.kv_policy`` (set via ``ProtectionPlan.with_kv_policy``), so one
    plan object can carry both the weight and the serving-state decisions.
    ``attention_impl`` overrides the resolved policy's attention routing
    ("strip" | "chunked") without rebuilding the policy — the switch onto
    the page-chunked online-softmax kernel for long contexts.
    """
    from . import kvcache
    if kv_policy is None and plan is not None:
        kv_policy = getattr(plan, "kv_policy", None)
    kvp = kvcache.get_kv_policy(kv_policy)
    if attention_impl is not None:
        if kvp is None:
            raise ValueError("attention_impl override needs a kv_policy")
        kvp = dataclasses.replace(kvp, attention_impl=attention_impl)
    if decode_at_use is None:
        decode_at_use = decode_per_step
    if act_quant is not None and not (decode_at_use and decode_per_step):
        raise ValueError("act_quant needs the decode-at-use serve step (the "
                         "whole-tree decode paths serve float weights)")
    if decode_at_use and decode_per_step:
        per_slot = bool(kvp is not None and kvp.per_slot_flags)
        router = _Router(plan, backend, act_quant=act_quant,
                         abft_per_slot=per_slot)
        lt = _layer_transform(router, dtype)
        track_abft = with_flags and router.any_abft

        def serve_step(enc_params, cache, tokens, pos):
            sink: list = []
            L.set_flags_sink(sink if with_flags else None)
            L.set_abft_sink([] if track_abft else None)
            try:
                params = _use_tree(enc_params, router, dtype)
                top_flags = L.drain_flags() if with_flags else None
                out = lm.decode_step(cfg, params, cache, tokens, pos,
                                     dtype=dtype, layer_transform=lt,
                                     collect_flags=with_flags,
                                     kv_policy=kvp)
                if with_flags:  # the output head decodes after the scans
                    top_flags = top_flags + L.drain_flags()
                # no matmul runs before the model call, so one post-step
                # drain captures every top-level ABFT record (pre-draining
                # zeros (2,) would not broadcast against per-slot (2, B))
                top_abft = L.drain_abft() if track_abft else None
            finally:
                L.set_flags_sink(None)
                L.set_abft_sink(None)
            if not with_flags:
                return out
            logits, new_cache, flags = out
            extra = {"top": top_flags, **flags}
            if track_abft:
                extra["top_abft"] = top_abft
            return logits, new_cache, extra

        return serve_step

    if with_flags:
        raise ValueError("with_flags needs the decode-at-use serve step "
                         "(the whole-tree decode paths discard flags)")
    decode = _decoder(plan, dtype, backend)

    def serve_step(enc_params, cache, tokens, pos):
        params = decode(enc_params) if decode_per_step else enc_params
        return lm.decode_step(cfg, params, cache, tokens, pos, dtype=dtype,
                              kv_policy=kvp)

    return serve_step


def make_prefill(cfg: ArchConfig, *, plan=None, dtype=jnp.bfloat16,
                 chunk: int = 2048, backend="xla",
                 decode_at_use: bool = True, with_flags: bool = False,
                 act_quant: Optional[str] = None, kv_policy=None,
                 attention_impl: Optional[str] = None):
    """prefill(enc_params, tokens, extras) -> logits (``+ flags`` with
    ``with_flags=True``). Decode-at-use by default, same routing as
    :func:`make_serve_step` (including the ``act_quant`` int8 path);
    ``decode_at_use=False`` keeps the whole-tree decode ablation.

    With ``kv_policy`` the returned callable is instead
    ``prefill(enc_params, cache, tokens, extras=None) -> (logits, cache)``
    (``+ flags``): it fills the paged protected KV cache through
    ``lm.prefill_with_cache`` so decode steps can continue from it, and the
    flags dict gains the per-layer "layers_kv" rows. ``attention_impl``
    overrides the resolved policy's attention routing, as in
    :func:`make_serve_step`."""
    from . import kvcache
    if kv_policy is None and plan is not None:
        kv_policy = getattr(plan, "kv_policy", None)
    kvp = kvcache.get_kv_policy(kv_policy)
    if attention_impl is not None:
        if kvp is None:
            raise ValueError("attention_impl override needs a kv_policy")
        kvp = dataclasses.replace(kvp, attention_impl=attention_impl)
    if act_quant is not None and not decode_at_use:
        raise ValueError("act_quant needs the decode-at-use prefill")

    def parse_args(args, extras):
        """(tokens[, extras]) without kv_policy; (cache, tokens[, extras])
        with — extras stays positional-compatible either way."""
        want = 2 if kvp is not None else 1
        if len(args) not in (want, want + 1):
            raise TypeError(f"prefill takes {want} positional args after "
                            f"enc_params (+ optional extras); got {len(args)}")
        if len(args) == want + 1:
            extras = args[-1]
        cache = args[0] if kvp is not None else None
        tokens = args[want - 1]
        return cache, tokens, extras or {}

    if decode_at_use:
        router = _Router(plan, backend, act_quant=act_quant)
        lt = _layer_transform(router, dtype)
        track_abft = with_flags and router.any_abft

        def prefill(enc_params, *args, extras=None):
            cache, tokens, extras = parse_args(args, extras)
            sink: list = []
            L.set_flags_sink(sink if with_flags else None)
            L.set_abft_sink([] if track_abft else None)
            try:
                params = _use_tree(enc_params, router, dtype)
                top_flags = L.drain_flags() if with_flags else None
                if kvp is not None:
                    out = lm.prefill_with_cache(
                        cfg, params, cache, tokens, dtype=dtype, chunk=chunk,
                        layer_transform=lt, collect_flags=with_flags,
                        kv_policy=kvp)
                else:
                    out = lm.forward(cfg, params, tokens, dtype=dtype,
                                     chunk=chunk, layer_transform=lt,
                                     collect_flags=with_flags, **extras)
                if with_flags:  # the output head decodes after the scans
                    top_flags = top_flags + L.drain_flags()
                top_abft = L.drain_abft() if track_abft else None
            finally:
                L.set_flags_sink(None)
                L.set_abft_sink(None)
            if not with_flags:
                return out
            extra_top = ({"top": top_flags, "top_abft": top_abft}
                         if track_abft else {"top": top_flags})
            if kvp is not None:
                logits, new_cache, flags = out
                return logits, new_cache, {**extra_top, **flags}
            logits, flags = out
            return logits, {**extra_top, **flags}

        return prefill

    if with_flags:
        raise ValueError("with_flags needs the decode-at-use prefill")
    decode = _decoder(plan, dtype, backend)

    def prefill(enc_params, *args, extras=None):
        cache, tokens, extras = parse_args(args, extras)
        params = decode(enc_params)
        if kvp is not None:
            return lm.prefill_with_cache(cfg, params, cache, tokens,
                                         dtype=dtype, chunk=chunk,
                                         kv_policy=kvp)
        return lm.forward(cfg, params, tokens, dtype=dtype, chunk=chunk,
                          **extras)
    return prefill


def calibrate_act_scales(cfg: ArchConfig, enc_params, tokens, *, plan=None,
                         backend="xla", dtype=jnp.bfloat16, chunk: int = 2048,
                         extras=None) -> dict:
    """Calibrate static activation scales from a small batch.

    Runs the float decode-at-use prefill over ``tokens`` (B, S) with every
    projection's activation absmax recorded at its point of use (the same
    per-leaf routing as serving, so exactly the leaves that will consume the
    scales observe them — scanned layers report through the scan, so each
    stacked leaf gets the max over its layers). Returns ``{leaf path:
    a_scale}`` with ``a_scale = absmax / 127`` — feed it to
    ``plan.with_act_quant("static", scales)`` and serve with
    ``make_serve_step(..., act_quant="static"`` or ``"plan")``.
    """
    router = _Router(plan, backend, calibrate=True)
    lt = _layer_transform(router, dtype)
    L.set_act_sink({})
    try:
        params = _use_tree(enc_params, router, dtype)
        extras = extras or {}
        _, acts = lm.forward(cfg, params, tokens, dtype=dtype, chunk=chunk,
                             layer_transform=lt, collect_acts=True, **extras)
        top = L.drain_acts()  # embed/head record outside the scans
    finally:
        L.set_act_sink(None)
    # same floor as quant.compute_scale: a projection whose calibration
    # activations were all zero must not bake a_scale=0 (divide-by-zero at
    # serve time)
    def scale(absmax):
        return max(float(absmax), 1e-12) / 127.0

    scales: dict = {}
    for sub in acts.values():          # {"layers": {path: (n_layers,)}, ...}
        for path, per_layer in (sub or {}).items():
            scales[path] = scale(jnp.max(per_layer))
    for path, absmax in top.items():
        scales[path] = scale(absmax)
    return scales


def spec_tree(enc_params_or_params, param_spec_fn, *, mesh=None):
    """Sharding specs for a serving tree: encoded image inherits the weight's
    spec; scales and check bytes replicated (flat images sharded when
    ``mesh`` is given — prefer ``make_plan(...).spec_tree()``)."""
    return protection.spec_tree(enc_params_or_params, param_spec_fn,
                                mesh=mesh)
