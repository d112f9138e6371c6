"""``repro.protection`` — the unified host/device protection API.

One surface for everything the paper's contribution needs in production:

* :class:`ProtectedTensor` — pytree carrier for encoded weights (replaces the
  old ``{"enc", "scale"}`` dict marker).
* :mod:`schemes <repro.protection.schemes>` — jittable codecs for all four
  paper schemes (``faulty`` / ``parity-zero`` / ``secded72`` / ``in-place``).
* :class:`ProtectionPolicy` — per-layer scheme selection, pad-and-protect,
  and :class:`CoverageReport` (subsumes ``wot.is_protected_weight`` and the
  old silent ``last-dim % 8`` gate).
* :mod:`backends <repro.protection.backends>` — ``backend="xla" | "pallas"``
  routes block codec compute and the fused protected matmul.
* :mod:`host <repro.protection.host>` — the NumPy Table-2 trial pipeline as a
  thin wrapper over the same schemes (the campaign cross-check oracle).
* :mod:`campaign <repro.protection.campaign>` — compiled on-device fault
  campaigns: encode once, sweep the whole (trial x rate) grid inside one
  jitted program, get a serializable :class:`CampaignResult`.

See ``docs/campaigns.md`` for the campaign API guide and ``docs/faq.md`` for
the fault model.
"""
from __future__ import annotations

import jax.numpy as jnp

from .backends import (BACKENDS, Backend, PallasBackend, XlaBackend,
                       get_backend)
from .campaign import (CampaignResult, accuracy_eval, compute_campaign,
                       due_campaign, due_eval, fidelity_campaign,
                       fidelity_eval, run_campaign, run_campaign_host)
from .fused import ProtectedWeight, can_fuse
from .host import HostScheme, Stored, get_host_scheme, run_fault_trial
from .plan import (POLICY_PRESETS, LeafPlan, ProtectionPlan,
                   get_policy_preset, make_plan)
from .policy import (CoverageEntry, CoverageReport, ProtectionPolicy,
                     decode_leaf, decode_leaf_with_flags, decode_tree,
                     decode_tree_with_flags, inject_tree,
                     inject_tree_device, space_overhead, spec_tree)
from .schemes import (ALIASES, SCHEMES, Faulty, InPlace, ParityZero, Scheme,
                      Secded72, get_scheme, scheme_ids)
from .tensor import ProtectedTensor, is_protected_tensor

__all__ = [
    "ProtectedTensor", "is_protected_tensor",
    "Scheme", "Faulty", "ParityZero", "Secded72", "InPlace",
    "SCHEMES", "ALIASES", "get_scheme", "scheme_ids",
    "ProtectionPolicy", "CoverageReport", "CoverageEntry",
    "ProtectionPlan", "LeafPlan", "make_plan",
    "POLICY_PRESETS", "get_policy_preset",
    "decode_leaf", "decode_tree", "decode_leaf_with_flags",
    "decode_tree_with_flags", "inject_tree", "inject_tree_device",
    "spec_tree", "space_overhead", "ProtectedWeight", "can_fuse",
    "Backend", "XlaBackend", "PallasBackend", "BACKENDS", "get_backend",
    "HostScheme", "Stored", "get_host_scheme", "run_fault_trial",
    "CampaignResult", "run_campaign", "run_campaign_host",
    "fidelity_campaign", "due_campaign", "compute_campaign", "accuracy_eval",
    "fidelity_eval",
    "due_eval",
    "default_policy", "encode_tree", "coverage", "qmatmul",
]

_DEFAULT_POLICY: ProtectionPolicy | None = None


def default_policy() -> ProtectionPolicy:
    """The serving default: in-place zero-space ECC on every weight tensor,
    pad-and-protect, XLA backend."""
    global _DEFAULT_POLICY
    if _DEFAULT_POLICY is None:
        _DEFAULT_POLICY = ProtectionPolicy()
    return _DEFAULT_POLICY


def encode_tree(params, policy: ProtectionPolicy | None = None):
    """Encode a parameter tree under ``policy`` (default: in-place on all
    weights). Decode side needs no policy — each ``ProtectedTensor`` carries
    its scheme id."""
    return (policy or default_policy()).encode_tree(params)


def coverage(params, policy: ProtectionPolicy | None = None) -> CoverageReport:
    return (policy or default_policy()).coverage(params)


def qmatmul(a_q, w, a_scale, *, backend="xla"):
    """Protected matmul: ``a_q (M,K) int8 @ decode(w) * scales -> (M,N) f32``.

    ``w`` is a ``ProtectedTensor`` holding an in-place-encoded 2-D weight in
    the same-shape layout (the fused Pallas kernel decodes 64-bit blocks on
    the way to the MXU; the XLA backend decodes then matmuls).
    """
    if not is_protected_tensor(w):
        raise TypeError(f"qmatmul needs a ProtectedTensor, got {type(w)}")
    if w.scheme_id != "in-place":
        raise ValueError(f"fused qmatmul supports the in-place scheme only, "
                         f"got {w.scheme_id!r}")
    if w.is_flat or w.enc.ndim != 2:
        raise ValueError("qmatmul needs a 2-D same-shape encoded image "
                         f"(got enc shape {tuple(w.enc.shape)} for weight "
                         f"{tuple(w.orig_shape)})")
    return get_backend(backend).qmatmul(a_q, w.enc, a_scale,
                                        w.scale.astype(jnp.float32))
