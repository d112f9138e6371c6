"""Compiled on-device fault campaigns — the paper's Table 2 at device speed.

The host pipeline re-encodes and re-injects per (scheme, rate, trial), so a
4-scheme x 5-rate x 5-trial grid is ~100 serial host round-trips.  A
*campaign* instead encodes the model **once**, then runs the whole
(trial x rate) grid of inject -> decode -> eval inside **one compiled
program**:

* the fault rate is a *traced* scalar: every leaf samples a fixed budget of
  ``n_faults(bits, max(rates))`` candidate bit positions and keeps the first
  ``round(bits * rate)`` (``core.faults.inject_jax_rate``), so one program
  shape covers every rate in the sweep;
* ``batch="vmap"`` lays the full grid out as two nested ``vmap`` axes
  (fastest; peak memory ~ grid-size x the per-cell parity vectors);
  ``batch="scan"`` runs the same cells sequentially under ``lax.scan``
  (constant memory; use for big models or large trial counts);
* exactly **one** jit compile happens per campaign (AOT ``lower().compile()``
  — the compile time is reported separately from the sweep wall-clock).

The host path (``protection.inject_tree`` + ``host.run_fault_trial``) stays
as the cross-check oracle: :func:`run_campaign_host` runs the identical grid
through it, and the test suite asserts statistical parity between the two.
"""
from __future__ import annotations

import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from .policy import (ProtectionPolicy, decode_leaf, decode_tree,
                     decode_tree_with_flags, inject_tree,
                     inject_tree_device, path_str, space_overhead)
from .tensor import is_protected_tensor

__all__ = ["CampaignResult", "run_campaign", "run_campaign_host",
           "fidelity_campaign", "due_campaign", "compute_campaign",
           "accuracy_eval", "fidelity_eval", "due_eval"]

RATES = (1e-6, 1e-5, 1e-4, 1e-3, 3e-3)


# ---------------------------------------------------------------------------
# result carrier
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class CampaignResult:
    """One campaign = one (model, policy) over a (rate x trial) grid.

    ``grid[r][t]`` is the raw metric value (accuracy or decode fidelity) of
    trial ``t`` at ``rates[r]``; ``clean`` is the same metric with zero
    faults.  Derived per-rate mean/std/drop views are computed, not stored,
    so the JSON round-trip stays lossless.
    """

    scheme: str                # scheme id(s) of the policy under test
    metric: str                # "accuracy" | "fidelity"
    rates: tuple               # swept fault rates
    trials: int
    clean: float               # metric at rate 0 (no injection)
    grid: tuple                # (len(rates), trials) nested tuples of float
    space_overhead: float      # (stored - weight) / weight bytes
    compile_s: float           # one-off jit compile time (0.0 for host)
    wall_clock_s: float        # grid execution time, compile excluded
    batch: str                 # "vmap" | "scan" | "host"
    backend: str               # protection backend ("xla" | "pallas")
    platform: str              # jax device platform ("cpu", "tpu", ...)
    device: str                # jax device kind string
    target: str = "weights"    # what the faults hit: "weights" | "kv" |
    #                            "both" | "compute" (ABFT campaign)
    layer_rows: tuple = ()     # (n_layers, 2) per-layer KV (corrected, due)
    #                            at max(rates) — () unless target covers KV
    coverage_rows: tuple = ()  # per-leaf (path, detected, injected) at
    #                            max(rates) — compute campaigns only

    # -- derived views -------------------------------------------------------

    def mean(self) -> tuple:
        """Per-rate mean metric across trials."""
        return tuple(float(np.mean(row)) for row in self.grid)

    def std(self) -> tuple:
        """Per-rate metric std across trials."""
        return tuple(float(np.std(row)) for row in self.grid)

    def drop(self) -> tuple:
        """Per-rate mean metric drop vs clean (the Table-2 cell value)."""
        return tuple(self.clean - m for m in self.mean())

    def row(self) -> list:
        """Table-2 row format: ``[(mean_drop, std), ...]`` per rate."""
        return list(zip(self.drop(), self.std()))

    # -- serialization -------------------------------------------------------

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["rates"] = list(self.rates)
        d["grid"] = [list(row) for row in self.grid]
        d["layer_rows"] = [list(row) for row in self.layer_rows]
        d["coverage_rows"] = [list(row) for row in self.coverage_rows]
        d["derived"] = {"mean": list(self.mean()), "std": list(self.std()),
                        "drop": list(self.drop())}
        return d

    @classmethod
    def from_dict(cls, d: dict) -> "CampaignResult":
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in d.items() if k in fields}
        kw["rates"] = tuple(kw["rates"])
        kw["grid"] = tuple(tuple(row) for row in kw["grid"])
        kw["layer_rows"] = tuple(tuple(int(v) for v in row)
                                 for row in kw.get("layer_rows", ()))
        kw["coverage_rows"] = tuple(
            (str(p), int(det), int(inj))
            for p, det, inj in kw.get("coverage_rows", ()))
        return cls(**kw)

    def to_json(self, **kw) -> str:
        return json.dumps(self.to_dict(), indent=kw.pop("indent", 2), **kw)

    @classmethod
    def from_json(cls, s: str) -> "CampaignResult":
        return cls.from_dict(json.loads(s))

    def save(self, path) -> None:
        with open(path, "w") as f:
            f.write(self.to_json() + "\n")

    @classmethod
    def load(cls, path) -> "CampaignResult":
        with open(path) as f:
            return cls.from_json(f.read())


# ---------------------------------------------------------------------------
# eval metrics
# ---------------------------------------------------------------------------


def accuracy_eval(fwd, batch):
    """Metric: top-1 accuracy of ``fwd(decoded_params, images)`` on a fixed
    eval batch (the Table-2 metric)."""
    images = jnp.asarray(batch["images"])
    labels = jnp.asarray(batch["labels"])

    def ev(dec_params):
        lg = fwd(dec_params, images)
        return jnp.mean((jnp.argmax(lg, -1) == labels).astype(jnp.float32))

    return ev


def fidelity_eval(enc_tree, backend="xla"):
    """Metric: fraction of *protected* weight values that decode identically
    to the fault-free decode.  Label-free, so it works for any model (the
    serving smoke-check uses it on LM weights)."""
    enc_leaves = jax.tree_util.tree_flatten(
        enc_tree, is_leaf=is_protected_tensor)[0]
    prot_idx = [i for i, l in enumerate(enc_leaves) if is_protected_tensor(l)]
    if not prot_idx:
        raise ValueError("fidelity_eval: the tree has no protected leaves "
                         "(did the policy's predicate select anything?)")
    clean = [decode_leaf(enc_leaves[i], jnp.float32, backend=backend)
             for i in prot_idx]
    total = sum(int(np.prod(c.shape)) for c in clean)

    def ev(dec_params):
        leaves = jax.tree_util.tree_leaves(dec_params)
        eq = sum(jnp.sum(leaves[i] == c) for i, c in zip(prot_idx, clean))
        return eq.astype(jnp.float32) / max(total, 1)

    return ev


def due_eval(backend="xla", *, what="due"):
    """Metric over the ENCODED tree: total detected-uncorrectable (double)
    errors — the per-leaf flags the decode-at-use serve step surfaces,
    summed at campaign scale (``what="corrected"`` counts repairs instead).
    """
    idx = {"corrected": 0, "due": 1}[what]

    def ev(enc_tree):
        _, flags = decode_tree_with_flags(enc_tree, jnp.float32,
                                          backend=backend)
        total = jnp.zeros((), jnp.int32)
        for pair in flags.values():
            total = total + pair[idx]
        return total.astype(jnp.float32)

    ev.wants_encoded = True
    return ev


# ---------------------------------------------------------------------------
# the compiled grid
# ---------------------------------------------------------------------------


def _scheme_label(enc_tree) -> str:
    sids = sorted({l.scheme_id for l in jax.tree_util.tree_leaves(
        enc_tree, is_leaf=is_protected_tensor) if is_protected_tensor(l)})
    return "+".join(sids) if sids else "none"


def _is_encoded(tree) -> bool:
    return any(is_protected_tensor(l) for l in jax.tree_util.tree_leaves(
        tree, is_leaf=is_protected_tensor))


def _run_grid(enc, eval_fn, rates, trials, key, batch, backend, metric):
    """Shared engine: compile one program for the whole (rate x trial) grid,
    execute it, and wrap everything into a :class:`CampaignResult`."""
    if batch not in ("vmap", "scan"):
        raise ValueError(f"batch must be 'vmap' or 'scan', got {batch!r}")
    rates = tuple(float(r) for r in rates)
    max_rate = max(rates) if rates else 0.0
    n_rates = len(rates)

    # eval fns tagged wants_encoded consume the (dirty) encoded tree itself
    # (e.g. the DUE-flags metric); everything else sees the decoded params
    wants_enc = getattr(eval_fn, "wants_encoded", False)
    clean = float(eval_fn(enc) if wants_enc else
                  eval_fn(decode_tree(enc, jnp.float32, backend=backend)))

    def cell(enc_tree, rate, k):
        dirty = inject_tree_device(enc_tree, rate, k, max_rate=max_rate)
        if wants_enc:
            return eval_fn(dirty)
        return eval_fn(decode_tree(dirty, jnp.float32, backend=backend))

    if batch == "vmap":
        def grid(enc_tree, rates_v, keys_v):
            per_rate = jax.vmap(cell, in_axes=(None, None, 0))   # trials
            return jax.vmap(per_rate, in_axes=(None, 0, 0))(     # rates
                enc_tree, rates_v, keys_v)
    else:
        def grid(enc_tree, rates_v, keys_v):
            flat_r = jnp.repeat(rates_v, trials)
            flat_k = keys_v.reshape((n_rates * trials,) + keys_v.shape[2:])

            def step(carry, rk):
                r, k = rk
                return carry, cell(enc_tree, r, k)

            _, out = jax.lax.scan(step, (), (flat_r, flat_k))
            return out.reshape(n_rates, trials)

    rates_arr = jnp.asarray(rates, jnp.float32)
    keys = jax.random.split(key, max(n_rates * trials, 1))
    keys = keys[: n_rates * trials].reshape((n_rates, trials) + keys.shape[1:])

    t0 = time.perf_counter()
    compiled = jax.jit(grid).lower(enc, rates_arr, keys).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = np.asarray(jax.block_until_ready(compiled(enc, rates_arr, keys)))
    wall = time.perf_counter() - t0

    dev = jax.devices()[0]
    be = getattr(backend, "name", str(backend))
    return CampaignResult(
        scheme=_scheme_label(enc), metric=metric, rates=rates, trials=trials,
        clean=clean, grid=tuple(tuple(float(v) for v in row) for row in out),
        space_overhead=float(space_overhead(enc)), compile_s=compile_s,
        wall_clock_s=wall, batch=batch, backend=be, platform=dev.platform,
        device=getattr(dev, "device_kind", dev.platform))


def _as_policy(policy) -> ProtectionPolicy:
    if isinstance(policy, ProtectionPolicy):
        return policy
    return ProtectionPolicy(default_scheme=policy,
                            predicate=lambda p, l: getattr(l, "ndim", 0) >= 2)


def _default_eval(fwd, tmpl, *, n_classes, img, eval_batch, eval_seed):
    from repro.data import synthetic
    b, _ = synthetic.image_batch(n_classes, eval_batch, img, seed=eval_seed,
                                 step=0, templates=tmpl)
    return accuracy_eval(fwd, b)


def run_campaign(params, fwd, tmpl, policy, rates=RATES, trials=5, key=None,
                 batch="vmap", *, eval_fn=None, eval_batch=256, n_classes=4,
                 img=32, eval_seed=777) -> CampaignResult:
    """Encode once, then sweep the full (trial x rate) fault grid on device.

    params:  fp32 parameter tree (encoded here under ``policy``).
    fwd:     ``fwd(decoded_params, images) -> logits`` (pass any input
             normalization inside); ignored when ``eval_fn`` is given.
    tmpl:    synthetic-data class templates for the eval batch (None draws
             fresh ones from ``eval_seed``); ignored when ``eval_fn`` given.
    policy:  a ``ProtectionPolicy`` or a scheme id (which gets the paper's
             eval policy: every >=2-D tensor protected).
    batch:   "vmap" (parallel grid, fastest) or "scan" (sequential,
             constant memory).
    eval_fn: optional ``(decoded_tree) -> scalar`` metric override.

    Returns a :class:`CampaignResult`; exactly one jit compile happens.
    """
    policy = _as_policy(policy)
    key = jax.random.PRNGKey(0) if key is None else key
    enc = policy.encode_tree(params)
    if eval_fn is None:
        eval_fn = _default_eval(fwd, tmpl, n_classes=n_classes, img=img,
                                eval_batch=eval_batch, eval_seed=eval_seed)
        metric = "accuracy"
    else:
        metric = "custom"
    return _run_grid(enc, eval_fn, rates, trials, key, batch, policy.backend,
                     metric)


def fidelity_campaign(tree, policy=None, rates=(1e-4,), trials=2, key=None,
                      batch="vmap") -> CampaignResult:
    """Label-free campaign: metric = decode fidelity vs the clean decode.

    ``tree`` may be raw fp32 params (encoded here under ``policy``) or an
    already-encoded tree (``policy`` then only supplies the backend).  This
    is the serving fault smoke-check: it answers "at rate r, what fraction
    of my resident weights still decode correctly?" without needing labels.
    """
    policy = _as_policy(policy if policy is not None else "in-place")
    key = jax.random.PRNGKey(0) if key is None else key
    enc = tree if _is_encoded(tree) else policy.encode_tree(tree)
    eval_fn = fidelity_eval(enc, backend=policy.backend)
    res = _run_grid(enc, eval_fn, rates, trials, key, batch, policy.backend,
                    "fidelity")
    return res


def due_campaign(tree, policy=None, rates=(1e-4,), trials=2, key=None,
                 batch="vmap", *, what="due", target="weights",
                 kv_tree=None) -> CampaignResult:
    """Fault-accounting campaign: metric = total detected-uncorrectable
    (double-error, DUE) count across protected leaves per cell — the same
    per-leaf flags the decode-at-use serve step reports per layer, swept
    over the (rate x trial) grid in one compiled program.  At the paper's
    fault model the in-place (64,57,1) code corrects all singles, so the DUE
    curve is exactly the residual risk curve; ``what="corrected"`` sweeps
    the repair counts instead.

    ``target`` picks what the faults hit: "weights" (default, ``tree``),
    "kv" (a paged KV cache's ProtectedTensor pools — build ``kv_tree`` with
    :func:`repro.serving.kvcache.as_protected_tree`), or "both" (one grid
    over the combined state).  When the target covers KV, the result also
    carries ``layer_rows``: per-layer (corrected, DUE) counts from one
    representative injection at ``max(rates)`` — the serving-state analogue
    of the per-layer weight flags."""
    if target not in ("weights", "kv", "both"):
        raise ValueError(f"target {target!r}; one of "
                         f"('weights', 'kv', 'both')")
    if target != "weights" and kv_tree is None:
        raise ValueError(f"target={target!r} needs kv_tree (see "
                         f"repro.serving.kvcache.as_protected_tree)")
    policy = _as_policy(policy if policy is not None else "in-place")
    key = jax.random.PRNGKey(0) if key is None else key
    if target == "kv":
        enc = kv_tree
    else:
        wtree = tree if _is_encoded(tree) else policy.encode_tree(tree)
        enc = wtree if target == "weights" else {"weights": wtree,
                                                 "kv": kv_tree}
    ev = due_eval(backend=policy.backend, what=what)
    res = _run_grid(enc, ev, rates, trials, key, batch, policy.backend,
                    f"{what}_count")
    res = dataclasses.replace(res, target=target)
    if target != "weights":
        from repro.serving import kvcache  # deferred: serving builds on us
        dirty = inject_tree_device(kv_tree, max(rates), key,
                                   max_rate=max(rates))
        rows = np.asarray(kvcache.tree_layer_flags(
            dirty, backend=getattr(policy.backend, "name", policy.backend)))
        res = dataclasses.replace(
            res, layer_rows=tuple(tuple(int(v) for v in r) for r in rows))
    return res


def compute_campaign(tree, policy=None, rates=(1e-3,), trials=2, key=None,
                     batch="vmap", *, target="acc", probe_m=8,
                     probe_seed=777) -> CampaignResult:
    """COMPUTE-fault campaign: how much silent data corruption in the
    matmuls themselves does the in-kernel ABFT check catch?

    Memory campaigns (:func:`due_campaign`) flip bits in the stored image
    and let ECC account for them. This one flips bits in the *arithmetic* —
    the fault classes ECC cannot see and the fused kernel's checksum pair
    (``ecc_qmatmul(..., with_abft=True)``) exists for. Per protected >=2-D
    leaf, a fixed int8 probe activation drives the leaf's exact int32
    accumulator (``quant.int8_acc`` — the same accumulator the requantize
    epilogue checks); each (rate, trial) cell then

    * ``target="acc"``: XORs a random bit (position 0..30) into each
      accumulator element selected by a Bernoulli(rate) mask — MXU/
      datapath SDCs; a fault is DETECTED when its row or column checksum
      fires;
    * ``target="wdec"``: flips a random bit of each selected decoded-weight
      byte *in the main dot only* (the checksum references keep the clean
      tile, exactly the kernel situation where the MXU reads a corrupted
      operand) — detected when the fault's column check or any affected
      row's check fires.

    The fault rate is traced and the whole (rate x trial) grid runs as ONE
    compiled program, like every other campaign here. Returns a
    :class:`CampaignResult` with ``metric="abft_coverage"``: ``grid`` cells
    are detected/injected coverage fractions, ``clean`` is the total number
    of checksum firings at rate 0 (the false-positive count — 0 by
    construction: the int8 path compares int32 modular sums bit-exactly),
    and ``coverage_rows`` carries per-leaf (path, detected, injected)
    counts from one representative injection at ``max(rates)``.
    """
    if target not in ("acc", "wdec"):
        raise ValueError(f"target {target!r}; one of ('acc', 'wdec')")
    if batch not in ("vmap", "scan"):
        raise ValueError(f"batch must be 'vmap' or 'scan', got {batch!r}")
    from repro.core import quant
    from repro.kernels import ref as kref
    policy = _as_policy(policy if policy is not None else "in-place")
    key = jax.random.PRNGKey(0) if key is None else key
    enc = tree if _is_encoded(tree) else policy.encode_tree(tree)
    rates = tuple(float(r) for r in rates)
    n_rates = len(rates)

    # stage per-leaf (probe, int8 weights) once — the campaign operands
    flat = jax.tree_util.tree_flatten_with_path(
        enc, is_leaf=is_protected_tensor)[0]
    paths, probes = [], []
    pk = jax.random.PRNGKey(probe_seed)
    for path, leaf in flat:
        if not (is_protected_tensor(leaf) and len(leaf.orig_shape) == 2):
            continue
        w = decode_leaf(leaf, jnp.float32, backend=policy.backend)
        w_q, _ = quant.quantize(w)
        pk, sub = jax.random.split(pk)
        x_q = jax.random.randint(sub, (probe_m, w.shape[0]), -127, 128,
                                 jnp.int32).astype(jnp.int8)
        paths.append(path_str(path))
        probes.append((x_q, w_q))
    if not probes:
        raise ValueError("compute_campaign: no protected >=2-D leaves "
                         "(did the policy's predicate select anything?)")

    def leaf_counts(x_q, w_q, rate, k):
        """-> (detected, injected, fired) int32 for one leaf/cell."""
        acc = quant.int8_acc(x_q, w_q)
        k1, k2 = jax.random.split(k)
        if target == "acc":
            mask = jax.random.bernoulli(k1, rate, acc.shape)
            bit = jnp.int32(1) << jax.random.randint(k2, acc.shape, 0, 31)
            faulty = jnp.where(mask, acc ^ bit, acc)
            row_bad, col_bad = kref.abft_counts(x_q, w_q, faulty)
            hit = jnp.logical_or(row_bad[:, None] > 0, col_bad[None, :] > 0)
            det = jnp.sum(jnp.logical_and(mask, hit).astype(jnp.int32))
        else:  # wdec: corrupt the dot's operand, checksums keep the clean w
            mask = jax.random.bernoulli(k1, rate, w_q.shape)
            bit = (jnp.uint8(1) << jax.random.randint(
                k2, w_q.shape, 0, 8, jnp.uint8))
            w_f = jnp.where(
                mask,
                jax.lax.bitcast_convert_type(
                    jax.lax.bitcast_convert_type(w_q, jnp.uint8) ^ bit,
                    jnp.int8),
                w_q)
            faulty = quant.int8_acc(x_q, w_f)
            row_bad, col_bad = kref.abft_counts(x_q, w_q, faulty)
            # fault at (k0, j): the rows it perturbs are those with
            # x[:, k0] != 0; detected when one of them fires, or column j
            rdet = jnp.any(jnp.logical_and(row_bad[:, None] > 0, x_q != 0),
                           axis=0)                                     # (K,)
            hit = jnp.logical_or(rdet[:, None], col_bad[None, :] > 0)
            det = jnp.sum(jnp.logical_and(mask, hit).astype(jnp.int32))
        inj = jnp.sum(mask.astype(jnp.int32))
        fired = jnp.sum(row_bad) + jnp.sum(col_bad)
        return det, inj, fired

    def cell(rate, k):
        det = inj = fired = jnp.int32(0)
        for idx, (x_q, w_q) in enumerate(probes):
            d, i, f = leaf_counts(x_q, w_q, rate, jax.random.fold_in(k, idx))
            det, inj, fired = det + d, inj + i, fired + f
        return jnp.stack([det, inj, fired])

    if batch == "vmap":
        def grid_fn(rates_v, keys_v):
            per_rate = jax.vmap(cell, in_axes=(None, 0))
            return jax.vmap(per_rate, in_axes=(0, 0))(rates_v, keys_v)
    else:
        def grid_fn(rates_v, keys_v):
            flat_r = jnp.repeat(rates_v, trials)
            flat_k = keys_v.reshape((n_rates * trials,) + keys_v.shape[2:])

            def step(carry, rk):
                return carry, cell(*rk)

            _, out = jax.lax.scan(step, (), (flat_r, flat_k))
            return out.reshape(n_rates, trials, 3)

    rates_arr = jnp.asarray(rates, jnp.float32)
    keys = jax.random.split(key, max(n_rates * trials, 1))
    keys = keys[: n_rates * trials].reshape((n_rates, trials) + keys.shape[1:])

    t0 = time.perf_counter()
    compiled = jax.jit(grid_fn).lower(rates_arr, keys).compile()
    compile_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = np.asarray(jax.block_until_ready(compiled(rates_arr, keys)))
    wall = time.perf_counter() - t0

    # rate-0 cell: every checksum firing would be a false positive
    clean = float(np.asarray(jax.jit(cell)(
        jnp.float32(0.0), jax.random.fold_in(key, 2**31)))[2])
    # per-leaf attribution at max(rates), one representative key
    rows = []
    rk = jax.random.fold_in(key, 2**31 + 1)
    for idx, ((x_q, w_q), p) in enumerate(zip(probes, paths)):
        d, i, _ = jax.jit(leaf_counts)(x_q, w_q, jnp.float32(max(rates)),
                                       jax.random.fold_in(rk, idx))
        rows.append((p, int(d), int(i)))

    # a cell that drew no injection let nothing escape: coverage 1, not 0
    grid = tuple(tuple(float(out[r, t, 0]) / float(out[r, t, 1])
                       if out[r, t, 1] else 1.0
                       for t in range(trials)) for r in range(n_rates))
    dev = jax.devices()[0]
    return CampaignResult(
        scheme=_scheme_label(enc), metric="abft_coverage", rates=rates,
        trials=trials, clean=clean, grid=grid,
        space_overhead=float(space_overhead(enc)), compile_s=compile_s,
        wall_clock_s=wall, batch=batch,
        backend=getattr(policy.backend, "name", str(policy.backend)),
        platform=dev.platform,
        device=getattr(dev, "device_kind", dev.platform),
        target="compute", coverage_rows=tuple(rows))


def run_campaign_host(params, fwd, tmpl, policy, rates=RATES, trials=5,
                      seed=0, *, eval_fn=None, eval_batch=256, n_classes=4,
                      img=32, eval_seed=777) -> CampaignResult:
    """The cross-check oracle: the identical grid through the host path
    (``protection.inject_tree`` NumPy injection, one eager round-trip per
    cell).  Slow by construction; campaign<->host statistical parity on the
    same grid is asserted in the test suite."""
    policy = _as_policy(policy)
    enc = policy.encode_tree(params)
    if eval_fn is None:
        eval_fn = _default_eval(fwd, tmpl, n_classes=n_classes, img=img,
                                eval_batch=eval_batch, eval_seed=eval_seed)
        metric = "accuracy"
    else:
        metric = "custom"
    rates = tuple(float(r) for r in rates)
    clean = float(eval_fn(decode_tree(enc, jnp.float32,
                                      backend=policy.backend)))
    t0 = time.perf_counter()
    grid = []
    for ri, rate in enumerate(rates):
        row = []
        for t in range(trials):
            dirty = inject_tree(enc, rate, seed + 1000 * t + ri) if rate \
                else enc
            dec = decode_tree(dirty, jnp.float32, backend=policy.backend)
            row.append(float(eval_fn(dec)))
        grid.append(tuple(row))
    wall = time.perf_counter() - t0
    dev = jax.devices()[0]
    return CampaignResult(
        scheme=_scheme_label(enc), metric=metric, rates=rates, trials=trials,
        clean=clean, grid=tuple(grid),
        space_overhead=float(space_overhead(enc)), compile_s=0.0,
        wall_clock_s=wall, batch="host",
        backend=getattr(policy.backend, "name", "xla"),
        platform=dev.platform,
        device=getattr(dev, "device_kind", dev.platform))
