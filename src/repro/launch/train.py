"""End-to-end training driver.

Trains an assigned arch at its published widths (``--smoke``: the reduced
same-family config, the CPU size) on synthetic data with the full
production stack — QAT + WOT throttling, SGD momentum, grad accumulation,
async ECC-protected checkpointing, resume after failure.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-4b --smoke \
      --steps 50
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.data import synthetic
from repro.models import lm
from repro.training import checkpoint, optim, train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-4b")
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--smoke", action="store_true",
                    help="train the reduced same-family config (CPU size) "
                         "instead of the published widths")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--no-wot", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    cfg = cfg.with_(microbatch=max(1, args.batch // 4))
    print(f"[train] {cfg.name} ({cfg.family}) layers={cfg.n_layers} "
          f"d={cfg.d_model} vocab={cfg.vocab_padded}")

    params = lm.init_params(cfg, jax.random.PRNGKey(args.seed))
    opt = optim.sgd_init(params)
    step0 = 0

    ckpt_mgr = None
    if args.ckpt:
        ckpt_mgr = checkpoint.AsyncCheckpointer(args.ckpt, protected=True)
        last = checkpoint.latest_step(args.ckpt)
        if last is not None:
            (params, opt), step0 = checkpoint.restore(args.ckpt, (params, opt))
            print(f"[train] resumed from step {step0}")

    step_fn = jax.jit(train.make_train_step(
        cfg, lr=args.lr, wot_throttle=not args.no_wot, chunk=64))

    extras = {}
    if cfg.family == "vlm":
        extras["prefix_embeds"] = jnp.zeros((args.batch, cfg.n_patches,
                                             cfg.d_model), jnp.bfloat16)
    if cfg.family == "encdec":
        extras["enc_embeds"] = jnp.asarray(np.random.default_rng(0).normal(
            size=(args.batch, cfg.enc_seq, cfg.d_model)), jnp.bfloat16)

    t0 = time.time()
    for step in range(step0, args.steps):
        batch = synthetic.token_batch(cfg.vocab_padded, args.batch, args.seq,
                                      seed=args.seed, step=step)
        batch = {**{k: jnp.asarray(v) for k, v in batch.items()}, **extras}
        params, opt, loss = step_fn(params, opt, batch)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"  step {step:4d} loss {float(loss):.4f} "
                  f"({time.time() - t0:.1f}s)")
        if ckpt_mgr and (step + 1) % args.ckpt_every == 0:
            ckpt_mgr.save((params, opt), step + 1)
    if ckpt_mgr:
        ckpt_mgr.save((params, opt), args.steps)
        ckpt_mgr.wait()
        print(f"[train] checkpointed to {args.ckpt}")
    return params


if __name__ == "__main__":
    main()
