"""Training driver.

On real hardware this runs under the production mesh; on this container it
runs reduced (smoke) configs on the host devices.  Supports three schemes:

    standard  plain data/tensor-parallel LM training of the selected arch
    inl       the paper's in-network learning split of the same arch
              (J encoder nodes + fusion decoder, eq.-6 loss)

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 50 --batch 8 --seq 128 [--scheme inl] [--ckpt-dir ckpts]
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint, compile_cache, optim
from repro.configs import get_config, get_smoke_config
from repro.core import inl_llm
from repro.data import prefetch
from repro.data import tokens as token_data
from repro.launch import steps as steps_lib


def make_optimizer(lr: float, steps: int):
    """The driver's AdamW: warmup over the first tenth of `steps`, cosine
    decay after, decoupled weight decay and global-norm clipping."""
    return optim.adamw(
        optim.warmup_cosine_schedule(lr, max(steps // 10, 1), steps),
        weight_decay=0.1, clip_norm=1.0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced config (CPU-sized)")
    ap.add_argument("--scheme", default="standard",
                    choices=["standard", "inl"])
    ap.add_argument("--learned-prior", action="store_true",
                    help="inl scheme: learned per-node Gaussian priors "
                         "Q_psi_j in the eq.-(6) rate (fused kernel path)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--scan-steps", type=int, default=10,
                    help="optimizer steps per jitted lax.scan call (donated "
                         "params/opt_state buffers; 1 = step-per-dispatch)")
    ap.add_argument("--prefetch", type=int, default=2,
                    help="stacked scan groups kept in flight host->device "
                         "(data/prefetch.py); 1 disables the overlap")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true",
                    help="restore the latest --ckpt-dir checkpoint (params "
                         "AND optimizer state) and fast-forward the "
                         "data/rng streams, finishing the schedule "
                         "bit-identically to an uninterrupted run "
                         "(repro/chaos.py SIGKILLs + asserts it)")
    ap.add_argument("--log-every", type=int, default=10,
                    help="(superseded: metrics are logged once per scan "
                         "group, i.e. every --scan-steps steps)")
    args = ap.parse_args(argv)
    compile_cache.enable()

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    import dataclasses
    cfg = dataclasses.replace(cfg, dtype="float32") if args.smoke else cfg

    key = jax.random.PRNGKey(args.seed)
    optimizer = make_optimizer(args.lr, args.steps)

    if args.scheme == "inl":
        from repro.models import transformer
        # the INL split needs >= encoder_layers + 1 periods; smoke configs
        # have exactly one — grow the reduced model by one period
        pat = transformer.block_pattern(cfg)
        need = (cfg.inl.encoder_layers + 1) * len(pat) \
            + cfg.moe.first_dense_layers
        if cfg.num_layers < need:
            cfg = dataclasses.replace(cfg, num_layers=need)
        if args.learned_prior:
            cfg = dataclasses.replace(
                cfg, inl=dataclasses.replace(cfg.inl, learned_prior=True))
        params = inl_llm.init(cfg, key)
        opt_state = optimizer.init(params)
    else:
        from repro.models import zoo
        params = zoo.init_params(cfg, key)
        opt_state = optimizer.init(params)
    epoch_fn = steps_lib.make_scan_train_step(
        cfg, optimizer, scheme=args.scheme, microbatches=args.microbatches)

    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} scheme={args.scheme} params={n_params:,} "
          f"devices={jax.device_count()}")

    data = token_data.lm_batches(cfg, args.batch, args.seq, steps=args.steps,
                                 seed=args.seed)
    rng = jax.random.PRNGKey(args.seed + 1)
    K = max(args.scan_steps, 1)
    step = 0
    if args.resume and args.ckpt_dir:
        latest = checkpoint.latest_step(args.ckpt_dir)
        if latest is not None:
            restored, _ = checkpoint.restore(
                args.ckpt_dir, {"params": params, "opt": opt_state},
                step=latest)
            params, opt_state = restored["params"], restored["opt"]
            step = latest
            # fast-forward the streams through the completed work: the data
            # generator is deterministic per (cfg, seed), and the inl rng
            # splits once per scan group — replaying both makes the resumed
            # subkeys (and so the trajectory) the uninterrupted run's
            for _ in range(step):
                next(data)
            if args.scheme == "inl":
                for _ in range((step + K - 1) // K):
                    rng, _ = jax.random.split(rng)
            print(f"resumed from step {step} ({args.ckpt_dir})")
    t0 = time.time()
    history = []

    def run_group(params, opt_state, rng, batches, k):
        # one jitted scan over the group: K optimizer steps, zero
        # per-step dispatch, donated params/opt_state; `batches` arrives
        # stacked AND device-resident from the prefetcher
        nonlocal step
        if args.scheme == "inl":
            rng, sub = jax.random.split(rng)
            rngs = jax.random.split(sub, k)
            params, opt_state, ms = epoch_fn(params, opt_state, batches,
                                             rngs)
        else:
            params, opt_state, ms = epoch_fn(params, opt_state, batches)
        prev_step, step = step, step + k
        last = jax.tree.map(lambda x: x[-1], ms)
        m = {k: float(v) for k, v in last.items() if jnp.ndim(v) == 0}
        m["step"] = step - 1
        m["wall_s"] = round(time.time() - t0, 1)
        history.append(m)
        print(json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                          for k, v in m.items()}), flush=True)
        # checkpoint when the group crossed a --ckpt-every boundary (step
        # advances by the group size, so an exact-multiple test would skip)
        if args.ckpt_dir and args.ckpt_every and \
                step // args.ckpt_every > prev_step // args.ckpt_every:
            checkpoint.save(args.ckpt_dir, step,
                            {"params": params, "opt": opt_state},
                            extra={"arch": cfg.name, "scheme": args.scheme})
        return params, opt_state, rng

    # the scan now crosses the data-loading boundary: groups are stacked
    # host-side and device_put by the double-buffered prefetcher, so the
    # transfer of group g+1 overlaps the scan executing group g
    stacked = (steps_lib.stack_batches(g)
               for g in steps_lib.grouped_batches(data, K))
    for batches in prefetch.prefetch_to_device(stacked,
                                               size=max(args.prefetch, 1)):
        k = jax.tree.leaves(batches)[0].shape[0]
        params, opt_state, rng = run_group(params, opt_state, rng, batches,
                                           k)
    if args.ckpt_dir:
        checkpoint.save(args.ckpt_dir, args.steps,
                        {"params": params, "opt": opt_state},
                        extra={"arch": cfg.name, "scheme": args.scheme})
    if history:
        first, last = history[0], history[-1]
        key_metric = "loss" if "loss" in last else "ce"
        print(f"loss {first[key_metric]:.4f} -> {last[key_metric]:.4f} "
              f"({args.steps} steps, {time.time()-t0:.1f}s)")
    return history


if __name__ == "__main__":
    main()
