"""Training driver.

On real hardware this runs under the production mesh; on this container it
runs reduced (smoke) configs on the host devices.  Supports three schemes:

    standard  plain data/tensor-parallel LM training of the selected arch
    inl       the paper's in-network learning split of the same arch
              (J encoder nodes + fusion decoder, eq.-6 loss)

Usage:
  PYTHONPATH=src python -m repro.launch.train --arch llama3.2-1b --smoke \
      --steps 50 --batch 8 --seq 128 [--scheme inl] [--ckpt-dir ckpts]

`main` is `setup`, then `run_group` on each scan group `device_groups`
yields, then `finish`; other callers (the chip benchmark's driver) run the
same three with a configuration of their own.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time
from typing import Any, Callable, Iterator, List

import jax
import jax.numpy as jnp
import numpy as np

from repro import checkpoint, compile_cache, optim, tracing
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


def parse_args(argv=None) -> argparse.Namespace:
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
    return ap.parse_args(argv)


def model_config(args):
    """--arch's configuration as the CLI trains it: the reduced one in
    float32 with --smoke; for the inl scheme grown to enough periods for
    the split, with learned priors on --learned-prior."""
    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    cfg = dataclasses.replace(cfg, dtype="float32") if args.smoke else cfg
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
    return cfg


@dataclasses.dataclass
class Trainer:
    """What the training loop carries from one scan group to the next."""
    args: argparse.Namespace
    cfg: Any
    params: Any
    opt_state: Any
    epoch_fn: Callable      # the jitted scan of --scan-steps steps
    data: Iterator          # the token stream, one batch per step
    rng: Any                # inl: split once per scan group
    step: int = 0           # optimizer steps done
    t0: float = 0.0
    history: List[dict] = dataclasses.field(default_factory=list)

    @property
    def group_size(self) -> int:
        return max(self.args.scan_steps, 1)


def group_keys(rng, k: int):
    """(next rng, the k per-step keys of one inl scan group)."""
    rng, sub = jax.random.split(rng)
    return rng, jax.random.split(sub, k)


def setup(args, cfg=None) -> Trainer:
    """Parameters, optimizer state, the jitted scan, the token stream and
    the rng for `args`; with --resume, restored from the latest checkpoint
    with the streams fast-forwarded past the completed steps.  `cfg`
    replaces `model_config(args)`."""
    cfg = model_config(args) if cfg is None else cfg
    key = jax.random.PRNGKey(args.seed)
    optimizer = make_optimizer(args.lr, args.steps)

    if args.scheme == "inl":
        init = inl_llm.init
    else:
        from repro.models import zoo
        init = zoo.init_params
    # one program each (op by op, a large model's init compiles hundreds)
    params = jax.jit(init, static_argnums=0)(cfg, key)
    opt_state = jax.jit(optimizer.init)(params)
    epoch_fn = steps_lib.make_scan_train_step(
        cfg, optimizer, scheme=args.scheme, microbatches=args.microbatches)

    n_params = sum(x.size for x in jax.tree.leaves(params))
    print(f"arch={cfg.name} scheme={args.scheme} params={n_params:,} "
          f"devices={jax.device_count()}")

    data = token_data.lm_batches(cfg, args.batch, args.seq, steps=args.steps,
                                 seed=args.seed)
    tr = Trainer(args, cfg, params, opt_state, epoch_fn, data,
                 jax.random.PRNGKey(args.seed + 1))
    if args.resume and args.ckpt_dir:
        latest = checkpoint.latest_step(args.ckpt_dir)
        if latest is not None:
            restored, _ = checkpoint.restore(
                args.ckpt_dir, {"params": params, "opt": opt_state},
                step=latest)
            tr.params, tr.opt_state = restored["params"], restored["opt"]
            tr.step = latest
            # fast-forward the streams through the completed work: the data
            # generator is deterministic per (cfg, seed), and the inl rng
            # splits once per scan group — replaying both makes the resumed
            # subkeys (and so the trajectory) the uninterrupted run's
            for _ in range(latest):
                next(data)
            if args.scheme == "inl":
                for _ in range((latest + tr.group_size - 1)
                               // tr.group_size):
                    tr.rng, _ = jax.random.split(tr.rng)
            print(f"resumed from step {latest} ({args.ckpt_dir})")
    tr.t0 = time.time()
    return tr


def stacked_groups(tr: Trainer) -> Iterator:
    """The token stream in scan groups stacked (K, ...) on the host; run on
    the prefetcher's producer thread."""
    for group in steps_lib.grouped_batches(tr.data, tr.group_size):
        with tracing.span("train.stack") as sp:
            item = steps_lib.stack_batches(group)
            sp.set_metadata(bytes=prefetch.nbytes(item))
        yield item


def device_groups(tr: Trainer) -> Iterator:
    """The scan groups, device-resident: the scan crosses the data-loading
    boundary, as groups are stacked host-side and device_put by the
    double-buffered prefetcher, so the transfer of group g+1 overlaps the
    scan executing group g."""
    return prefetch.prefetch_to_device(stacked_groups(tr),
                                       size=max(tr.args.prefetch, 1))


def run_group(tr: Trainer, batches):
    """One jitted scan over the group: K optimizer steps, zero per-step
    dispatch, donated params/opt_state; `batches` arrives stacked AND
    device-resident from the prefetcher.  Logs the group's last step and
    checkpoints when the group crossed a --ckpt-every boundary; returns the
    stacked per-step metrics."""
    args = tr.args
    k = jax.tree.leaves(batches)[0].shape[0]
    with tracing.span("train.group", steps=k,
                      tokens=int(np.prod(batches["labels"].shape))):
        if args.scheme == "inl":
            tr.rng, rngs = group_keys(tr.rng, k)
            tr.params, tr.opt_state, ms = tr.epoch_fn(
                tr.params, tr.opt_state, batches, rngs)
        else:
            tr.params, tr.opt_state, ms = tr.epoch_fn(
                tr.params, tr.opt_state, batches)
        # the last step's scalar metrics; a per-step array (a recurrence
        # record) stays on the device, unsliced
        m = {k: float(v[-1]) for k, v in ms.items() if jnp.ndim(v) == 1}
    prev_step, tr.step = tr.step, tr.step + k
    m["step"] = tr.step - 1
    m["wall_s"] = round(time.time() - tr.t0, 1)
    tr.history.append(m)
    print(json.dumps({k: (round(v, 4) if isinstance(v, float) else v)
                      for k, v in m.items()}), flush=True)
    # checkpoint when the group crossed a --ckpt-every boundary (step
    # advances by the group size, so an exact-multiple test would skip)
    if args.ckpt_dir and args.ckpt_every and \
            tr.step // args.ckpt_every > prev_step // args.ckpt_every:
        checkpoint.save(args.ckpt_dir, tr.step,
                        {"params": tr.params, "opt": tr.opt_state},
                        extra={"arch": tr.cfg.name, "scheme": args.scheme})
    return ms


def finish(tr: Trainer) -> List[dict]:
    """The final checkpoint and the loss summary; returns the history."""
    args = tr.args
    if args.ckpt_dir:
        checkpoint.save(args.ckpt_dir, args.steps,
                        {"params": tr.params, "opt": tr.opt_state},
                        extra={"arch": tr.cfg.name, "scheme": args.scheme})
    if tr.history:
        first, last = tr.history[0], tr.history[-1]
        key_metric = "loss" if "loss" in last else "ce"
        print(f"loss {first[key_metric]:.4f} -> {last[key_metric]:.4f} "
              f"({args.steps} steps, {time.time()-tr.t0:.1f}s)")
    return tr.history


def main(argv=None):
    args = parse_args(argv)
    compile_cache.enable()
    tr = setup(args)
    for batches in device_groups(tr):
        run_group(tr, batches)
    return finish(tr)


if __name__ == "__main__":
    main()
