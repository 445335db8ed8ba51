"""Step functions: train / prefill / decode (+ INL paper-mode train), the
units the launcher jits, shards, and the dry-run lowers.

`make_scan_train_step` wraps K optimizer steps into one jitted
lax.scan with donated (params, opt_state) buffers — the launcher's epoch
unit; per-batch Python dispatch overhead amortises over K.  The scan now
extends across the data-loading boundary: `grouped_batches` +
`stack_batches` assemble the (K, ...) scan xs host-side and
`data/prefetch.prefetch_to_device` keeps >= 2 stacked groups in flight, so
the host->device transfer of group g+1 overlaps the scan executing group g
(see launch/train.py --prefetch).
"""
from __future__ import annotations

from typing import Iterable, Iterator, List, Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro import optim as optim_lib
from repro.core import inl_llm
from repro.models import zoo


def grouped_batches(data: Iterable, k: int) -> Iterator[List]:
    """Chunk a batch stream into lists of k (trailing partial group kept —
    the scan retraces once for it at most)."""
    group = []
    for batch in data:
        group.append(batch)
        if len(group) == k:
            yield group
            group = []
    if group:
        yield group


def stack_batches(group: List):
    """Stack a group of batch pytrees into the scan's (K, ...) xs on the
    HOST (numpy) — the device transfer belongs to the prefetcher, which
    overlaps it with compute."""
    return jax.tree.map(lambda *xs: np.stack(xs), *group)


def make_train_step(cfg, optimizer, *, microbatches: int = 1,
                    unroll: bool = False):
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    microbatches > 1 splits the global batch along axis 0 and accumulates
    fp32 gradients over a lax.scan — activation residency divides by the
    microbatch count while arithmetic is unchanged (gradient accumulation)."""
    def grad_fn(params, batch):
        return jax.value_and_grad(
            lambda p: zoo.loss_and_metrics(p, cfg, batch), has_aux=True)(
            params)

    if microbatches == 1:
        def train_step(params, opt_state, batch):
            (loss, metrics), grads = grad_fn(params, batch)
            new_params, new_opt = optimizer.update(grads, opt_state, params)
            metrics["grad_norm"] = optim_lib.global_norm(grads)
            return new_params, new_opt, metrics
        return train_step

    def train_step(params, opt_state, batch):
        B = jax.tree.leaves(batch)[0].shape[0]
        assert B % microbatches == 0, (B, microbatches)

        def split(x):
            return x.reshape((microbatches, B // microbatches) + x.shape[1:])
        mb = jax.tree.map(split, batch)

        def body(acc, one):
            (loss, metrics), grads = grad_fn(params, one)
            acc = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32), acc, grads)
            return acc, metrics
        zeros = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), params)
        if unroll:
            # inline accumulation loop: exact cost_analysis (a lax.scan body
            # is counted once), used by the dry-run's trade-off studies
            gsum = zeros
            mlist = []
            for i in range(microbatches):
                one = jax.tree.map(lambda x: x[i], mb)
                gsum, m = body(gsum, one)
                mlist.append(m)
            ms = jax.tree.map(lambda *t: jnp.stack(t), *mlist)
        else:
            gsum, ms = jax.lax.scan(body, zeros, mb)
        grads = jax.tree.map(lambda g: g / microbatches, gsum)
        metrics = jax.tree.map(lambda m: m.mean(axis=0), ms)
        new_params, new_opt = optimizer.update(grads, opt_state, params)
        metrics["grad_norm"] = optim_lib.global_norm(grads)
        return new_params, new_opt, metrics
    return train_step


def make_prefill_step(cfg):
    """(params, batch) -> (last_logits, cache)."""
    def prefill_step(params, batch):
        logits, cache, _ = zoo.forward(params, cfg, batch, mode="prefill",
                                       logits_positions="last")
        return logits[:, -1], cache
    return prefill_step


def make_decode_step(cfg, *, greedy: bool = False, trace_log: list = None):
    """(params, batch, cache) -> (logits, new_cache).  batch carries the new
    token(s) + cache_len; serve_step semantics per the assignment: ONE new
    token against a cache of seq_len entries.

    greedy=True returns the argmax TOKEN ids (B,) int32 instead of logits —
    the sampling folds into the jitted step, so a serving decode loop never
    dispatches an eager per-token argmax against the in-flight logits (the
    host round trip the old `serve.py` loop paid every generated token).
    Audio (multi-codebook) logits argmax per codebook and keep the first —
    the same flattening the serve loop applied host-side.

    trace_log — optional list appended to at TRACE time (not per call);
    tests assert the serving loop compiles this step exactly once."""
    def decode_step(params, batch, cache):
        if trace_log is not None:
            trace_log.append(jax.tree.map(jnp.shape, batch))
        logits, new_cache, _ = zoo.forward(params, cfg, batch, mode="decode",
                                           cache=cache)
        logits = logits[:, -1]
        if not greedy:
            return logits, new_cache
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        if tok.ndim > 1:                     # audio: (B, K) -> first codebook
            tok = tok[:, 0]
        return tok, new_cache
    return decode_step


def make_inl_train_step(cfg, optimizer):
    """The paper's scheme on this architecture (core/inl_llm)."""
    def inl_step(params, opt_state, batch, rng):
        (loss, metrics), grads = jax.value_and_grad(
            inl_llm.loss_fn, has_aux=True)(params, cfg, batch, rng)
        with jax.named_scope("optimizer"):
            new_params, new_opt = optimizer.update(grads, opt_state, params)
        return new_params, new_opt, metrics
    return inl_step


def make_scan_train_step(cfg, optimizer, *, scheme: str = "standard",
                         microbatches: int = 1, donate: bool = None):
    """K optimizer steps in ONE jitted `jax.lax.scan`, with the (params,
    opt_state) buffers donated — per-step Python dispatch and the
    params/opt_state copy at every update both disappear.

    standard scheme: (params, opt_state, batches) -> (params, opt_state,
    stacked metrics), where `batches` is the usual batch pytree with an
    extra leading K axis.  inl scheme additionally takes `rngs` (K, 2)
    PRNG keys, one per step.

    donate=None donates only on accelerators (CPU XLA cannot alias the
    buffers and would just warn)."""
    if donate is None:
        donate = jax.default_backend() != "cpu"
    donate_args = (0, 1) if donate else ()

    if scheme == "inl":
        inner = make_inl_train_step(cfg, optimizer)

        def epoch(params, opt_state, batches, rngs):
            def body(carry, x):
                batch, rng = x
                p, o, m = inner(carry[0], carry[1], batch, rng)
                return (p, o), m
            (p, o), ms = jax.lax.scan(body, (params, opt_state),
                                      (batches, rngs))
            return p, o, ms
    else:
        inner = make_train_step(cfg, optimizer, microbatches=microbatches)

        def epoch(params, opt_state, batches):
            def body(carry, batch):
                p, o, m = inner(carry[0], carry[1], batch)
                return (p, o), m
            (p, o), ms = jax.lax.scan(body, (params, opt_state), batches)
            return p, o, ms

    return jax.jit(epoch, donate_argnums=donate_args)


def default_optimizer(cfg, total_steps: int = 10_000):
    sched = optim_lib.warmup_cosine_schedule(3e-4, min(200, total_steps // 10 + 1),
                                             total_steps)
    return optim_lib.adamw(sched, weight_decay=0.1, clip_norm=1.0)
