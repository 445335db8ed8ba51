"""Training of the paper's model through the runner users call:
`core/schemes/runner.run_scheme(dispatch="scan")`, one jitted lax.scan per
epoch fed by the host gather and the device prefetcher, with the runner's
per-epoch metering and evaluation inside the window.

Set-up makes the data and the weights from the seed, starts run_scheme
and lets its first epoch call compile and run; that call's outputs (the
per-round losses and the state after its K rounds) are what the reference
follows.  The window opens once the host pipeline is in its steady state:
at the first later epoch call whose input the runner had to wait for (the
prefetcher's buffer has drained), or once the most epochs the prefetcher
can hold ready have been used, whichever comes first.  So a set-up that
took longer, as one that compiles does, leaves no epochs ready-made for
the window.  It closes at the first call that starts after `--seconds`:
every epoch inside it ran whole, with its host gather, transfer, metering
and evaluation.
"""
from __future__ import annotations

import contextlib
import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import data as data_lib
from bench import flops, hooks
from bench.hooks import WindowClosed
from bench.harness import Check, Outcome

# run_scheme's own seed (its round keys come from PRNGKey(seed + 1))
PROGRAM_SEED_MOD = 2 ** 30
# a pull from the prefetcher that takes this long found its buffer empty
# (a ready item is handed over in well under a millisecond)
STEADY_WAIT_S = 0.1


def program_config(conf: dict, *, bf16: bool):
    from repro.configs.paper_inl import PaperExperimentConfig
    return PaperExperimentConfig(
        num_clients=conf["num_clients"],
        noise_stds=tuple(conf["noise_stds"]),
        num_classes=conf["num_classes"],
        image_shape=tuple(conf["image_shape"]),
        conv_channels=tuple(conf["conv_channels"]),
        d_bottleneck=conf["d_bottleneck"],
        dense_units=tuple(conf["dense_units"]),
        s=conf["s"], link_bits=conf["link_bits"],
        compute_dtype="bf16" if bf16 else conf["compute_dtype"],
        dataset_size=conf["dataset_size"])


def program_params(p: dict):
    """The reference layout's weights as the program's INLParams."""
    from repro.core import inl
    enc = {"convs": p["conv"], "bns": p["bn"],
           "head": {"mu": p["mu"], "logvar": p["lv"]}}
    dec = {"dense": p["dense"], "branch_heads": p["branch"]}
    return inl.INLParams(enc, dec, {})


def reference_layout(params) -> dict:
    """The program's INLParams (or a tree shaped like them) in the
    reference layout."""
    enc, dec = params.encoders, params.decoder
    return {"conv": enc["convs"], "bn": enc["bns"],
            "mu": enc["head"]["mu"], "lv": enc["head"]["logvar"],
            "dense": dec["dense"], "branch": dec["branch_heads"]}


class ProgramHooks:
    """Spans and the window around run_scheme's calls into its layers,
    installed on the program's objects for the duration of one run."""

    def __init__(self, run, scheme, state0, most_ready: int):
        self.run, self.scheme, self.state0 = run, scheme, state0
        self.most_ready = most_ready  # epochs the prefetcher can hold ready
        self.calls = 0
        self.first = None    # epoch 0's (per-round losses, params)
        self.window_losses = []
        self.epochs_in_window = 0
        self.t_open = None
        self.open_call = None

    def _steady(self, i: int) -> bool:
        """Whether epoch call i finds the host pipeline in its steady state:
        its input had to be waited for, or every epoch that could have been
        made ready during set-up is used up."""
        waits = [b - a for n, a, b in self.run.spans
                 if n == "bench.input_wait"]
        return i > self.most_ready or (bool(waits)
                                       and waits[-1] >= STEADY_WAIT_S)

    def _init(self, cfg, key, *, lr=2e-3):
        from repro import optim
        st = type(self.scheme).init(self.scheme, cfg, key, lr=lr)
        # a copy: the epoch program donates the state it is given
        st["params"] = jax.tree.map(jnp.copy, self.state0)
        st["opt"] = optim.adam(lr).init(st["params"])
        return st

    def _make_epoch(self, *args, **kw):
        epoch_fn = type(self.scheme).make_epoch(self.scheme, *args, **kw)
        run = self.run

        def epoch(state, views, labels, rngs):
            i = self.calls
            self.calls += 1
            if self.t_open is None:
                if i >= 1 and self._steady(i):
                    run.trace_start()
                    self.t_open = run.window_open()
                    self.open_call = i
            elif time.perf_counter() - self.t_open >= run.seconds:
                self.epochs_in_window = i - self.open_call
                run.window_close()
                run.trace_stop()
                raise WindowClosed
            with run.span("bench.epoch"):
                state, metrics = epoch_fn(state, views, labels, rngs)
            if i == 0:
                self.first = jax.device_get((metrics["loss"],
                                             state["params"]))
            elif self.t_open is not None:
                self.window_losses.append(metrics["loss"])
            return state, metrics
        return epoch

    @contextlib.contextmanager
    def installed(self):
        from repro.core.schemes import base
        from repro.data import prefetch
        run = self.run
        prefetch_to_device = prefetch.prefetch_to_device
        evaluate_accuracy = base.evaluate_accuracy

        def timed_eval(*a, **kw):
            with run.span("bench.eval"):
                return evaluate_accuracy(*a, **kw)

        self.scheme.init = self._init
        self.scheme.make_epoch = self._make_epoch
        prefetch.prefetch_to_device = hooks.timed_prefetch(
            run, prefetch_to_device)
        base.evaluate_accuracy = timed_eval
        try:
            yield self
        finally:
            del self.scheme.init
            del self.scheme.make_epoch
            prefetch.prefetch_to_device = prefetch_to_device
            base.evaluate_accuracy = evaluate_accuracy


def epoch_batches(views, labels, K: int, B: int, epoch: int = 0):
    """One epoch's rounds in the runner's documented feed order (a fresh
    `default_rng(epoch)` permutation cut into full batches): views
    (K, J, B, ...), labels (K, B)."""
    n = labels.shape[0]
    perm = np.random.default_rng(epoch).permutation(n)[:K * B].reshape(K, B)
    return np.moveaxis(views[:, perm], 0, 1), labels[perm]


@functools.partial(jax.jit, static_argnums=1)
def _split_chain(k, K: int):
    def body(k, _):
        k, sub = jax.random.split(k)
        return k, sub
    return jax.lax.scan(body, k, None, length=K)[1]


def round_keys(seed: int, K: int):
    """The runner's round keys for its first epoch: the chain of K splits
    of PRNGKey(seed + 1)."""
    return _split_chain(jax.random.PRNGKey(seed + 1), K)


def compare(ref_mod, conf, tr, p0, views, labels, prog_seed, first):
    """Epoch 0's numbers against the reference following its K rounds:
    the relative gap of the first round's loss and the largest of the
    first three rounds', and after the K rounds the gap of the per-leaf
    norms of the parameters' change, by the worst leaf and by the median
    leaf.  Returns (readings by name, facts); the traffic's `limits` say
    which readings are compared."""
    K = conf["dataset_size"] // tr["batch_size"]
    vb, lb = epoch_batches(views, labels, K, tr["batch_size"])
    keys = round_keys(prog_seed, K)
    train = jax.jit(lambda p, v, y, k: ref_mod.train(
        p, v, y, k, conf, lr=tr["lr"], dtype=jnp.float32))
    losses, g0, pK = jax.device_get(
        train(p0, jnp.asarray(vb), jnp.asarray(lb), keys))
    del vb
    p_losses, p_params = first
    p0 = jax.device_get(p0)
    keep = hooks.kept_leaves([float(x) for x in jax.tree.leaves(g0)])

    def change_norms(params):
        return [hooks.leaf_norm(a - b) for a, b, k in zip(
            jax.tree.leaves(params), jax.tree.leaves(p0), keep) if k]
    gaps = hooks.leaf_gaps(change_norms(reference_layout(p_params)),
                           change_norms(pK))
    rel = np.abs(np.asarray(p_losses, np.float64) - losses) / np.abs(losses)
    readings = {"loss_gap.round0": float(rel[0]),
                "loss_gap.first3": float(rel[:3].max()),
                "param_change_gap.epoch0": float(max(gaps)),
                "param_change_gap_median.epoch0": float(np.median(gaps))}
    return readings, {"leaves_kept": int(sum(keep)), "leaves": len(keep)}


def run(run) -> Outcome:
    from repro.core import schemes
    from repro.core.schemes import runner
    conf, tr = run.config, run.traffic
    ref_mod = run.reg.reference(conf["reference"])
    cfg = program_config(conf, bf16=run.control)
    B, n = tr["batch_size"], conf["dataset_size"]
    K = n // B
    views_d, labels_d = data_lib.multiview(
        data_lib.key(run.seed, 1), n=n, num_classes=conf["num_classes"],
        image_shape=tuple(conf["image_shape"]),
        noise_stds=tuple(conf["noise_stds"]))
    views, labels = np.asarray(views_d), np.asarray(labels_d)
    del views_d, labels_d
    p0, _ = jax.jit(lambda k: ref_mod.init(conf, k))(
        data_lib.key(run.seed, 2))
    prog_seed = run.seed % PROGRAM_SEED_MOD
    scheme = schemes.get("inl")
    # ready at most: the prefetcher's queue and one made item waiting to
    # enter it
    program = ProgramHooks(run, scheme, program_params(p0),
                           most_ready=tr["prefetch"] + 1)
    with program.installed():
        try:
            runner.run_scheme(
                "inl", views, labels, cfg, epochs=10 ** 6, batch_size=B,
                lr=tr["lr"], seed=prog_seed, eval_n=tr["eval_n"],
                dispatch="scan", prefetch_size=tr["prefetch"],
                wire=tr["wire"])
        except WindowClosed:
            pass
    hooks.join_prefetchers()
    t0, t1 = run.window
    window_s = t1 - t0
    window_losses = np.concatenate(
        [np.asarray(x).reshape(-1) for x in program.window_losses]) \
        if program.window_losses else np.zeros((0,))
    run.trace_reduce()
    readings, leaf_facts = compare(ref_mod, conf, tr, p0, views, labels,
                                   prog_seed, program.first)
    checks = [Check(k, readings[k], lim) for k, lim in tr["limits"].items()]
    rounds = program.epochs_in_window * K
    examples = rounds * B
    facts = {
        "examples_per_s": examples / window_s, "window_s": window_s,
        "epochs_in_window": program.epochs_in_window,
        "input_wait_s": run.span_seconds("bench.input_wait", t0, t1),
        "train_flops_per_example": flops.paper_train_flops(conf),
        "cut_rows": conf["num_clients"] * B, "cut_d": conf["d_bottleneck"],
        "rounds_traced": rounds if run.trace else 0, **leaf_facts,
        **{"reading." + k: v for k, v in readings.items()}}
    return Outcome(attempted=rounds,
                   failed=int(np.sum(~np.isfinite(window_losses))),
                   metrics={"train_examples_per_s": examples / window_s},
                   checks=checks, facts=facts)


# Faults this path can have (bench/faults.py plants one for a run):
# state_unchanged  the round returns the state it was given
# half_batch       the round sees half its batch; the mean is taken over
#                  that half
FAULTS = ("state_unchanged", "half_batch")


def plant(fault: str):
    from repro.core.schemes.inl import INLScheme

    def make(orig):
        def make_round(self, cfg, **kw):
            round_fn = orig(self, cfg, **kw)

            def broken(state, views, labels, rng):
                if fault == "half_batch":
                    half = labels.shape[-1] // 2
                    return round_fn(state, views[:, :, :half],
                                    labels[:, :half], rng)
                _, metrics = round_fn(state, views, labels, rng)
                return state, metrics
            return broken
        return make_round
    return hooks.patched(INLScheme, "make_round", make)
