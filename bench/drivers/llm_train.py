"""Training of a language model's INL split through the launcher users call:
`launch/train.py`'s `setup`, `device_groups` and `run_group` (the functions
`python -m repro.launch.train --scheme inl` runs), with the configuration
file's model in place of --arch's, one jitted lax.scan of `scan_steps`
optimizer steps per group fed by the device prefetcher.

Set-up makes the weights (the launcher's own init, from the seed) and the
token stream (`data/tokens.lm_batches`, from the seed), and runs groups
until the host pipeline is steady; the first group compiles, and its two
per-step losses, the state after it and its step 0's record are what the
reference follows.  `program_config` sets `ssm.record_mlstm`, so each
step of the timed program also returns the J cut means and node 0's first
mLSTM block's recurrence (its q, k, v, gate pre-activations and h).
The window opens at the first later group whose input had to be waited for
(0.1 s or more), or once the groups the prefetcher can hold ready are used;
it closes at the first group boundary after `--seconds`.  An example is a
trained token: a position with a label.

With --trace 1 the profiler records TRACED_GROUPS groups run right after
the window, so that the window's host clock holds no profiler work; the
traced metrics are of those groups.

Once the window has closed the program's state is dropped, and the
reference (the configuration's `reference`) computes group 0 from the
seed-made weights, tokens and step keys: the J encoders' cut means at step
0, the losses of steps 0 and 1 (step 1 follows the first AdamW update),
and the parameters' change after the group.  Node 0's first mLSTM
recurrence at step 0, as the timed program ran it, is held against the
reference's parallel form on the q, k, v and gates the program recorded.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import json
import re
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import flops_llm, hooks, program_trace
from bench.harness import Check, Outcome

# the launcher's seed (its rng is PRNGKey(seed + 1), its tokens
# default_rng(seed))
PROGRAM_SEED_MOD = 2 ** 30
# a pull from the prefetcher that takes this long found its buffer empty
STEADY_WAIT_S = 0.1
# scopes of the recurrences (models/ssm.py), as an op_name path component
# of a forward, backward or recomputed op
RECURRENCE = re.compile(
    r"^(?:(?:jvp|transpose|vmap|remat|checkpoint)\()*(?:mlstm|slstm)\)*$")
# a traced run records this many groups after the window: a group is about
# 1.5 million device ops, the profiler keeps only part of a whole window's,
# and stopping it takes about a minute per 3 million
TRACED_GROUPS = 1
# ops whose device time is that of the ops they run
ENCLOSING = ("while", "call", "conditional")
_OPCODE = re.compile(r"(?:^|[\s)}\]])([a-z][\w.-]*)\(")


def program_config(conf: dict):
    """The configuration file's model as the launcher's ModelConfig."""
    from repro.configs import get_config
    if conf["precision"]["matmul_precision"] != "default":
        raise ValueError("the program runs its products at the default "
                         "precision only")
    m, inl = conf["mlstm"], conf["inl"]
    if conf["num_layers"] // len(conf["block_pattern"]) \
            <= inl["encoder_periods"]:
        raise ValueError("the configuration leaves no period for the "
                         "fusion node")
    base = get_config(conf["arch"])
    return dataclasses.replace(
        base, num_layers=conf["num_layers"], d_model=conf["d_model"],
        num_heads=conf["num_heads"], num_kv_heads=conf["num_heads"],
        vocab_size=conf["vocab_size"], norm_eps=conf["norm_eps"],
        block_pattern=tuple(conf["block_pattern"]),
        dtype=conf["precision"]["params"],
        ssm=dataclasses.replace(base.ssm, expand=m["proj_factor"],
                                conv_width=m["conv_width"],
                                chunk_size=m["chunk_size"],
                                record_mlstm=True),
        inl=dataclasses.replace(
            base.inl, num_nodes=inl["num_nodes"],
            encoder_layers=inl["encoder_periods"],
            d_bottleneck=inl["d_bottleneck"], s=inl["s"],
            link_bits=inl["link_bits"], learned_prior=False))


def check_widths(conf: dict, params) -> None:
    """The configuration file's widths against the program's weights."""
    enc = params.encoders["stack"]["pattern"]
    ml, sl = enc[0]["mlstm"], enc[conf["block_pattern"].index("slstm")]
    got = {"mlstm.inner_dim": ml["wq"]["w"].shape[-1],
           "mlstm.head_dim": ml["wq"]["w"].shape[-1] // conf["num_heads"],
           "slstm.head_dim": sl["slstm"]["r"].shape[-2],
           "slstm.ffn_dim": sl["slstm"]["ffn"]["wi"]["w"].shape[-1],
           "inl.d_bottleneck": params.encoders["head"]["mu"]["w"].shape[-1]}
    want = {k: conf[k.split(".")[0]][k.split(".")[1]] for k in got}
    if got != want:
        raise ValueError(f"the program's widths {got} are not the "
                         f"configuration's {want}")


def program_args(conf: dict, tr: dict, seed: int):
    from repro.launch import train
    return train.parse_args([
        "--arch", conf["arch"], "--scheme", "inl",
        "--steps", str(tr["schedule_steps"]), "--batch", str(tr["batch"]),
        "--seq", str(tr["seq"]), "--lr", str(tr["lr"]),
        "--scan-steps", str(tr["scan_steps"]),
        "--prefetch", str(tr["prefetch"]), "--seed", str(seed)])


def _shape(x):
    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                sharding=getattr(x, "sharding", None))


def as_dict(params) -> dict:
    """The launcher's INLLLMParams as the reference's dict of its fields
    (the leaves then come in the same order on both sides)."""
    return params._asdict()


@jax.jit
def change_norms(master, p0):
    """Norm of each leaf's change from p0 (in its dtype) to the float32
    master weights."""
    return [jnp.sqrt(jnp.sum(jnp.square(m - p.astype(jnp.float32))))
            for m, p in zip(jax.tree.leaves(master), jax.tree.leaves(p0))]


def gap(prog, ref) -> float:
    """Relative L2 distance of the program's array from the reference's."""
    prog, ref = (np.asarray(jax.device_get(x), np.float64)
                 for x in (prog, ref))
    return float(np.linalg.norm(prog - ref) / np.linalg.norm(ref))


def recurrence_program(hlo_text: str) -> dict:
    """Of a compiled program: its module name, the instructions that run
    under the recurrences' scopes, and those whose time is their
    children's (`while`, `call`, `conditional`)."""
    module, names = program_trace.op_names(hlo_text)
    enclosing = set()
    for line in hlo_text.splitlines():
        hit = program_trace._INSTRUCTION.match(line)
        if hit:
            op = _OPCODE.search(hit.group(2))
            if op and op.group(1) in ENCLOSING:
                enclosing.add(hit.group(1))
    recurrent = {i for i, name in names.items()
                 if any(RECURRENCE.match(part) for part in name.split("/"))}
    return {"module": module, "recurrent": recurrent - enclosing,
            "enclosing": enclosing}


def recurrence_seconds(reduced, program: dict) -> float:
    """Device seconds of the leaf ops under the recurrences' scopes."""
    return sum(t for name, t in reduced.ops_s.items()
               if program_trace.instruction(name) in program["recurrent"])


class Window:
    """When the window opens and closes around the launcher's groups, and
    which groups after it the profiler records."""

    def __init__(self, run, most_ready: int):
        self.run, self.most_ready = run, most_ready
        self.t_open = None
        self.open_group = self.close_group = None
        self.traced = [None, None]    # perf_counter s

    def steady(self, i: int) -> bool:
        waits = [b - a for n, a, b in self.run.spans
                 if n == "bench.input_wait"]
        return i > self.most_ready or (bool(waits)
                                       and waits[-1] >= STEADY_WAIT_S)

    def before(self, i: int) -> bool:
        """At the start of group i: opens the window when due, closes it
        at the first group boundary after `--seconds`, then (with --trace
        1) records TRACED_GROUPS more groups; returns False when the loop
        is done."""
        run = self.run
        if self.t_open is None:
            if i >= 1 and self.steady(i):
                self.t_open = run.window_open()
                self.open_group = i
        elif self.close_group is None:
            if time.perf_counter() - self.t_open >= run.seconds:
                run.window_close()
                self.close_group = i
                if not run.trace:
                    return False
                run.trace_start()
                self.traced[0] = time.perf_counter()
        elif i - self.close_group == TRACED_GROUPS:
            self.traced[1] = time.perf_counter()
            run.trace_stop()
            return False
        return True

    @property
    def open(self) -> bool:
        return self.t_open is not None and self.close_group is None

    @property
    def groups(self) -> int:
        return self.close_group - self.open_group


def start(conf: dict, tr: dict, seed: int):
    """The launcher's set-up for the cell: (trainer, the initial weights on
    the host, the device-resident scan groups)."""
    from repro.launch import train
    trainer = train.setup(program_args(conf, tr, seed),
                          cfg=program_config(conf))
    check_widths(conf, trainer.params)
    return trainer, jax.device_get(trainer.params), \
        train.device_groups(trainer)


def first_group(trainer, batches, p0) -> dict:
    """Group 0 through `run_group`, with what the reference follows: its
    batches and step keys, its per-step losses, step 0's record (the cut
    means and node 0's first mLSTM recurrence), each leaf's change, and
    the shapes of the train program's arguments."""
    from repro.launch import train
    got = {"batches0": jax.device_get(batches), "rng0": trainer.rng,
           "p0": p0, "epoch_fn": trainer.epoch_fn,
           "shapes": jax.tree.map(_shape, (
               trainer.params, trainer.opt_state, batches,
               train.group_keys(trainer.rng, trainer.group_size)[1]))}
    ms = train.run_group(trainer, batches)
    got["losses0"] = np.asarray(jax.device_get(ms["loss"]))
    got["record0"] = jax.device_get({
        k.split(".", 1)[1]: v[0] for k, v in ms.items()
        if k.startswith("record.")})
    del ms
    got["prog_norms"] = [float(x) for x in change_norms(
        as_dict(trainer.opt_state["master"]), as_dict(p0))]
    return got


def train_in_window(run, conf: dict, tr: dict, seed: int) -> dict:
    """The launcher's loop from set-up to the window's close; returns what
    the comparison and the facts need."""
    from repro.launch import train
    t0 = time.perf_counter()
    trainer, p0, groups = start(conf, tr, seed)
    setup_fn_s = time.perf_counter() - t0
    window = Window(run, most_ready=tr["prefetch"] + 1)
    losses = []
    i = 0
    try:
        while True:
            with run.span("bench.input_wait"):
                batches = next(groups, None)
            if batches is None:
                raise RuntimeError("the token stream ended inside the "
                                   "window; raise schedule_steps")
            if not window.before(i):
                break
            with run.span("bench.epoch"):
                if i == 0:
                    t0 = time.perf_counter()
                    got = first_group(trainer, batches, p0)
                    got["first_group_s"] = time.perf_counter() - t0
                else:
                    ms = train.run_group(trainer, batches)
            if i and window.open:
                losses.append(ms["loss"])
            i += 1
    finally:
        groups.close()
    trainer.params = trainer.opt_state = None
    got["window_losses"] = np.concatenate(
        [np.asarray(x).reshape(-1) for x in losses]) if losses \
        else np.zeros((0,))
    got.update(groups=window.groups,
               groups_traced=TRACED_GROUPS if run.trace else 0,
               traced_s=window.traced, setup_fn_s=setup_fn_s)
    return got


def compare(ref_mod, conf: dict, tr: dict, got: dict) -> dict:
    """Group 0 of the program against the reference; returns the readings
    by name (the traffic's `limits` say which are compared)."""
    from repro.launch import train
    rec = got["record0"]
    b0 = got["batches0"]
    step0 = {k: v[0] for k, v in b0.items()}
    step1 = {k: v[1] for k, v in b0.items()}
    _, keys = train.group_keys(got["rng0"], b0["labels"].shape[0])
    # the state's products in float32, as the configuration states it
    h_ref = jax.jit(lambda r: ref_mod.mlstm_parallel(
        r["q"], r["k"], r["v"], r["i"], r["f"],
        prec=ref_mod.Spec.of(conf).state_prec))(rec)
    recurrence_gap = gap(rec["h"], h_ref)
    del h_ref
    p0 = jax.device_put(got["p0"])
    params0 = as_dict(p0)
    opt = tr["optimizer"]
    loss0, mu_ref, g0 = ref_mod.loss_and_grad(params0, conf, step0, keys[0])
    mu_gap = gap(rec["mu"], mu_ref)
    del mu_ref

    @jax.jit
    def first_update(p, g):
        return ref_mod.adamw(opt, g, ref_mod.first_state(p), 1)[0]

    @jax.jit
    def norms_after_two(p, g0, g1):
        _, st = ref_mod.adamw(opt, g0, ref_mod.first_state(p), 1)
        _, st = ref_mod.adamw(opt, g1, st, 2)
        return [jnp.sqrt(jnp.sum(jnp.square(m - w.astype(jnp.float32))))
                for m, w in zip(jax.tree.leaves(st["master"]),
                                jax.tree.leaves(p))]
    p1 = first_update(params0, g0)
    loss1, _, g1 = ref_mod.loss_and_grad(p1, conf, step1, keys[1])
    del p1
    g0_norms = [float(x) for x in jax.jit(lambda g: [
        jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
        for x in jax.tree.leaves(g)])(g0)]
    ref_norms = [float(x) for x in norms_after_two(params0, g0, g1)]
    del g0, g1, params0, p0
    keep = hooks.kept_leaves(g0_norms)
    gaps = hooks.leaf_gaps(
        [n for n, k in zip(got["prog_norms"], keep) if k],
        [n for n, k in zip(ref_norms, keep) if k])
    ref_losses = np.array([float(loss0), float(loss1)])
    rel = np.abs(got["losses0"].astype(np.float64) - ref_losses) \
        / np.abs(ref_losses)
    return {
        "mu_gap.step0": mu_gap, "recurrence_gap.step0": recurrence_gap,
        "loss_gap.step0": float(rel[0]),
        "loss_gap.first2": float(rel.max()),
        "param_change_gap.group0": float(max(gaps)),
        "param_change_gap_median.group0": float(np.median(gaps)),
        "leaves_kept": int(sum(keep)), "leaves": len(keep)}


def run(run) -> Outcome:
    conf, tr = run.config, run.traffic
    ref_mod = run.reg.reference(conf["reference"])
    seed = run.seed % PROGRAM_SEED_MOD
    with (state_in_bf16() if run.control else contextlib.nullcontext()):
        got = train_in_window(run, conf, tr, seed)
        hooks.join_prefetchers()
        gc.collect()
        t0, t1 = run.window
        window_s = t1 - t0
        run.trace_reduce()
        traced = {}
        if run.reduced is not None:
            a, b = got["traced_s"]
            run.reduced.window_s = b - a
            # v5e op events carry no op_name: the scopes come from the
            # compiled train program (a hit in the compilation cache)
            program = recurrence_program(
                got["epoch_fn"].lower(*got["shapes"]).compile().as_text())
            traced["recurrence_s_traced"] = recurrence_seconds(run.reduced,
                                                               program)
            if run.events_out:
                # beside the trace's slice, what names its ops
                with open(run.events_out + ".scopes.json", "w") as f:
                    json.dump({k: sorted(v) if isinstance(v, set) else v
                               for k, v in program.items()}, f)
        readings = compare(ref_mod, conf, tr, got)
    steps = got["groups"] * tr["scan_steps"]
    tokens = steps * tr["batch"] * tr["seq"]
    checks = [Check(k, readings[k], lim) for k, lim in tr["limits"].items()]
    facts = {
        "tokens_per_s": tokens / window_s, "window_s": window_s,
        "groups_in_window": got["groups"], "steps_in_window": steps,
        "input_wait_s": run.span_seconds("bench.input_wait", t0, t1),
        "train_flops_per_token": flops_llm.train_flops_per_token(conf),
        "steps_traced": got["groups_traced"] * tr["scan_steps"],
        "setup_fn_s": got["setup_fn_s"],
        "first_group_s": got["first_group_s"], **traced,
        **{"reading." + k: v for k, v in readings.items()}}
    return Outcome(attempted=steps,
                   failed=int(np.sum(~np.isfinite(got["window_losses"]))),
                   metrics={"train_examples_per_s": tokens / window_s},
                   checks=checks, facts=facts)


def state_in_bf16():
    """The control: the mLSTM's matrix memory C and normaliser n carried
    from step to step in bfloat16 (models/ssm.py's `_mlstm_cell`)."""
    from repro.models import ssm

    def make(cell):
        def rounded(carry, qkvif):
            (C, n, m), h = cell(carry, qkvif)
            return (jax.lax.reduce_precision(C, 8, 7),
                    jax.lax.reduce_precision(n, 8, 7), m), h
        return rounded
    return hooks.patched(ssm, "_mlstm_cell", make)


# Faults this path can have (bench/faults.py plants one for a run):
# slstm_no_recurrence  the sLSTM's recurrent matrices read as zero
# state_unchanged      each optimizer step returns the state it was given
# rate_dropped         the cut layer's eq.-(6) rate reads as zero, so the
#                      loss leaves it out
FAULTS = ("slstm_no_recurrence", "state_unchanged", "rate_dropped")


def plant(fault: str):
    if fault == "rate_dropped":
        from repro.kernels import ops

        def make_cut(cutlayer):
            def no_rate(*args, **kw):
                u, rate = cutlayer(*args, **kw)
                return u, jnp.zeros_like(rate)
            return no_rate
        return hooks.patched(ops, "cutlayer", make_cut)
    if fault == "slstm_no_recurrence":
        from repro.models import ssm

        def make(cell):
            def no_recurrence(p_r, carry, x_gates, H, dh):
                return cell(jnp.zeros_like(p_r), carry, x_gates, H, dh)
            return no_recurrence
        return hooks.patched(ssm, "_slstm_cell", make)
    from repro.launch import steps

    def make_step(orig):
        def make_inl_train_step(cfg, optimizer):
            step = orig(cfg, optimizer)

            def unchanged(params, opt_state, batch, rng):
                _, _, metrics = step(params, opt_state, batch, rng)
                return params, opt_state, metrics
            return unchanged
        return make_inl_train_step
    return hooks.patched(steps, "make_inl_train_step", make_step)
