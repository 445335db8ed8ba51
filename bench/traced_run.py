"""Run one cell once with the profiler on, as `bench/run.py --trace 1`
does, and also read the program's own spans and named scopes from the
same trace (bench/program_trace.py).

    python3 bench/traced_run.py --workload <cell> --seed <n> --seconds <s> \
        [--events-out <file.json>]

The last line of standard output is bench/run.py's result line with one
more key, `program`: the values of PROGRAM_METRICS (bench/metrics/, read
from `run.program`) and the facts beside them (`facts`).  `--events-out`
writes a short record of the trace's program events
(`program_trace.save_slice`).

The epoch program's ops are given their scopes from its compiled HLO:
the first call of `Scheme.make_epoch`'s program is kept with its
arguments' shapes, and once the run is over that program is compiled
again for the same shapes (a hit in JAX's compilation cache) to read its
instructions' `op_name`.
"""
from __future__ import annotations

import argparse
import contextlib
import json
import math
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, program_trace, registry  # noqa: E402

PROGRAM_METRICS = (
    "prefetch_wait_share.paper_train", "assemble_s_per_epoch.paper_train",
    "put_s_per_epoch.paper_train", "idle_in_assemble_share.paper_train",
    "idle_in_put_share.paper_train", "encoder_us_per_round.paper_train",
    "fusion_node_us_per_round.paper_train",
    "optimizer_us_per_round.paper_train")


class EpochProgram:
    """Keeps the first epoch program `Scheme.make_epoch` builds while
    installed, and the shapes of its first call's arguments."""

    def __init__(self):
        self.fn = None
        self.args = None

    @contextlib.contextmanager
    def installed(self):
        import jax
        from repro.core.schemes import base
        make_epoch = base.Scheme.make_epoch

        def shape(x):
            return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                        sharding=getattr(x, "sharding",
                                                         None))

        def kept(scheme, *a, **kw):
            fn = make_epoch(scheme, *a, **kw)

            def epoch(*args):
                if self.fn is None:
                    self.fn, self.args = fn, jax.tree.map(shape, args)
                return fn(*args)
            return epoch
        base.Scheme.make_epoch = kept
        try:
            yield self
        finally:
            base.Scheme.make_epoch = make_epoch

    def hlo_text(self) -> str:
        return self.fn.lower(*self.args).compile().as_text()


def facts(p: program_trace.ProgramReduced, out) -> dict:
    """The numbers read beside the metrics: the epoch program's name, the
    cut and unscoped device time per round, each program's device time per
    epoch, and the threads the spans ran on."""
    rounds = out.facts.get("rounds_traced") or 0
    epochs = out.facts.get("epochs_in_window") or 0
    f = {"epoch_module": p.module}
    if rounds and p.scope_s:
        for key, scope in (("cut_scope", "cut"), ("unscoped", "")):
            f[key + "_us_per_round"] = 1e6 * p.scope_s.get(scope, 0.0) / rounds
        f["unnamed_us_per_round"] = 1e6 * p.unnamed_s / rounds
    if epochs and p.module_s:
        for mod, s in sorted(p.module_s.items()):
            f[f"device_ms_per_epoch.{mod or 'none'}"] = 1e3 * s / epochs
        f["eval_device_ms_per_epoch"] = 1e3 * sum(
            s for mod, s in p.module_s.items() if "predict" in mod) / epochs
    scoped = sum(s for k, s in p.scope_s.items() if k)
    if scoped:
        f["scoped_share_of_epoch_module"] = 100.0 * scoped / sum(
            p.scope_s.values())
    for name in ("repro.runner.assemble", "repro.prefetch.put",
                 "repro.prefetch.wait"):
        f[f"threads.{name}"] = p.threads_of(name)
        f[f"count.{name}"] = len(p.durations_s(name))
    return f


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="Run one cell once, traced, with the program's own "
                    "spans and scopes read from the trace.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--events-out", default=None,
                    help="write a short record of the program's events "
                         "to this JSON file")
    return ap.parse_args(argv)


def main(argv=None, *, root: str = registry.ROOT, require_chip: bool = True,
         t_start: float = None) -> int:
    args = parse_args(argv)
    try:
        run, driver = harness.prepare(
            harness.parse_args(["--workload", args.workload, "--seed",
                                str(args.seed), "--seconds",
                                str(args.seconds), "--trace", "1"]),
            root=root, require_chip=require_chip,
            t_start=T_START if t_start is None else t_start)
    except harness.NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return harness.EXIT_NO_CHIP
    loaded = []
    trace_reduce = run.trace_reduce

    def read_then_reduce():
        # the harness deletes the trace once it has reduced it
        run.trace_stop()
        if run._trace_dir is not None and not loaded:
            loaded.append(program_trace.load(run._trace_dir))
        trace_reduce()
    run.trace_reduce = read_then_reduce
    program = EpochProgram()
    with program.installed():
        out = driver.run(run)
    events = loaded[0] if loaded else []
    module = ""
    if program.fn is not None:
        hlo = program.hlo_text()
        module = program_trace.op_names(hlo)[0]
        events = program_trace.name_scopes(events, hlo)
    run.program = program_trace.reduce(
        events, window_s=run.window[1] - run.window[0], module=module,
        num_devices=len(run.devices))
    if args.events_out:
        program_trace.save_slice(events, args.events_out)
    result = harness.report(run, out)
    values = {}
    for name in PROGRAM_METRICS:
        v = run.reg.metric(name).read(run, out)
        if v is not None and math.isfinite(v):
            values[name] = float(v)
    result["program"] = {"metrics": values,
                         "facts": facts(run.program, out)}
    if run.window_compiles is not None:
        harness.say(f"compiles in the window: {run.window_compiles[0]}")
    harness.say("end-to-end (host clock, traced): " + ", ".join(
        f"{k}={v!r}" for k, v in [("setup_s", run.setup_s),
                                  *out.metrics.items()]))
    harness.say("facts: " + ", ".join(f"{k}={v!r}"
                                      for k, v in out.facts.items()))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
