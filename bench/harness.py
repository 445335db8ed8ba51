"""One run of one benchmark cell: what every driver shares.

`main` finds the cell's parts by name (bench/registry.py), refuses to run
without the chips the cell asks for, hands a `Run` to the cell's driver and
prints the result line.  A driver builds the program's objects, calls
`run.setup_done()` when every shape is warm, measures between
`run.window_open()` and `run.window_close()`, and returns an `Outcome`:
the end-to-end metrics it timed, the counts of attempted and failed work,
the numbers it compared with the reference (each with its limit) and the
facts the per-layer metric readers (bench/metrics/) take their numbers from.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import os
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional

from bench import registry

EXIT_NO_CHIP = 3


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared with the reference, and its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return math.isfinite(self.value) and self.value <= self.limit


@dataclasses.dataclass
class Outcome:
    attempted: int
    failed: int
    metrics: Dict[str, float]
    checks: List[Check]
    facts: dict = dataclasses.field(default_factory=dict)

    @property
    def correct(self) -> bool:
        return (self.attempted > 0 and self.failed == 0
                and all(c.ok for c in self.checks))


def load_peaks(kind: str, root: str = registry.ROOT) -> dict:
    """The published peaks of one chip, keyed by JAX's `device_kind`.  A
    device missing from the table is an error, never a default."""
    with open(os.path.join(root, "bench", "peaks.json")) as f:
        table = json.load(f)["devices"]
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in bench/peaks.json "
                       f"(known: {sorted(table)})")
    return table[kind]


def require_chips(n: int):
    """The devices a cell runs on: the first n TPU chips.  Raises NoChip
    on any other backend or with fewer chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX's first device is {devs[0].platform} "
                     f"({devs[0].device_kind}), not a TPU")
    if len(devs) < n:
        raise NoChip(f"the cell asks for {n} chips and JAX finds "
                     f"{len(devs)}")
    return devs[:n]


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, unless JAX_COMPILATION_CACHE_DIR names one.  Every program is
    kept, however fast it compiled, so a cell's second run compiles
    nothing."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        root, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class CompileCounter:
    """Counts XLA compilations and jaxpr traces through jax.monitoring, so
    a window can show that nothing compiled inside it."""

    _instance = None

    def __init__(self):
        self.compiles = 0
        self.traces = 0

    @classmethod
    def get(cls) -> "CompileCounter":
        if cls._instance is None:
            import jax
            cls._instance = inst = cls()

            def on_duration(name, _secs, **_kw):
                if name == "/jax/core/compile/backend_compile_duration":
                    inst.compiles += 1
                elif name == "/jax/core/compile/jaxpr_trace_duration":
                    inst.traces += 1
            jax.monitoring.register_event_duration_secs_listener(on_duration)
        return cls._instance

    def snapshot(self):
        return self.compiles, self.traces


def say(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class Run:
    """The state of one run, handed to the cell's driver."""

    def __init__(self, *, reg: registry.Registry, cell: dict, config: dict,
                 traffic: dict, seed: int, seconds: float, trace: bool,
                 control: bool, devices, peaks: Optional[dict],
                 t_start: float, events_out: Optional[str] = None):
        self.reg, self.cell, self.config, self.traffic = (reg, cell, config,
                                                          traffic)
        self.seed, self.seconds, self.trace = seed, seconds, trace
        self.control = control
        self.events_out = events_out
        self.devices, self.peaks = devices, peaks
        self.t_start = t_start
        self.setup_s: Optional[float] = None
        self.spans: List[tuple] = []         # (name, t0, t1) perf_counter s
        self.counter = CompileCounter.get()
        self.window: Optional[tuple] = None  # (t0, t1) perf_counter s
        self.window_compiles = None
        self.memory_peak_bytes = 0
        self.reduced = None                  # bench.trace.Reduced
        self._trace_dir = None
        self._trace_stopped = False
        self._trace_t = [None, None]          # traced part, perf_counter s
        self._t_open = None
        self._c_open = None

    # -- host spans -----------------------------------------------------------

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span around one call into a layer: recorded here on the
        host clock and, when tracing, in the profiler's trace as well."""
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            try:
                yield
            finally:
                self.spans.append((name, t0, time.perf_counter()))

    def span_seconds(self, name: str, t0: float, t1: float) -> float:
        """Seconds spent in spans called `name` within [t0, t1]."""
        return sum(max(0.0, min(b, t1) - max(a, t0))
                   for n, a, b in self.spans if n == name)

    # -- phases ---------------------------------------------------------------

    def setup_done(self) -> None:
        self.setup_s = time.perf_counter() - self.t_start

    def window_open(self) -> float:
        if self.setup_s is None:
            self.setup_done()
        self._c_open = self.counter.snapshot()
        self._t_open = time.perf_counter()
        return self._t_open

    def window_close(self) -> float:
        t1 = time.perf_counter()
        self.window = (self._t_open, t1)
        c1 = self.counter.snapshot()
        self.window_compiles = (c1[0] - self._c_open[0],
                                c1[1] - self._c_open[1])
        self.memory_peak_bytes = max(
            (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
            for d in self.devices)
        return t1

    # -- the profiler -----------------------------------------------------------

    def trace_start(self) -> None:
        """Start the profiler (a run with --trace 1 only) just before the
        window opens; `trace_stop` just after it closes.  The traced window
        is the measured one."""
        if not self.trace:
            return
        import jax
        self._trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self._trace_dir, profiler_options=opts)
        self._trace_t = [time.perf_counter(), None]

    def trace_stop(self) -> None:
        """Stop the profiler; the trace is read by `trace_reduce`, once the
        window has closed."""
        if self._trace_dir is None or self._trace_stopped:
            return
        import jax
        self._trace_t[1] = time.perf_counter()
        jax.profiler.stop_trace()
        self._trace_stopped = True

    def trace_reduce(self) -> None:
        if self._trace_dir is None:
            return
        from bench import trace as trace_lib
        self.trace_stop()
        try:
            events = trace_lib.load_xplane(self._trace_dir)
            if self.events_out:
                trace_lib.save_slice(events, self.events_out)
            self.reduced = trace_lib.reduce(
                events, num_devices=len(self.devices),
                window_s=(self.window[1] - self.window[0] if self.window
                          else self._trace_t[1] - self._trace_t[0]))
        finally:
            shutil.rmtree(self._trace_dir, ignore_errors=True)
            self._trace_dir = None


def report(run: Run, out: Outcome) -> dict:
    """The result line, and the compared numbers as the last lines of
    standard error."""
    reg, name = run.reg, run.cell["name"]
    metrics = {}
    if run.trace:
        for m in reg.per_layer(name):
            v = reg.metric(m["name"]).read(run, out)
            if v is not None and math.isfinite(v):
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in reg.end_to_end(name):
            v = run.setup_s if m["name"] == "setup_s" else \
                out.metrics[m["name"]]
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    d0 = run.devices[0]
    device = {"platform": d0.platform, "kind": d0.device_kind,
              "count": len(run.devices),
              "memory_peak_bytes": int(run.memory_peak_bytes)}
    result = {"correct": bool(out.correct), "attempted": int(out.attempted),
              "failed": int(out.failed), "metrics": metrics,
              "device": device}
    if run.trace and run.reduced is not None:
        device["busy_s"] = run.reduced.busy_s
        device["window_s"] = run.reduced.window_s
        result["breakdown"] = run.reduced.breakdown()
    result["checks"] = {c.name: {"value": float(c.value),
                                 "limit": float(c.limit)}
                        for c in out.checks}
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="Run one cell of BENCHMARK.json once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="run the cell's control in the program's place "
                         "(its compared numbers must fail their limits)")
    ap.add_argument("--events-out", default=None,
                    help="with --trace 1: also write a short slice of the "
                         "trace's events (around the first cut-layer "
                         "kernel) to this JSON file")
    return ap.parse_args(argv)


def prepare(args, *, root: str = registry.ROOT, require_chip: bool = True,
            t_start: Optional[float] = None):
    """The cell's parts found by name, the chips checked, the compile cache
    on: returns (Run, driver module).  Raises NoChip."""
    t_start = time.perf_counter() if t_start is None else t_start
    reg = registry.Registry(root)
    cell = reg.cell(args.workload)
    config = reg.config(cell["config"])
    traffic = reg.traffic(cell["traffic"])
    driver = reg.driver(traffic["driver"])
    import jax
    if require_chip:
        devices = require_chips(int(cell["chips"]))
        peaks = load_peaks(devices[0].device_kind, root)
    else:
        devices, peaks = jax.devices()[:int(cell["chips"])], None
    d0 = devices[0]
    say(f"device platform={d0.platform} kind={d0.device_kind} "
        f"count={len(devices)} jax={jax.__version__}")
    if require_chip:
        say(f"compile cache {enable_compile_cache(root)}")
    run = Run(reg=reg, cell=cell, config=config, traffic=traffic,
              seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
              control=bool(args.control), devices=devices, peaks=peaks,
              t_start=t_start, events_out=args.events_out)
    return run, driver


def main(argv=None, *, root: str = registry.ROOT, require_chip: bool = True,
         t_start: Optional[float] = None) -> int:
    """Run one cell; returns the exit code.  `require_chip=False` skips the
    look for a TPU and the peaks table (tests drive the rest of a run on
    the CPU that way)."""
    args = parse_args(argv)
    try:
        run, driver = prepare(args, root=root, require_chip=require_chip,
                              t_start=t_start)
    except NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return EXIT_NO_CHIP
    out = driver.run(run)
    if run.window_compiles is not None:
        say(f"compiles in the window: {run.window_compiles[0]} (jaxpr "
            f"traces {run.window_compiles[1]})")
    say("end-to-end (host clock): " + ", ".join(
        f"{k}={v!r}" for k, v in [("setup_s", run.setup_s),
                                  *out.metrics.items()]))
    say("facts: " + ", ".join(f"{k}={v!r}" for k, v in out.facts.items()))
    result = report(run, out)
    for c in out.checks:
        print(f"check {c.name} = {c.value!r} (limit {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0
