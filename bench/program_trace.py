"""The program's own spans and named scopes, read from a profiler trace.

`bench/trace.py` reads the benchmark's `bench.` spans and the device ops by
name.  This module reads what the program records itself, on the same
clock:

  spans     its host spans (`repro.` names, `repro/tracing.py`), with the
            thread they ran on and their stats;
  modules   the XLA program each device op ran in (the device's
            "XLA Modules" line);
  scopes    each op's `jax.named_scope` (SCOPES; the cut layer's kernels
            by their call, KERNEL_CALLS).  A TPU v5e trace carries no op
            metadata, so `name_scopes` looks each op of one program up in
            that program's compiled HLO text, whose instructions keep their
            `op_name`.

An event here is (plane, line, name, start_ns, duration_ns, extra): a host
span's extra is its stats, a device op's its "module" and, once named, its
"op_name".  A host line is named "<thread name>#<index in its plane>",
since the threads of one process share a name.  `reduce` sums from events
alone:

  spans     the `repro.` spans by thread;
  scope_s   leaf device time of one program's ops by scope: an op that
            encloses others on its line (the epoch's `while`) is not
            counted, its children are;
  module_s  leaf device time by program;
  idle_s    device 0's idle time (no op running, from the first event read
            to the last) split over the `repro.` spans that overlap it, by
            span name.
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import json
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

from bench.trace import DEVICE_PLANE, OP_LINE, union

SPAN_PREFIX = "repro."
BENCH_PREFIX = "bench."
MODULE_LINE = "XLA Modules"
SCOPES = ("encoder", "cut", "decoder", "loss", "optimizer")
# The cut layer's kernel calls run outside every scope, as a scope would
# rename their instructions (which the cut-layer reader finds by name);
# the jit of the call in their op_name marks them as the cut's.
KERNEL_CALLS = {"_cutlayer_call": "cut", "_cutlayer_prior_call": "cut"}
# one path component of an op_name that is a scope, as forward
# ("jvp(encoder)") or backward ("transpose(jvp(encoder))") op
_SCOPE_PART = re.compile(
    r"^(?:(?:jvp|transpose|vmap|remat|checkpoint)\()*"
    r"(?:(%s)|jit\((%s)\))\)*$" % ("|".join(SCOPES),
                                    "|".join(KERNEL_CALLS)))
_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+)\s*=\s(.*)$")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')

Event = Tuple[str, str, str, float, float, dict]


def instruction(op: str) -> str:
    """The instruction name of a device op event ("%fusion.59 = f32[...]
    fusion(...)" -> "fusion.59")."""
    return op.split(" = ", 1)[0].lstrip("%").strip()


def module_name(event_name: str) -> str:
    """"jit_epoch_fn(1468...)" -> "jit_epoch_fn"."""
    return event_name.split("(", 1)[0]


def _times(e) -> Tuple[float, float]:
    """A device event's start and duration in ns, from the picosecond
    stats where the trace has them: rounded to whole ns, one op could seem
    to outlast the start of the next and be taken for a parent."""
    st = {k: v for k, v in e.stats}
    if "device_offset_ps" in st and "device_duration_ps" in st:
        return (int(st["device_offset_ps"]) / 1e3,
                int(st["device_duration_ps"]) / 1e3)
    return float(e.start_ns), float(e.duration_ns)


def load(trace_dir: str) -> List[Event]:
    """The `repro.` and `bench.` host spans with their stats, and each
    device's ops and programs, from the `.xplane.pb` under `trace_dir`;
    each op is given the program whose run encloses its start."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events: List[Event] = []
    for path in paths:
        for plane in ProfileData.from_file(path).planes:
            if DEVICE_PLANE.match(plane.name):
                dev = [(plane.name, line.name,
                        module_name(e.name) if line.name == MODULE_LINE
                        else e.name) + _times(e) + ({},)
                       for line in plane.lines
                       if line.name in (OP_LINE, MODULE_LINE)
                       for e in line.events]
                events += with_modules(dev)
                continue
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    if e.name.startswith((SPAN_PREFIX, BENCH_PREFIX)):
                        events.append((plane.name, f"{line.name}#{i}",
                                       e.name, float(e.start_ns),
                                       float(e.duration_ns),
                                       {k: v for k, v in e.stats}))
    return events


def with_modules(events: Sequence[Event]) -> List[Event]:
    """One device's ops and program runs, each op given (in its extra's
    "module") the program whose run encloses its start."""
    runs = sorted((e[3], e[3] + e[4], e[2]) for e in events
                  if e[1] == MODULE_LINE)
    starts = [r[0] for r in runs]
    out = []
    for e in events:
        if e[1] == OP_LINE:
            k = bisect.bisect_right(starts, e[3]) - 1
            if k >= 0 and e[3] < runs[k][1]:
                e = e[:5] + ({**e[5], "module": runs[k][2]},)
        out.append(e)
    return out


def op_names(hlo_text: str) -> Tuple[str, Dict[str, str]]:
    """A compiled program's module name and each of its instructions'
    `op_name`, from its HLO text (`compiled.as_text()`).  An instruction
    the compiler added without one (an async copy or slice between memory
    spaces, and the wait for it) takes that of the nearest named
    instruction that reads what it made, else of the nearest that made
    what it reads: the op it stalls for, or the op whose result it
    moves."""
    m = re.match(r"HloModule\s+([^\s,]+)", hlo_text)
    names: Dict[str, str] = {}
    users: Dict[str, List[str]] = collections.defaultdict(list)
    operands: Dict[str, List[str]] = {}
    for line in hlo_text.splitlines():
        hit = _INSTRUCTION.match(line)
        if not hit:
            continue
        inst, rest = hit.groups()
        op = _OP_NAME.search(rest)
        if op:
            names[inst] = op.group(1)
        operands[inst] = re.findall(r"%([^\s,(){}=]+)", rest)
        for ref in operands[inst]:
            users[ref].append(inst)

    def nearest(inst, links):
        seen, frontier = {inst}, [inst]
        while frontier:
            nxt = []
            for i in frontier:
                for j in links.get(i, ()):
                    if j in names:
                        return names[j]
                    if j not in seen:
                        seen.add(j)
                        nxt.append(j)
            frontier = nxt
        return None
    for inst in [i for i in operands if i not in names]:
        found = nearest(inst, users) or nearest(inst, operands)
        if found is not None:
            names[inst] = found
    return (m.group(1) if m else ""), names


def name_scopes(events: Sequence[Event], hlo_text: str) -> List[Event]:
    """`events` with the `op_name` of each op of the program that
    `hlo_text` holds, where the program has that instruction."""
    module, names = op_names(hlo_text)
    out = []
    for e in events:
        if e[1] == OP_LINE and e[5].get("module") == module:
            name = names.get(instruction(e[2]))
            if name is not None:
                e = e[:5] + ({**e[5], "op_name": name},)
        out.append(e)
    return out


def scope_of(op_name: Optional[str]) -> str:
    """The named scope an op ran under ("" for none)."""
    for part in reversed((op_name or "").split("/")):
        m = _SCOPE_PART.match(part)
        if m:
            return m.group(1) or KERNEL_CALLS[m.group(2)]
    return ""


def device_of(e: Event) -> Optional[int]:
    """The device index of an op event; None for any other event."""
    m = DEVICE_PLANE.match(e[0])
    return int(m.group(1)) if m and e[1] == OP_LINE else None


def bench_events(events: Sequence[Event]) -> list:
    """The device ops and `bench.` spans, as `bench.trace.reduce` reads
    them."""
    return [e[:5] for e in events if device_of(e) is not None
            or e[2].startswith(BENCH_PREFIX)]


def idle_gaps(ops: Sequence[Event]) -> List[Tuple[float, float]]:
    """The intervals between one device's busy stretches, in order."""
    busy = union([(e[3], e[3] + e[4]) for e in ops])
    return [(a, b) for (_, a), (b, _) in zip(busy[:-1], busy[1:])]


def leaves(ops: Sequence[Event]) -> List[Event]:
    """The ops of one device line that enclose no other op."""
    ops = sorted(ops, key=lambda e: (e[3], -e[4]))
    return [e for e, nxt in zip(ops, ops[1:] + [None])
            if nxt is None or nxt[3] >= e[3] + e[4]]


@dataclasses.dataclass
class ProgramReduced:
    window_s: float
    spans: Dict[str, List[Tuple[str, float, float, dict]]]  # by thread
    scope_s: Dict[str, float]     # of `module`'s leaf ops; "" no scope
    module_s: Dict[str, float]    # leaf device time by program
    idle_s: Dict[str, float]      # device 0's idle time by `repro.` span;
                                  # empty without device 0's ops
    module: str = ""
    unnamed_s: float = 0.0        # of `module`'s leaf ops with no op_name

    def durations_s(self, name: str) -> List[float]:
        """Seconds of each span called `name`, in order of start."""
        return [(b - a) * 1e-9 for a, b in sorted(
            (a, b) for spans in self.spans.values()
            for n, a, b, _ in spans if n == name)]

    def threads_of(self, name: str) -> List[str]:
        return sorted(t for t, spans in self.spans.items()
                      if any(n == name for n, *_ in spans))

    # what the per-layer readers (bench/metrics/) report; None where the
    # trace holds nothing to read, as for a program without the spans

    def mean_s(self, name: str) -> Optional[float]:
        """Mean seconds of the spans called `name`."""
        spans = self.durations_s(name)
        return sum(spans) / len(spans) if spans else None

    def window_share(self, name: str) -> Optional[float]:
        """% of the window inside the spans called `name`."""
        spans = self.durations_s(name)
        if not spans or self.window_s <= 0:
            return None
        return 100.0 * sum(spans) / self.window_s

    def idle_share(self, name: str, epochs: int) -> Optional[float]:
        """% of the window device 0 was idle inside spans called `name`,
        one of which runs per epoch: the mean over the recorded spans
        times `epochs`, as the profiler drops a span begun before it
        started."""
        spans = len(self.durations_s(name))
        if not spans or not epochs or name not in self.idle_s \
                or self.window_s <= 0:
            return None
        return 100.0 * self.idle_s[name] / spans * epochs / self.window_s

    def us_per_round(self, scopes: Sequence[str],
                     rounds: int) -> Optional[float]:
        """Leaf device time of `module` under `scopes`, us per round."""
        spent = sum(self.scope_s.get(s, 0.0) for s in scopes)
        return 1e6 * spent / rounds if spent > 0 and rounds else None


def reduce(events: Sequence[Event], *, window_s: float, module: str = "",
           num_devices: int = 1) -> ProgramReduced:
    """`module` is the program whose ops `scope_s` splits by scope."""
    spans: Dict[str, list] = collections.defaultdict(list)
    ops: Dict[int, list] = collections.defaultdict(list)
    for e in events:
        dev = device_of(e)
        if dev is not None and dev < num_devices:
            ops[dev].append(e)
        elif dev is None and e[2].startswith(SPAN_PREFIX):
            spans[f"{e[0]}/{e[1]}"].append((e[2], e[3], e[3] + e[4], e[5]))
    scope_s: Dict[str, float] = collections.defaultdict(float)
    module_s: Dict[str, float] = collections.defaultdict(float)
    unnamed_s = 0.0
    for dev_ops in ops.values():
        for e in leaves(dev_ops):
            mod = e[5].get("module", "")
            module_s[mod] += e[4] * 1e-9
            if mod == module:
                if "op_name" not in e[5]:
                    unnamed_s += e[4] * 1e-9
                scope_s[scope_of(e[5].get("op_name"))] += e[4] * 1e-9
    by_name: Dict[str, list] = collections.defaultdict(list)
    for thread_spans in spans.values():
        for name, a, b, _ in thread_spans:
            by_name[name].append((a, b))
    idle_s = {}
    if ops.get(0):
        # idle: device 0 without an op, from the first event read to the
        # last, so a span that outlasts the last op keeps its idle tail
        busy = union([(e[3], e[3] + e[4]) for e in ops[0]])
        read = busy + [(a, b) for t in spans.values() for _, a, b, _ in t]
        edges = ([min(a for a, _ in read)] + [x for ab in busy for x in ab]
                 + [max(b for _, b in read)])
        idle = list(zip(edges[::2], edges[1::2]))
        for name, intervals in by_name.items():
            idle_s[name] = 1e-9 * sum(
                max(0.0, min(b, ib) - max(a, ia))
                for a, b in union(intervals) for ia, ib in idle)
    return ProgramReduced(window_s=window_s, spans=dict(spans),
                          scope_s=dict(scope_s), module_s=dict(module_s),
                          idle_s=idle_s, module=module,
                          unnamed_s=unnamed_s)


def save_slice(events: Sequence[Event], path: str, *,
               edge_ns: float = 6e6) -> None:
    """Write a short record of the trace as JSON: the longest idle gap of
    device 0 and the `edge_ns` before and after it (a round or so of the
    epoch program each side), with the device's ops and program runs and
    the host spans, cut to that stretch.  Recorded traces for tests are
    made this way."""
    gaps = idle_gaps([e for e in events if device_of(e) == 0])
    if not gaps:
        return
    ga, gb = max(gaps, key=lambda g: g[1] - g[0])
    lo, hi = ga - edge_ns, gb + edge_ns

    def near(s, d):
        return (s < ga and s + d > lo) or (s < hi and s + d > gb)
    keep = []
    for e in sorted(events, key=lambda e: e[3]):
        if DEVICE_PLANE.match(e[0]):
            if near(e[3], e[4]):
                keep.append(list(e))
        elif e[3] < hi and e[3] + e[4] > lo:
            a, b = max(e[3], lo), min(e[3] + e[4], hi)
            keep.append([e[0], e[1], e[2], a, b - a, e[5]])
    with open(path, "w") as f:
        json.dump({"gap_ns": [ga, gb], "edge_ns": edge_ns,
                   "events": keep}, f)


def read_slice(path: str) -> List[Event]:
    with open(path) as f:
        return [tuple(e[:5]) + (e[5],) for e in json.load(f)["events"]]
