"""From a profiler trace to the numbers the per-layer metrics read.

`load_xplane` flattens the `.xplane.pb` that `jax.profiler` writes into
plain events: (plane, line, name, start_ns, duration_ns).  Of the host it
keeps only the benchmark's own spans (names starting "bench."); of each
device, the op-level line.  `reduce` then computes, from events alone:

  busy      the union of the intervals in which an op ran, per device,
            averaged over the devices;
  ops       device time summed by op name (the top ones go into the
            result line's `breakdown`);
  gaps      every idle interval on the first device, attributed to the
            benchmark span that overlaps it most ("host:none" when none);
  collectives, kernels   device time of the ops whose names match.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Sequence, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OP_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all|"
    r"psum|ppermute", re.I)

Event = Tuple[str, str, str, float, float]   # plane, line, name, start, dur
# a device op's name is its whole HLO instruction, operands and all; the
# breakdown keeps its head (name, result type, opcode)
BREAKDOWN_NAME_CHARS = 160


def load_xplane(trace_dir: str) -> List[Event]:
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    events: List[Event] = []
    for path in paths:
        pd = ProfileData.from_file(path)
        for plane in pd.planes:
            device = DEVICE_PLANE.match(plane.name)
            for line in plane.lines:
                if device and line.name != OP_LINE:
                    continue
                for e in line.events:
                    if not device and not e.name.startswith(SPAN_PREFIX):
                        continue
                    events.append((plane.name, line.name, e.name,
                                   float(e.start_ns), float(e.duration_ns)))
    return events


def save_slice(events: Sequence[Event], path: str, *,
               around: str = r"_cut_|cutlayer", before_ns: float = 5e6,
               length_ns: float = 25e6) -> None:
    """Write the events of a short stretch of the trace as JSON: from
    `before_ns` ahead of the first device op whose name matches `around`
    (else of the first device op), for `length_ns`.  Host spans are kept
    where they overlap the stretch.  Small recorded traces for tests are
    made this way."""
    import json
    dev = sorted((e for e in events if DEVICE_PLANE.match(e[0])),
                 key=lambda e: e[3])
    if not dev:
        return
    rx = re.compile(around)
    hit = next((e for e in dev if rx.search(e[2])), dev[0])
    lo = hit[3] - before_ns
    hi = lo + length_ns
    keep = [list(e) for e in sorted(events, key=lambda e: e[3])
            if e[3] < hi and e[3] + e[4] > lo]
    with open(path, "w") as f:
        json.dump({"slice_ns": [lo, hi], "events": keep}, f)


def union(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                    float]]:
    """Merge (start, end) intervals into disjoint ones, in order."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


@dataclasses.dataclass
class Reduced:
    busy_s: float                 # mean over devices
    window_s: float
    ops_s: Dict[str, float]       # device time by op name, all devices
    gaps_s: Dict[str, float]      # idle time on device 0 by host span
    num_devices: int

    def matching(self, pattern) -> float:
        """Device seconds of the ops whose names match `pattern`, summed
        over devices."""
        rx = re.compile(pattern) if isinstance(pattern, str) else pattern
        return sum(t for n, t in self.ops_s.items() if rx.search(n))

    def collective_s(self) -> float:
        return self.matching(COLLECTIVE)

    def breakdown(self, top: int = 10) -> dict:
        def best(d):
            return [[k[:BREAKDOWN_NAME_CHARS], v] for k, v in sorted(
                d.items(), key=lambda kv: -kv[1])[:top]]
        return {"device_ops": best(self.ops_s),
                "idle_gaps": best(self.gaps_s)}


def reduce(events: Sequence[Event], *, num_devices: int,
           window_s: float = None) -> Reduced:
    """`window_s` is the traced window's length; without it, the span
    from the first to the last event of device 0."""
    per_dev: Dict[int, List[Tuple[float, float]]] = collections.defaultdict(
        list)
    ops: Dict[str, float] = collections.defaultdict(float)
    spans: List[Tuple[float, float, str]] = []
    for plane, _line, name, start, dur in events:
        m = DEVICE_PLANE.match(plane)
        if m:
            dev = int(m.group(1))
            if dev >= num_devices:
                continue
            per_dev[dev].append((start, start + dur))
            ops[name] += dur * 1e-9
        else:
            spans.append((start, start + dur, name))
    busy = [sum(b - a for a, b in union(per_dev.get(d, [])))
            for d in range(num_devices)]
    dev0 = union(per_dev.get(0, []))
    if window_s is None:
        window_s = (dev0[-1][1] - dev0[0][0]) * 1e-9 if dev0 else 0.0
    gaps: Dict[str, float] = collections.defaultdict(float)
    spans.sort()
    ends = [b for _, b, _ in spans]
    first = 0
    for (_, a), (b, _) in zip(dev0[:-1], dev0[1:]):
        # spans are the benchmark's own, sequential on one thread: skip
        # those that ended before this gap, read those that start in it
        while first < len(spans) and ends[first] <= a:
            first += 1
        best, owner = 0.0, "host:none"
        k = first
        while k < len(spans) and spans[k][0] < b:
            ov = min(spans[k][1], b) - max(spans[k][0], a)
            if ov > best:
                best, owner = ov, spans[k][2]
            k += 1
        gaps[owner] += (b - a) * 1e-9
    return Reduced(busy_s=sum(busy) / num_devices * 1e-9, window_s=window_s,
                   ops_s=dict(ops), gaps_s=dict(gaps),
                   num_devices=num_devices)
