"""The trace reduction (bench/trace.py): busy time as the union of op
intervals, op and kernel sums, collective time, and idle gaps attributed
to the benchmark's host spans, on a small hand-made trace whose answers are
counted by hand, on a slice recorded on a TPU v5e, and the reading of a
real `.xplane.pb`."""
import bench_testlib
from bench import trace

DEV0, DEV1, HOST = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
OPS = "XLA Ops"

# device 0 (ns): [0, 10) and [5, 15) overlap, then [20, 30) and [40, 45);
# its idle gaps are [15, 20) and [30, 40)
EVENTS = [
    (DEV0, OPS, "fusion.1", 0.0, 10.0),
    (DEV0, OPS, "_cut_fwd_kernel", 5.0, 10.0),
    (DEV0, OPS, "all-gather.3", 20.0, 10.0),
    (DEV0, OPS, "fusion.1", 40.0, 5.0),
    # the host waited on the prefetcher through the first gap and was in
    # the evaluation for 8 of the second gap's 10 ns
    (HOST, "python", "bench.input_wait", 14.0, 7.0),
    (HOST, "python", "bench.eval", 32.0, 18.0),
]


def test_busy_is_the_union_of_op_intervals():
    r = trace.reduce(EVENTS, num_devices=1)
    assert abs(r.busy_s - 30e-9) < 1e-18
    # without a window the span of device 0's ops is taken
    assert abs(r.window_s - 45e-9) < 1e-18
    assert trace.union([(5, 8), (0, 2), (1, 3), (8, 9)]) == [(0, 3), (5, 9)]


def test_op_sums_kernels_and_breakdown():
    r = trace.reduce(EVENTS, num_devices=1, window_s=1e-7)
    assert abs(r.ops_s["fusion.1"] - 15e-9) < 1e-18
    assert abs(r.matching(r"_cut_(fwd|bwd)(_pack)?_kernel") - 10e-9) < 1e-18
    b = r.breakdown()
    assert b["device_ops"][0] == ["fusion.1", r.ops_s["fusion.1"]]
    assert {k for k, _ in b["device_ops"][1:]} == {"_cut_fwd_kernel",
                                                   "all-gather.3"}
    assert [k for k, _ in b["idle_gaps"]] == ["bench.eval",
                                              "bench.input_wait"]
    tops = [v for _, v in b["device_ops"]]
    assert tops == sorted(tops, reverse=True) and len(tops) <= 10
    many = [(DEV0, OPS, f"op{i}", 100.0 * i, 1.0 + i) for i in range(15)]
    assert len(trace.reduce(many, num_devices=1).breakdown()["device_ops"]) \
        == 10


def test_collectives_and_gap_owners():
    r = trace.reduce(EVENTS, num_devices=1)
    assert abs(r.collective_s() - 10e-9) < 1e-18
    assert abs(r.gaps_s["bench.input_wait"] - 5e-9) < 1e-18
    assert abs(r.gaps_s["bench.eval"] - 10e-9) < 1e-18
    # every idle gap between the first and the last op is attributed once
    assert abs(sum(r.gaps_s.values()) + r.busy_s - 45e-9) < 1e-18
    bare = [e for e in EVENTS if e[0] != HOST]
    assert set(trace.reduce(bare, num_devices=1).gaps_s) == {"host:none"}


def test_devices_average_and_window():
    two = EVENTS + [(DEV1, OPS, "fusion.1", 0.0, 10.0)]
    r = trace.reduce(two, num_devices=2, window_s=1.0)
    assert abs(r.busy_s - 20e-9) < 1e-18 and r.window_s == 1.0
    # a device beyond the cell's count is left out
    assert abs(trace.reduce(two, num_devices=1).busy_s - 30e-9) < 1e-18


def test_load_xplane_keeps_device_ops_and_bench_spans(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        f(x).block_until_ready()
    with jax.profiler.TraceAnnotation("not_ours"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    ev = trace.load_xplane(str(tmp_path))
    names = {e[2] for e in ev}
    assert "bench.step" in names and "not_ours" not in names


def _tpu_slice():
    import json
    import os
    path = os.path.join(os.path.dirname(__file__), "data",
                        "paper_inl_train.v5e.events.json")
    with open(path) as f:
        return [tuple(e) for e in json.load(f)["events"]]


def test_recorded_tpu_slice():
    """12 ms of a traced paper_inl_train window on one TPU v5e: the input's
    cast to bf16 and weight copies, then the epoch's while op, which
    encloses every later op; two rounds' cut-layer kernels; no
    collective."""
    events = _tpu_slice()
    r = trace.reduce(events, num_devices=1, window_s=1.0)
    # device 0 runs from 222,459,817 ns (the cast) to 1,078,274,922 ns (the
    # while op's end), idle for 30 ns in all between the copies before the
    # loop, while the host was in the evaluation span
    assert abs(r.busy_s - 855_815_075e-9) < 1e-12
    assert list(r.gaps_s) == ["bench.eval"]
    assert abs(r.gaps_s["bench.eval"] - 30e-9) < 1e-15
    # forward 3,450 and 3,448 ns, backward 2,279 and 2,280 ns
    kernels = r'_cutlayer_call_.*custom_call_target="tpu_custom_call"'
    assert abs(r.matching(kernels) - 11457e-9) < 1e-15
    assert r.collective_s() == 0.0
    ops = r.breakdown()["device_ops"]
    assert ops[0][0].startswith("%while.") and len(ops) == 10
    assert all(len(name) <= trace.BREAKDOWN_NAME_CHARS for name, _ in ops)


def test_cutlayer_reader_on_the_recorded_slice():
    from types import SimpleNamespace
    from bench import registry
    reader = registry.Registry(bench_testlib.ROOT).metric(
        "cutlayer_us_per_round.paper_train")
    run = SimpleNamespace(reduced=trace.reduce(_tpu_slice(), num_devices=1,
                                               window_s=1.0))
    out = SimpleNamespace(facts={"rounds_traced": 2})
    assert abs(reader.read(run, out) - 11457e-9 * 1e6 / 2) < 1e-9
    # nothing to read: no traced rounds, or no such kernel in the trace
    assert reader.read(run, SimpleNamespace(facts={"rounds_traced": 0})) \
        is None
    run.reduced = trace.reduce(EVENTS, num_devices=1)
    assert reader.read(run, out) is None
