"""The reduction of the program's own spans and scopes
(bench/program_trace.py) and the per-layer readers that take their numbers
from it: leaf device time by named scope, device time by program, and
device 0's idle time split over the host spans that overlap it, on a small
hand-made trace whose answers are counted by hand, on a slice recorded on a
TPU v5e, on a real `.xplane.pb`, and through bench/traced_run.py on a tiny
cell on the CPU."""
import os
from types import SimpleNamespace

import pytest

import bench_testlib as bt
from bench import program_trace as pt
from bench import registry, trace

DEV0, HOST = "/device:TPU:0", "/host:CPU"
OPS, MODS = pt.OP_LINE, pt.MODULE_LINE
EPOCH, PREDICT = "jit_epoch_fn", "jit__predict"

# device 0 (ns): the epoch program runs [0, 100), its `while` op enclosing
# eight ops; the evaluation program [110, 120); the next epoch [200, 210).
# Idle gaps: [100, 110) and [120, 200).
DEVICE = pt.with_modules([
    (DEV0, MODS, EPOCH, 0.0, 100.0, {}),
    (DEV0, MODS, PREDICT, 110.0, 10.0, {}),
    (DEV0, MODS, EPOCH, 200.0, 10.0, {}),
    (DEV0, OPS, "%while.1 = (f32[]) while(...)", 0.0, 100.0, {}),
    (DEV0, OPS, "%copy-done.1 = f32[] copy-done(...)", 0.0, 4.0, {}),
    (DEV0, OPS, "%fusion.1 = f32[] fusion(...)", 4.0, 26.0, {}),
    (DEV0, OPS, "%fusion.2 = f32[] fusion(...)", 30.0, 20.0, {}),
    (DEV0, OPS, "%fusion.3 = f32[] fusion(...)", 50.0, 10.0, {}),
    (DEV0, OPS, "%fusion.4 = f32[] fusion(...)", 60.0, 10.0, {}),
    (DEV0, OPS, "%fusion.5 = f32[] fusion(...)", 70.0, 10.0, {}),
    (DEV0, OPS, "%fusion.6 = f32[] fusion(...)", 80.0, 15.0, {}),
    (DEV0, OPS, "%copy.1 = f32[] copy(...)", 95.0, 5.0, {}),
    # the evaluation program has an instruction of the same name
    (DEV0, OPS, "%fusion.1 = f32[] fusion(...)", 110.0, 10.0, {}),
    (DEV0, OPS, "%fusion.1 = f32[] fusion(...)", 200.0, 10.0, {}),
])
# the producer thread assembled through [105, 150) and put through
# [150, 180); the runner's thread waited through [100, 205)
SPANS = [
    (HOST, "python3#5", "repro.runner.assemble", 105.0, 45.0,
     {"rounds": 2, "bytes": 1000}),
    (HOST, "python3#5", "repro.prefetch.put", 150.0, 30.0, {"bytes": 1000}),
    (HOST, "python3#6", "repro.prefetch.wait", 100.0, 105.0, {}),
    (HOST, "python3#6", "bench.input_wait", 99.0, 107.0, {}),
]
STEP = "jit(epoch_fn)/while/body/closed_call/jit(step)"
HLO = f"""HloModule {EPOCH}, is_scheduled=true

%fused_computation.1 (param_0: f32[]) -> f32[] {{
  ROOT %inner.1 = f32[] negate(%param_0), metadata={{op_name="{STEP}/jvp(loss)/neg"}}
}}

ENTRY %main.1 (p: f32[]) -> f32[] {{
  %while.1 = (f32[]) while(%t), condition=%c, body=%b, metadata={{op_name="jit(epoch_fn)/while"}}
  %copy-start.1 = (f32[], f32[], u32[]) copy-start(%p)
  %copy-done.1 = f32[] copy-done(%copy-start.1)
  %fusion.1 = f32[] fusion(%copy-done.1), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jvp(encoder)/vmap()/conv_general_dilated" stack_frame_id=3}}
  %fusion.2 = f32[] fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/transpose(jvp(encoder))/vmap()/mul"}}
  %fusion.3 = f32[] fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jvp(cut)/jit(_cutlayer_call)/mul"}}
  %fusion.4 = f32[] fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/transpose(jvp(decoder))/dot_general"}}
  %fusion.5 = f32[] fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/jvp(loss)/jit(log_softmax)/sub"}}
  %fusion.6 = f32[] fusion(%p), kind=kLoop, calls=%f, metadata={{op_name="{STEP}/optimizer/div"}}
  %copy.2 = f32[] copy(%fusion.6)
  ROOT %copy.1 = f32[] copy(%p), metadata={{op_name="jit(epoch_fn)/while/body/dynamic_update_slice"}}
}}
"""
EVENTS = pt.name_scopes(DEVICE + SPANS, HLO)


def _reduced(events=EVENTS):
    return pt.reduce(events, window_s=1e-6, module=EPOCH)


def test_op_names_and_scopes_from_the_hlo_text():
    module, names = pt.op_names(HLO)
    assert module == EPOCH and len(names) == 12 and "inner.1" in names
    # the async copy the compiler added without an op_name, and the wait
    # for it, are named after the op that reads the copy; a copy nothing
    # named reads, after the op that made its input
    assert names["copy-start.1"] == names["copy-done.1"] == names["fusion.1"]
    assert names["copy.2"] == names["fusion.6"]
    assert pt.instruction("%fusion.1 = f32[] fusion(...)") == "fusion.1"
    assert pt.module_name("jit_epoch_fn(1468074330)") == EPOCH
    assert pt.scope_of(names["fusion.2"]) == "encoder"
    assert pt.scope_of(names["fusion.6"]) == "optimizer"
    assert pt.scope_of(names["copy.1"]) == pt.scope_of(None) == ""
    # a function named like a scope is not one; the cut layer's kernel
    # call is the cut's
    assert pt.scope_of("jit(step)/jit(loss)/mul") == ""
    for op in ("jit(step)/jvp(jit(_cutlayer_call))/pallas_call",
               "jit(step)/transpose(jvp(jit(_cutlayer_call)))/pallas_call"):
        assert pt.scope_of(op) == "cut"


def test_each_op_has_the_program_whose_run_encloses_it():
    mods = [e[5].get("module") for e in DEVICE if e[1] == OPS]
    assert mods == [EPOCH] * 9 + [PREDICT, EPOCH]
    stray = pt.with_modules([(DEV0, OPS, "%x = f32[] x()", 150.0, 1.0, {})])
    assert "module" not in stray[0][5]
    # an op of the evaluation program is not named from the epoch's HLO
    assert [e[5].get("op_name") is None for e in EVENTS
            if e[1] == OPS and e[3] == 110.0] == [True]


def test_leaf_scope_sums_count_no_parent_twice():
    r = _reduced()
    want = {"encoder": 60, "cut": 10, "decoder": 10, "loss": 10,
            "optimizer": 15, "": 5}
    assert {k: round(v * 1e9, 6) for k, v in r.scope_s.items()} == want
    # the `while` is left out, its eight children and the next epoch's op
    # are not
    assert abs(r.module_s[EPOCH] - 110e-9) < 1e-18
    assert abs(sum(r.scope_s.values()) - r.module_s[EPOCH]) < 1e-18
    assert r.unnamed_s == 0.0
    unnamed = pt.reduce(DEVICE, window_s=1e-6, module=EPOCH)
    assert abs(unnamed.unnamed_s - 110e-9) < 1e-18


def test_evaluation_ops_stay_out_of_the_scope_sums():
    r = _reduced()
    assert abs(r.module_s[PREDICT] - 10e-9) < 1e-18
    # without the evaluation program the scope sums do not move
    no_eval = [e for e in EVENTS if not (e[0] == DEV0 and 110 <= e[3] < 120)]
    assert _reduced(no_eval).scope_s == r.scope_s


def test_an_idle_gap_is_split_over_the_spans_that_overlap_it():
    r = _reduced()
    # assemble: 5 ns of [100, 110) and 30 of [120, 200); put: 30 of
    # [120, 200); the wait: all 90 idle ns
    got = {k: round(v * 1e9, 6) for k, v in r.idle_s.items()}
    assert got == {"repro.runner.assemble": 35, "repro.prefetch.put": 30,
                   "repro.prefetch.wait": 90}
    assert r.threads_of("repro.runner.assemble") == [HOST + "/python3#5"]
    assert r.threads_of("repro.prefetch.wait") == [HOST + "/python3#6"]
    assert r.durations_s("repro.prefetch.put") == [pytest.approx(30e-9)]
    # a span that outlasts the last op keeps its idle tail: [210, 260)
    tail = (HOST, "python3#5", "repro.runner.assemble", 205.0, 55.0, {})
    r = pt.reduce(EVENTS + [tail], window_s=1e-6, module=EPOCH)
    assert round(r.idle_s["repro.runner.assemble"] * 1e9, 6) == 35 + 50
    # spans alone, no device op: nothing to split
    assert pt.reduce(SPANS, window_s=1.0).idle_s == {}


def test_bench_reduction_is_the_same_with_the_program_events():
    """`bench.trace.reduce` takes its spans from `bench.` names only; with
    the program's spans and the program runs dropped it reads as on the
    device ops and bench spans alone."""
    base = [e[:5] for e in DEVICE + SPANS
            if e[1] == OPS or e[2].startswith("bench.")]
    a = trace.reduce(pt.bench_events(EVENTS), num_devices=1, window_s=1e-6)
    b = trace.reduce(base, num_devices=1, window_s=1e-6)
    assert a == b and list(a.gaps_s) == ["bench.input_wait"]


def _metric(name):
    return registry.Registry(bt.ROOT).metric(name)


READ = {  # reader: its value on EVENTS with a window of 1 us, 2 rounds in
          # 2 epochs (one span of each kind recorded)
    "prefetch_wait_share.paper_train": 100.0 * 105e-9 / 1e-6,
    "assemble_s_per_epoch.paper_train": 45e-9,
    "put_s_per_epoch.paper_train": 30e-9,
    "idle_in_assemble_share.paper_train": 100.0 * 35e-9 * 2 / 1e-6,
    "idle_in_put_share.paper_train": 100.0 * 30e-9 * 2 / 1e-6,
    "encoder_us_per_round.paper_train": 1e6 * 60e-9 / 2,
    "fusion_node_us_per_round.paper_train": 1e6 * 20e-9 / 2,
    "optimizer_us_per_round.paper_train": 1e6 * 15e-9 / 2,
}


@pytest.mark.parametrize("name", sorted(READ))
def test_program_readers(name):
    reader = _metric(name)
    out = SimpleNamespace(facts={"rounds_traced": 2, "epochs_in_window": 2})
    got = reader.read(SimpleNamespace(program=_reduced()), out)
    assert abs(got - READ[name]) < 1e-9 * abs(READ[name])
    # nothing to read: a run without the program's trace (as at a commit
    # whose program records none), or one with no traced rounds
    assert reader.read(SimpleNamespace(), out) is None
    assert reader.read(SimpleNamespace(program=pt.reduce(
        [], window_s=1.0)), out) is None


def test_load_keeps_the_program_spans_with_their_thread_and_stats(tmp_path):
    import threading

    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((32, 32))
    f(x).block_until_ready()

    def producer():
        with jax.profiler.TraceAnnotation("repro.runner.assemble", rounds=3,
                                          bytes=12345):
            f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.step"):
        t = threading.Thread(target=producer)
        t.start()
        t.join()
    with jax.profiler.TraceAnnotation("not_ours"):
        f(x).block_until_ready()
    jax.profiler.stop_trace()
    events = pt.load(str(tmp_path))
    spans = {e[2]: e for e in events}
    assert set(spans) == {"bench.step", "repro.runner.assemble"}
    assert spans["repro.runner.assemble"][5] == {"rounds": 3, "bytes": 12345}
    assert spans["repro.runner.assemble"][1] != spans["bench.step"][1]
    # the benchmark's own loader keeps none of the program's spans, so
    # its gap attribution reads as before
    assert {e[2] for e in trace.load_xplane(str(tmp_path))
            if not e[0].startswith("/device")} == {"bench.step"}


def test_traced_run_on_a_tiny_cell(tmp_path):
    """bench/traced_run.py on the CPU: the run's own result line, and the
    program's host-span metrics; the CPU trace has no device ops, so the
    scope and idle readers find nothing."""
    import io
    import json
    from contextlib import redirect_stderr, redirect_stdout

    from bench import traced_run
    root = bt.tiny_root(
        tmp_path, traffic={"tiny_train": bt.tiny_train_traffic()},
        cells=[{"name": "tiny_traced", "config": "tiny_paper",
                "traffic": "tiny_train", "chips": 1, "why": "test",
                "like": "paper_inl_train"}])
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = traced_run.main(["--workload", "tiny_traced", "--seed",
                              "2400000011", "--seconds", "0.5"],
                             root=root, require_chip=False)
    assert rc == 0, err.getvalue()
    res = json.loads(out.getvalue().strip().splitlines()[-1])
    assert res["correct"] and "breakdown" in res
    got = res["program"]["metrics"]
    assert set(got) == {"prefetch_wait_share.paper_train",
                        "assemble_s_per_epoch.paper_train",
                        "put_s_per_epoch.paper_train"}
    assert all(v > 0 for v in got.values())
    facts = res["program"]["facts"]
    assert facts["epoch_module"] == "jit_epoch_fn"
    producer = facts["threads.repro.runner.assemble"]
    assert producer == facts["threads.repro.prefetch.put"]
    assert len(producer) == 1
    assert producer != facts["threads.repro.prefetch.wait"]


def _scoped_slice():
    return pt.read_slice(os.path.join(os.path.dirname(__file__), "data",
                                      "paper_inl_train.v5e.scoped.events.json"))


def test_the_recorded_scoped_v5e_slice():
    """11 s of a traced paper_inl_train window on one TPU v5e
    (`bench/traced_run.py --events-out`): device 0's longest idle stretch,
    between two epochs, with 6 ms of device work each side, the ops named
    from the epoch program's compiled HLO, and the spans cut to the
    stretch.  The producer assembled the next epoch's input through
    almost all of it; the runner waited; the cut layer's kernels ran once
    each way, under their names (5,726.25 ns, as cutlayer_us_per_round
    reads them)."""
    ev = _scoped_slice()
    r = pt.reduce(ev, window_s=1.0, module=EPOCH)
    # leaf ns, counted by hand over the slice's 1,565 ops (an op of no
    # length at another's end is not inside it)
    want = {"encoder": 4757962.5, "optimizer": 53760.0, "decoder": 33155.0,
            "loss": 17286.25, "cut": 14272.5, "": 31545.0}
    assert {k: round(v * 1e9, 2) for k, v in r.scope_s.items()} == want
    assert r.unnamed_s == 0.0
    assert round(r.module_s[PREDICT] * 1e9, 2) == 1006337.5
    assert round(r.module_s["jit__split_chain"] * 1e9, 2) == 330767.5
    assert r.threads_of("repro.runner.assemble") == r.threads_of(
        "repro.prefetch.put") != r.threads_of("repro.prefetch.wait")
    idle = {k: round(v * 1e9, 1) for k, v in r.idle_s.items()}
    assert idle == {"repro.runner.assemble": 10756893452.5 + 13333207.5,
                    "repro.prefetch.put": 152851161.0,
                    "repro.prefetch.wait": 10906681382.0}
    # the readers, with the stretch as the window, one round of each
    # kind and one epoch
    window = (23508902595.0 - 12538733625.0 + 2 * 6e6) * 1e-9
    run = SimpleNamespace(program=pt.reduce(ev, window_s=window,
                                            module=EPOCH))
    out = SimpleNamespace(facts={"rounds_traced": 1, "epochs_in_window": 1})
    got = {n: _metric(n).read(run, out) for n in READ}
    assert got["encoder_us_per_round.paper_train"] == pytest.approx(
        4757.9625)
    assert got["fusion_node_us_per_round.paper_train"] == pytest.approx(
        50.44125)
    assert got["optimizer_us_per_round.paper_train"] == pytest.approx(
        53.76)
    # two assemble spans (the second cut to 13.8 ms) for one epoch
    assert got["idle_in_assemble_share.paper_train"] == pytest.approx(
        100 * (10756893452.5 + 13333207.5) * 1e-9 / 2 / window)
    assert got["idle_in_put_share.paper_train"] == pytest.approx(
        100 * 152851161.0e-9 / window)
    # the cut scope holds the kernels the accepted reader finds by name
    cut = registry.Registry(bt.ROOT).metric(
        "cutlayer_us_per_round.paper_train")
    kernels = cut.read(SimpleNamespace(reduced=trace.reduce(
        pt.bench_events(ev), num_devices=1, window_s=window)), out)
    assert kernels == pytest.approx(5.72625)
    assert 1e6 * r.scope_s["cut"] >= kernels
