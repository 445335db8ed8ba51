"""The xlstm_inl_train cell's driver (bench/drivers/llm_train.py) at a
tiny size on the CPU: through the driver's functions a sound first group
passes the limits the cell's traffic file commits, and the control and
each fault fail one; the INL split's and the recurrences' scopes reach the
compiled train program; a whole run goes through the harness; the
cell's readers on a slice of a trace recorded on a TPU v5e."""
import contextlib
import json
import os

import pytest

import bench_testlib as bt
import xlstm_testlib as xt
from bench import program_trace, trace
from bench.drivers import llm_train as D
from bench.references import xlstm_inl as ref

DATA = os.path.join(os.path.dirname(__file__), "data")


def _planted(variant):
    if variant == "control":
        return D.state_in_bf16()
    if variant in D.FAULTS:
        return D.plant(variant)
    return contextlib.nullcontext()


@pytest.fixture(scope="module")
def readings():
    """Group 0 of the tiny cell through the driver's functions, compared
    with the reference, for the sound program, the control and each
    fault."""
    conf, tr = xt.tiny_xlstm(), xt.tiny_llm_traffic()
    out = {}
    for variant in ("sound", "control") + D.FAULTS:
        with _planted(variant):
            trainer, p0, groups = D.start(conf, tr, 11)
            try:
                got = D.first_group(trainer, next(groups), p0)
            finally:
                groups.close()
            trainer.params = trainer.opt_state = None
            out[variant] = D.compare(ref, conf, tr, got)
    return tr["limits"], out


@pytest.mark.parametrize("variant", ["sound", "control"] + list(D.FAULTS))
def test_sound_group_passes_and_control_and_faults_fail(readings, variant):
    limits, out = readings
    failed = [k for k, lim in limits.items() if not out[variant][k] <= lim]
    if variant == "sound":
        assert not failed, out[variant]
    else:
        assert failed, (variant, out[variant])


def test_scopes_reach_the_compiled_train_program():
    """The INL split's scopes and the recurrences' reach the op_names of
    the compiled train program, backward and recomputed ops included; the
    time scans' `while` ops are left out of the recurrences' time."""
    from repro.launch import train
    conf, tr = xt.tiny_xlstm(), xt.tiny_llm_traffic()
    trainer, p0, groups = D.start(conf, tr, 5)
    try:
        batches = next(groups)
    finally:
        groups.close()
    keys = train.group_keys(trainer.rng, trainer.group_size)[1]
    hlo = trainer.epoch_fn.lower(trainer.params, trainer.opt_state, batches,
                                 keys).compile().as_text()
    _, names = program_trace.op_names(hlo)
    scopes = {program_trace.scope_of(n) for n in names.values()}
    assert {"encoder", "cut", "decoder", "loss", "optimizer"} <= scopes
    prog = D.recurrence_program(hlo)
    recurrent = [names[i] for i in prog["recurrent"]]
    for scope in ("mlstm", "slstm"):
        assert any(f"/{scope}/" in n and "transpose(" in n for n in recurrent)
        assert any(f"/{scope}/" in n and "transpose(" not in n
                   for n in recurrent)
    assert prog["enclosing"] and not prog["enclosing"] & prog["recurrent"]
    assert any(i.startswith("while") for i in prog["enclosing"])


def test_cell_runs_through_the_harness(tmp_path):
    root = bt.tiny_root(
        tmp_path, configs={"tiny_xlstm": xt.tiny_xlstm()},
        traffic={"tiny_llm": xt.tiny_llm_traffic()},
        cells=[{"name": "tiny_xlstm_train", "config": "tiny_xlstm",
                "traffic": "tiny_llm", "chips": 1, "why": "test",
                "like": "xlstm_inl_train"}])
    rc, res, err = bt.run_cell(root, "tiny_xlstm_train", trace=1)
    assert rc == 0, err
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert "compiles in the window: 0" in err
    assert set(res["checks"]) == set(xt.tiny_llm_traffic()["limits"])
    # on the CPU only the host-clock readers find something to read
    assert "input_wait_share.xlstm_train" in res["metrics"]




def test_readers_on_a_recorded_v5e_slice():
    """0.6 ms of a traced xlstm_inl_train run on a TPU v5e around the cut
    layer's forward kernel, with the names of the train program's
    instructions under the recurrences' scopes (`recurrent`) and of its
    control-flow ops (`enclosing`).  Op names are cut to 160 characters,
    the kernel's kept whole.  Each reader against the sums counted from
    the events."""
    from types import SimpleNamespace

    from bench import registry
    with open(os.path.join(DATA, "xlstm_inl_train.v5e.events.json")) as f:
        rec = json.load(f)
    events = [tuple(e) for e in rec["events"]]
    lo, hi = rec["slice_ns"]
    window_s = (hi - lo) * 1e-9
    reduced = trace.reduce(events, num_devices=1, window_s=window_s)
    program = {"module": rec["module"], "recurrent": set(rec["recurrent"]),
               "enclosing": set(rec["enclosing"])}
    ops = [e for e in events if e[0] == "/device:TPU:0"]
    recurrence_s = 1e-9 * sum(e[4] for e in ops if e[2].split(" = ")[0]
                              .lstrip("%") in program["recurrent"])
    cut_s = 1e-9 * sum(e[4] for e in ops if "_cutlayer_call" in e[2])
    busy_s = 1e-9 * sum(b - a for a, b in trace.union(
        [(e[3], e[3] + e[4]) for e in ops]))
    assert recurrence_s > 0 and cut_s > 0 and 0 < busy_s < window_s
    assert D.recurrence_seconds(reduced, program) == pytest.approx(
        recurrence_s, rel=1e-12)
    run = SimpleNamespace(reduced=reduced, devices=[None],
                          peaks={"bf16_flops_per_s": 197e12})
    out = SimpleNamespace(facts={
        "recurrence_s_traced": recurrence_s, "steps_traced": 1,
        "input_wait_s": 0.01, "window_s": 2.0, "tokens_per_s": 1000.0,
        "train_flops_per_token": 2.675e9})
    want = {"recurrence_us_per_step.xlstm_train": 1e6 * recurrence_s,
            "cutlayer_us_per_step.xlstm_train": 1e6 * cut_s,
            "device_idle.xlstm_train": 100 * (1 - busy_s / window_s),
            "input_wait_share.xlstm_train": 0.5,
            "step_mfu.xlstm_train": 100 * 2.675e9 * 1000.0 / 197e12}
    reg = registry.Registry()
    assert {m["name"] for m in reg.per_layer("xlstm_inl_train")} == set(want)
    for name, value in want.items():
        assert reg.metric(name).read(run, out) == pytest.approx(value), name


@pytest.mark.parametrize("variant", ["control"] + list(D.FAULTS))
def test_control_and_faults_are_undone_after_their_run(variant):
    from repro.kernels import ops
    from repro.launch import steps
    from repro.models import ssm

    def hooked():
        return (ssm._mlstm_cell, ssm._slstm_cell, steps.make_inl_train_step,
                ops.cutlayer)
    before = hooked()
    with _planted(variant):
        assert hooked() != before
    assert hooked() == before
