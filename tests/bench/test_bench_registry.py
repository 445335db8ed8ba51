"""The harness finds a cell, a configuration, a traffic mix and a per-layer
metric by name, as new files, without an edit to any existing file; and
it refuses to run without a TPU or on a device missing from the peaks
table."""
import io
import json
import os
from contextlib import redirect_stderr, redirect_stdout

import pytest

import bench_testlib as bt
from bench import harness, registry

NEW_METRIC = '''"""A metric a later change adds: examples per second over 1000."""


def read(run, out):
    return out.facts["examples_per_s"] / 1000.0
'''


def test_new_cell_config_and_metric_are_found_by_name(tmp_path):
    before = {p: open(os.path.join(bt.ROOT, "bench", p), "rb").read()
              for p in ("harness.py", "registry.py", "run.py")}
    root = bt.tiny_root(
        tmp_path, traffic={"tiny_train": bt.tiny_train_traffic()},
        cells=[{"name": "tiny_new", "config": "tiny_paper",
                "traffic": "tiny_train", "chips": 1, "why": "test"}],
        per_layer=[{"name": "kilo_examples_per_s.tiny", "unit": "1000/s",
                    "better": "higher", "source": "host_clock",
                    "layer": "step program", "moves": "train_examples_per_s",
                    "workloads": ["tiny_new"]}])
    with open(os.path.join(root, "bench", "metrics",
                           "kilo_examples_per_s.tiny.py"), "w") as f:
        f.write(NEW_METRIC)
    spec = json.load(open(os.path.join(root, "BENCHMARK.json")))
    for m in spec["end_to_end"]:
        if m["name"] == "train_examples_per_s":
            m["workloads"].append("tiny_new")
    json.dump(spec, open(os.path.join(root, "BENCHMARK.json"), "w"))

    reg = registry.Registry(root)
    assert reg.config("tiny_paper")["num_clients"] == 2
    assert reg.traffic("tiny_train")["driver"] == "paper_train"
    assert [m["name"] for m in reg.per_layer("tiny_new")] == [
        "kilo_examples_per_s.tiny"]
    rc, res, err = bt.run_cell(root, "tiny_new", trace=1)
    assert rc == 0, err
    assert res["metrics"]["kilo_examples_per_s.tiny"]["value"] > 0
    assert "busy_s" in res["device"] and "breakdown" in res
    for p, text in before.items():
        assert open(os.path.join(bt.ROOT, "bench", p), "rb").read() == text


def test_unknown_names_are_errors():
    reg = registry.Registry()
    with pytest.raises(KeyError, match="no workloads entry"):
        reg.cell("no_such_cell")
    with pytest.raises(FileNotFoundError):
        reg.metric("no_such_metric")


def test_unknown_device_kind_is_an_error():
    assert harness.load_peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        harness.load_peaks("TPU v99")


def test_no_tpu_is_an_error_and_prints_no_result():
    with pytest.raises(harness.NoChip):
        harness.require_chips(1)
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = harness.main(["--workload", "paper_inl_train", "--seed", "1",
                           "--seconds", "1"])
    assert rc == harness.EXIT_NO_CHIP
    assert out.getvalue() == "" and "no result" in err.getvalue()


def test_every_cell_names_existing_files():
    reg = registry.Registry()
    for cell in reg.spec["workloads"]:
        reg.config(cell["config"])
        traffic = reg.traffic(cell["traffic"])
        reg.driver(traffic["driver"])
        assert reg.end_to_end(cell["name"]) and reg.per_layer(cell["name"])
    for m in reg.spec["per_layer"]:
        assert callable(reg.metric(m["name"]).read)


def test_faults_are_found_by_the_drivers_name():
    from bench import faults
    reg = registry.Registry()
    for cell in reg.spec["workloads"]:
        driver = reg.traffic(cell["traffic"])["driver"]
        assert reg.driver(driver).FAULTS, driver
    with pytest.raises(KeyError, match="has no fault"):
        faults.planted("paper_train", "no_such_fault")


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_planted_fault_is_undone_after_its_run(fault):
    from bench import faults
    from repro.core.schemes.inl import INLScheme
    orig = INLScheme.make_round
    with faults.planted("paper_train", fault):
        assert INLScheme.make_round is not orig
    assert INLScheme.make_round is orig
