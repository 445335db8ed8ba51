"""The training cells' `correct` on the CPU, at a tiny size: a sound run
passes; the control (the program's own bf16 compute path in its place)
and each fault the timed path can have fail.  The harness's look for a
chip is skipped; everything else of a run is driven."""
import pytest

import bench_testlib as bt
from bench import faults


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return bt.tiny_root(
        tmp_path_factory.mktemp("bench"),
        traffic={"tiny_train": bt.tiny_train_traffic()},
        cells=[{"name": "tiny_train", "config": "tiny_paper",
                "traffic": "tiny_train", "chips": 1, "why": "test",
                "like": "paper_inl_train"}])


def _checks(res):
    return {k: v["value"] for k, v in res["checks"].items()}


def test_sound_run_is_correct(root):
    rc, res, err = bt.run_cell(root, "tiny_train")
    assert rc == 0, err
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_examples_per_s", "setup_s"}
    assert list(res)[-1] == "checks"
    assert "compiles in the window: 0" in err


def test_control_is_not_correct(root):
    rc, res, err = bt.run_cell(root, "tiny_train", control=1)
    assert rc == 0, err
    assert not res["correct"], res["checks"]


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_fault_is_not_correct(root, fault):
    with faults.planted("paper_train", fault):
        rc, res, err = bt.run_cell(root, "tiny_train")
    assert rc == 0, err
    assert not res["correct"], (fault, _checks(res))
