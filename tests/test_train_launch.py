"""launch/train.py as `setup`, `device_groups`, `run_group` and `finish`:
`main` trains through them, a caller that runs them itself (the chip
benchmark's driver) gets main's trajectory, and --resume from a kept
checkpoint finishes bit-identically to the uninterrupted run."""
import os
import shutil

import numpy as np
import pytest

from repro.launch import train

ARGS = ["--arch", "xlstm-125m", "--smoke", "--scheme", "inl", "--steps", "6",
        "--batch", "1", "--seq", "16", "--scan-steps", "2", "--prefetch",
        "1", "--seed", "3"]


def _lines(history):
    return [{k: v for k, v in m.items() if k != "wall_s"} for m in history]


@pytest.fixture(scope="module")
def uninterrupted(tmp_path_factory):
    ckpt = str(tmp_path_factory.mktemp("full"))
    history = train.main(ARGS + ["--ckpt-dir", ckpt, "--ckpt-every", "2"])
    return ckpt, history


def test_main_logs_every_group(uninterrupted):
    _, history = uninterrupted
    assert [m["step"] for m in history] == [1, 3, 5]
    assert all(np.isfinite(m["loss"]) for m in history)


def test_the_functions_give_mains_trajectory(uninterrupted):
    _, history = uninterrupted
    tr = train.setup(train.parse_args(ARGS))
    for batches in train.device_groups(tr):
        ms = train.run_group(tr, batches)
        assert ms["loss"].shape == (2,)
    assert _lines(train.finish(tr)) == _lines(history)


def test_resume_finishes_bit_identically(uninterrupted, tmp_path):
    full, history = uninterrupted
    for ext in ("npz", "json"):
        shutil.copy(os.path.join(full, f"ckpt_00000004.{ext}"), tmp_path)
    resumed = train.main(ARGS + ["--ckpt-dir", str(tmp_path),
                                 "--ckpt-every", "2", "--resume"])
    assert _lines(resumed) == _lines(history)[-1:]
    with np.load(os.path.join(full, "ckpt_00000006.npz")) as a, \
            np.load(os.path.join(tmp_path, "ckpt_00000006.npz")) as b:
        assert sorted(a.files) == sorted(b.files)
        for k in a.files:
            assert np.array_equal(a[k], b[k]), k
