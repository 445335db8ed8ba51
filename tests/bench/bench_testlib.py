"""A tiny copy of the benchmark for tests on the CPU: the real harness,
drivers, references and metric readers under a temporary root, with small
configurations and traffic in place of the cells' own."""
from __future__ import annotations

import io
import json
import os
import shutil
import sys
from contextlib import redirect_stderr, redirect_stdout

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_PAPER = {
    "name": "tiny_paper", "source": "test", "reference": "paper_cnn",
    "num_clients": 2, "noise_stds": [0.4, 1.0], "num_classes": 10,
    "image_shape": [8, 8, 3], "conv_channels": [4, 8], "d_bottleneck": 8,
    "dense_units": [16], "s": 0.01, "link_bits": 32, "compute_dtype": "fp32",
    "matmul_precision": "default",
    "dataset_size": 64}


def tiny_root(tmp_path, *, configs=None, traffic=None, cells=None,
              end_to_end=None, per_layer=None) -> str:
    """A checkout-shaped directory: the real bench/ plus BENCHMARK.json
    naming the tiny cells.  A cell's `like` gives it the metrics of that
    cell of BENCHMARK.json; `end_to_end` and `per_layer` add entries."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        spec = json.load(f)
    configs = configs or {"tiny_paper": TINY_PAPER}
    traffic = traffic or {}
    for name, conf in configs.items():
        with open(os.path.join(root, "bench", "configs", name + ".json"),
                  "w") as f:
            json.dump(conf, f)
    for name, tr in traffic.items():
        with open(os.path.join(root, "bench", "traffic", name + ".json"),
                  "w") as f:
            json.dump(tr, f)
    spec["configs"] += [{"name": n, "source": "test",
                         "file": f"bench/configs/{n}.json", "reduced": [],
                         "why": "test"} for n in configs]
    for c in cells or []:
        spec["workloads"].append(c)
        for m in spec["end_to_end"]:
            if "workloads" in m and c.get("like") in m["workloads"]:
                m["workloads"].append(c["name"])
        for m in spec["per_layer"]:
            if "workloads" in m and c.get("like") in m["workloads"]:
                m["workloads"].append(c["name"])
    spec["end_to_end"] += end_to_end or []
    spec["per_layer"] += per_layer or []
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def tiny_train_traffic(**limits):
    """The training traffic at a tiny size, held to the limits that the
    cell's own traffic file commits."""
    with open(os.path.join(ROOT, "bench", "traffic",
                           "inl_train_b256.json")) as f:
        lim = json.load(f)["limits"]
    lim.update(limits)
    return {"driver": "paper_train", "batch_size": 16, "lr": 0.002,
            "wire": "dense", "eval_n": 32, "prefetch": 2, "limits": lim}


def run_cell(root, workload, *, seed=123, seconds=1.0, trace=0, control=0):
    """Drive a whole run on the CPU; returns (exit code, result dict or
    None, stderr text)."""
    from bench import harness
    out, err = io.StringIO(), io.StringIO()
    with redirect_stdout(out), redirect_stderr(err):
        rc = harness.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace),
                           "--control", str(control)],
                          root=root, require_chip=False)
    lines = out.getvalue().strip().splitlines()
    return rc, (json.loads(lines[-1]) if lines else None), err.getvalue()

