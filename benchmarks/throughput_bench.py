"""Benchmark 5 — end-to-end scheme-runner throughput (ISSUE 3 tentpole).

Measures what the sharded/pipelined execution layer actually buys at the
system level, on the same registry runner every scheme uses:

    per_round     the seed-style loop: one host->device transfer + one
                  jitted dispatch per round (runner dispatch="per_round")
    scan          whole-epoch lax.scan + double-buffered device prefetcher:
                  ONE dispatch per epoch (dispatch="scan")
    scan_sharded  the scan pipeline with the shard_map round on the
                  (client, data) host mesh — J node branches in parallel

Timings are the MEDIAN of --reps runs of a --epochs training run (examples/s
and rounds/s computed from the epoch geometry), after one unmeasured warmup
run that absorbs compilation.  On an accelerator it runs in-process over the
real devices.  On the CPU it runs on a FORCED multi-device host
(XLA_FLAGS=--xla_force_host_platform_device_count=2) so the shard_map path
executes real collectives: when the current process was started without
that flag the benchmark re-executes itself in a subprocess with it set
(device count is frozen at jax init).

Results: stdout CSV + BENCH_throughput.json (tracked across PRs, consumed
by the ROADMAP's measured-throughput entry).
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import subprocess
import sys
import time

DEVICE_FLAG = "--xla_force_host_platform_device_count"
DEFAULT_DEVICES = 2


def _reexec_with_devices(argv, devices: int):
    env = dict(os.environ)
    flags = env.get("XLA_FLAGS", "")
    env["XLA_FLAGS"] = f"{flags} {DEVICE_FLAG}={devices}".strip()
    env.setdefault("JAX_PLATFORMS", "cpu")
    cmd = [sys.executable, "-m", "benchmarks.throughput_bench"] + argv
    return subprocess.call(cmd, env=env, cwd=os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))


def _bench_config():
    """CPU-sized but dispatch-bound: a small Fig.-4 model over many rounds,
    so per-round orchestration overhead is the measurable quantity.  J=2
    divides the 2 forced devices -> a real client axis for scan_sharded."""
    from repro.configs.paper_inl import PaperExperimentConfig
    return PaperExperimentConfig(
        num_clients=2, noise_stds=(0.4, 2.0), conv_channels=(8,),
        d_bottleneck=8, dense_units=(32,), image_shape=(16, 16, 3),
        dataset_size=2048)


def run(reps: int = 5, epochs: int = 2, batch: int = 32,
        json_path: str = "BENCH_throughput.json", scheme: str = "inl"):
    import jax
    import numpy as np

    from repro.core import schemes
    from repro.core.schemes import runner
    from repro.data import multiview
    from repro.launch import mesh as mesh_lib

    cfg = _bench_config()
    n = cfg.dataset_size
    imgs, labels = multiview.make_base_dataset(
        n, image_shape=cfg.image_shape, seed=0)
    views = multiview.make_views(imgs, cfg.noise_stds)
    bpr = schemes.get(scheme).batches_per_round(cfg)
    rounds = (n // batch) // bpr              # what the runner executes
    examples = rounds * bpr * batch

    mesh = mesh_lib.make_inl_host_mesh(cfg.num_clients)
    variants = {
        "per_round": dict(dispatch="per_round"),
        "scan": dict(dispatch="scan"),
        "scan_sharded": dict(dispatch="scan", mesh=mesh),
    }

    results = {"meta": {
        "scheme": scheme, "devices": jax.device_count(),
        "mesh": dict(mesh.shape), "epochs": epochs, "batch": batch,
        "rounds_per_epoch": rounds, "examples_per_epoch": examples,
        "reps": reps, "backend": jax.default_backend(),
        "device_kind": jax.devices()[0].device_kind,
    }}
    print("variant,examples_per_sec,rounds_per_sec,sec_per_epoch,"
          "speedup_vs_per_round")
    base_eps = None
    for name, kw in variants.items():
        def go():
            return runner.run_scheme(scheme, views, labels, cfg,
                                     epochs=epochs, batch_size=batch,
                                     eval_n=batch, seed=0, **kw)
        go()                                   # warmup: compile + caches
        ts = []
        for _ in range(reps):
            t0 = time.perf_counter()
            go()
            ts.append(time.perf_counter() - t0)
        sec_per_epoch = statistics.median(ts) / epochs
        eps = examples / sec_per_epoch
        rps = rounds / sec_per_epoch
        base_eps = eps if name == "per_round" else base_eps
        speedup = eps / base_eps if base_eps else float("nan")
        results[name] = {
            "examples_per_sec": round(eps, 1),
            "rounds_per_sec": round(rps, 2),
            "sec_per_epoch": round(sec_per_epoch, 4),
            "speedup_vs_per_round": round(speedup, 3),
        }
        print(f"{name},{eps:.1f},{rps:.2f},{sec_per_epoch:.4f},"
              f"{speedup:.3f}")

    with open(json_path, "w") as f:
        json.dump(results, f, indent=2)
        f.write("\n")
    print(f"# wrote {json_path}")
    return results


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--epochs", type=int, default=2)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--scheme", default="inl")
    ap.add_argument("--json", default="BENCH_throughput.json")
    ap.add_argument("--devices", type=int, default=DEFAULT_DEVICES,
                    help="CPU only: forced host device count (re-exec if "
                         "the current process was started without the XLA "
                         "flag)")
    args = ap.parse_args(argv)

    import jax
    # on an accelerator the run stays in this process over the real
    # devices: a child would find the chip held by this parent.  Only the
    # CPU host re-executes with forced virtual devices
    if (jax.default_backend() == "cpu"
            and DEVICE_FLAG not in os.environ.get("XLA_FLAGS", "")):
        argv = argv if argv is not None else sys.argv[1:]
        rc = _reexec_with_devices(list(argv), args.devices)
        if rc:
            raise SystemExit(rc)
        return None
    return run(reps=args.reps, epochs=args.epochs, batch=args.batch,
               json_path=args.json, scheme=args.scheme)


if __name__ == "__main__":
    main()
