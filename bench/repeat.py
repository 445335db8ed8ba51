"""Run one cell several times, one process per run, and summarise the spread.

    python3 bench/repeat.py --workload <cell> --seeds 11,12,13 --seconds 10 \
        [--trace 0|1] [--control 0|1] [--out <runs>.jsonl] \
        [--events-dir <dir>]

Each run is `bench/run.py` in a child process (this process never touches
JAX, so the child has the chip to itself).  Every run's result line, exit
code, wall time and the end of its standard error are appended to `--out`
as one JSON line.  At the end it prints, for each metric and each compared
number, the values over the runs, their median and the spread: the
distance between the first and third quartile (`statistics.quantiles`,
n=4) as a share of the median.
"""
import argparse
import json
import os
import statistics
import subprocess
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def spread(values):
    """(median, interquartile distance over the median)."""
    med = statistics.median(values)
    if len(values) < 2 or med == 0:
        return med, float("nan")
    q1, _, q3 = statistics.quantiles(values, n=4)
    return med, (q3 - q1) / abs(med)


def one_run(args, seed):
    cmd = [sys.executable, os.path.join(ROOT, "bench", "run.py"),
           "--workload", args.workload, "--seed", str(seed),
           "--seconds", str(args.seconds), "--trace", str(args.trace),
           "--control", str(args.control)]
    if args.events_dir and args.trace:
        os.makedirs(args.events_dir, exist_ok=True)
        cmd += ["--events-out", os.path.join(
            args.events_dir, f"{args.workload}.{seed}.events.json")]
    t0 = time.time()
    p = subprocess.run(cmd, cwd=ROOT, capture_output=True, text=True,
                       timeout=args.timeout)
    lines = p.stdout.strip().splitlines()
    try:
        result = json.loads(lines[-1]) if lines else None
    except json.JSONDecodeError:
        result = None
    return {"workload": args.workload, "seed": seed, "trace": args.trace,
            "control": args.control, "rc": p.returncode,
            "wall_s": time.time() - t0, "result": result,
            "stderr_tail": p.stderr[-6000:]}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated; one run per seed, in order")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, default=0)
    ap.add_argument("--control", type=int, default=0)
    ap.add_argument("--timeout", type=float, default=1500)
    ap.add_argument("--out", default=None)
    ap.add_argument("--events-dir", default=None,
                    help="with --trace 1: keep a short slice of each run's "
                         "trace events here")
    args = ap.parse_args(argv)
    records = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        rec = one_run(args, seed)
        records.append(rec)
        if args.out:
            os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                        exist_ok=True)
            with open(args.out, "a") as f:
                f.write(json.dumps(rec) + "\n")
        res = rec["result"] or {}
        print(f"run seed={seed} rc={rec['rc']} wall={rec['wall_s']:.1f}s "
              f"correct={res.get('correct')} "
              f"metrics={ {k: v['value'] for k, v in res.get('metrics', {}).items()} } "
              f"checks={ {k: v['value'] for k, v in res.get('checks', {}).items()} }",
              flush=True)
        if rec["rc"] != 0 or not res:
            print(rec["stderr_tail"][-3000:], flush=True)
    table = {}
    for rec in records:
        res = rec["result"] or {}
        for k, v in res.get("metrics", {}).items():
            table.setdefault(k, []).append(v["value"])
        for k, v in res.get("checks", {}).items():
            table.setdefault("check:" + k, []).append(v["value"])
    for k, vals in table.items():
        med, sp = spread(vals)
        print(f"summary {k}: median={med!r} spread={sp!r} "
              f"values={vals!r}", flush=True)


if __name__ == "__main__":
    main()
