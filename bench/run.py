"""Run one cell of BENCHMARK.json once, on the chip it runs on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is one JSON object with the keys
`correct`, `attempted`, `failed`, `metrics`, `device` (and `breakdown` with
--trace 1), then `checks`: each number compared with the plain reference
beside its limit.  Without a TPU, or with fewer chips than the cell asks
for, it prints no result and exits with code 3.
"""
import os
import sys
import time

T_START = time.perf_counter()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

if __name__ == "__main__":
    from bench import harness
    sys.exit(harness.main(t_start=T_START))
