# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV
# sections (see each module for details):
#   table1    bandwidth_table    paper Table I closed-form vs published, plus
#                                per-round bits of every registered scheme
#   fig5/7    accuracy_curves    accuracy-vs-epoch / accuracy-vs-bandwidth for
#                                every scheme in the unified registry
#   kernels   kernel_bench       hot-spot micro-benchmarks
#   wire      wire_bench         packed wire format: bytes-on-wire per round
#                                (asserted == closed forms) + packed-vs-dense
#                                round throughput + bf16 policy leg
#   topology  topology_bench     star vs chain vs tree: per-edge bytes
#                                (asserted == closed forms) + round
#                                wall-clock per topology
#   links     links_bench        unreliable links: accuracy-vs-erasure per
#                                scheme (asserted: INL's partial fusion
#                                beats the single-uplink schemes at 0.3)
#                                + delivered-vs-offered training bandwidth
#   serve     serve_bench        serving plane: p50/p99 latency + goodput
#                                vs Poisson offered load per topology/wire
#                                (asserted: continuous batching >= 2x the
#                                serial baseline, one compile per bucket)
#   throughput throughput_bench  end-to-end runner throughput: per-round
#                                dispatch vs whole-epoch scan+prefetch vs
#                                shard_map (in-process on a TPU, forced
#                                2-device subprocess on the CPU)
#   chaos     chaos_bench        deterministic fault tolerance: serving
#                                goodput under churn, breaker vs none,
#                                node-kill degradation per scheme, and
#                                bit-identical crash-resume
#   cluster   cluster_bench      multi-process worker plane: 3-process ==
#                                in-process parity, SIGKILL+restart resume
#                                identity, serving goodput recovery, and
#                                adaptive vs fixed fault policies
#   frontier  frontier_bench     auto-placement search: accuracy-per-Gbit
#                                Pareto frontier over (scheme, cut depth,
#                                topology, width, wire) with exhaustively
#                                verified ledger pruning (asserted: the
#                                frontier beats the pure baselines at >= 1
#                                bandwidth budget, closed == measured bits
#                                on every trained point)
#   roofline  roofline_report    dry-run three-term roofline rows
from __future__ import annotations

import argparse
import sys
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma list: table1,curves,kernels,wire,topology,"
                         "links,serve,throughput,chaos,cluster,frontier,"
                         "roofline")
    ap.add_argument("--epochs", type=int, default=3,
                    help="epochs for the accuracy curves (CPU-sized)")
    args = ap.parse_args()
    only = set(args.only.split(",")) if args.only else None
    from repro import compile_cache
    compile_cache.enable()

    def want(name):
        return only is None or name in only

    t0 = time.time()
    if want("table1"):
        from benchmarks import bandwidth_table
        bandwidth_table.main()
        sys.stdout.flush()
    if want("kernels"):
        from benchmarks import kernel_bench
        kernel_bench.main()
        sys.stdout.flush()
    if want("wire"):
        from benchmarks import wire_bench
        wire_bench.main([])
        sys.stdout.flush()
    if want("topology"):
        from benchmarks import topology_bench
        topology_bench.main([])
        sys.stdout.flush()
    if want("links"):
        from benchmarks import links_bench
        links_bench.main([])
        sys.stdout.flush()
    if want("serve"):
        from benchmarks import serve_bench
        serve_bench.main([])
        sys.stdout.flush()
    if want("curves"):
        from benchmarks import accuracy_curves
        accuracy_curves.main(experiment=2, epochs=args.epochs)
        sys.stdout.flush()
    if want("throughput"):
        # in-process on an accelerator; on the CPU it re-executes with the
        # forced multi-device XLA flag, which must be set before jax
        # initialises (already the case here)
        from benchmarks import throughput_bench
        throughput_bench.main([])
        sys.stdout.flush()
    if want("chaos"):
        from benchmarks import chaos_bench
        chaos_bench.main(["--smoke", "--json", ""])
        sys.stdout.flush()
    if want("cluster"):
        from benchmarks import cluster_bench
        cluster_bench.main(["--smoke", "--json", ""])
        sys.stdout.flush()
    if want("frontier"):
        # keeps its JSON: CI's BENCH_*.json artifact step uploads it
        from benchmarks import frontier_bench
        frontier_bench.main(["--smoke", "--json", "BENCH_frontier.json"])
        sys.stdout.flush()
    if want("roofline"):
        from benchmarks import roofline_report
        roofline_report.main()
    print(f"# benchmarks done in {time.time()-t0:.1f}s")


if __name__ == '__main__':
    main()
