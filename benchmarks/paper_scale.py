"""Paper-scale smoke: the 208x208 (43,264-core) mesh end to end.

    PYTHONPATH=src python benchmarks/paper_scale.py [--smoke] [--out f]

The source paper's headline is simulating a 43k-core bufferless mesh
within one GTX 690's memory.  This benchmark runs that exact mesh shape
through the dense driver under the ``packed`` state-dtype policy — the
layout that makes the footprint practical — for a small, fixed number of
cycles, and gates on *completion*: the run must reach the cycle cap
without aborting.  A capped run is deliberate: CI measures that the
paper-scale state allocates, compiles and steps on a CPU host in
minutes; full-length runs belong on real accelerators.

Gated metrics: the completion flag and the analytic bytes/node under
both dtype policies at this exact config (any state growth at paper
scale shows up here).  Wall-clock and throughput are reported ungated —
CI hosts vary.
"""
from __future__ import annotations

import sys
import time

sys.path.insert(0, "src")

import numpy as np                                              # noqa: E402

from repro.bench import BenchReport, Benchmark, bench_main      # noqa: E402
from repro.core.engine import enable_compile_cache              # noqa: E402
from repro.core import SimConfig                                # noqa: E402
from repro.core.state import state_bytes                        # noqa: E402


def add_args(ap) -> None:
    ap.add_argument("--rows", type=int, default=208,
                    help="mesh rows (paper scale: 208)")
    ap.add_argument("--cols", type=int, default=208,
                    help="mesh columns (paper scale: 208)")
    ap.add_argument("--max-cycles", type=int, default=64,
                    help="cycle cap for the completion smoke")
    ap.add_argument("--refs", type=int, default=8,
                    help="memory references per core")
    ap.add_argument("--policy", choices=("packed", "wide"),
                    default="packed",
                    help="state-dtype policy to run under")


def run_bench(args) -> BenchReport:
    """Contract entry: run the paper-scale mesh to its cycle cap."""
    from repro.core import sim
    from repro.core.workloads import random_trace

    cfg = SimConfig(rows=args.rows, cols=args.cols,
                    max_cycles=args.max_cycles,
                    centralized_directory=False, dir_layout="home",
                    state_dtype_policy=args.policy)
    n = cfg.num_nodes
    bw = state_bytes(cfg, trace_len=args.refs, policy="wide") // n
    bp = state_bytes(cfg, trace_len=args.refs, policy="packed") // n
    print(f"{args.rows}x{args.cols} = {n:,} cores, {args.refs} refs/core, "
          f"cap {args.max_cycles} cycles, policy={args.policy}")
    print(f"state bytes/node: wide {bw}  packed {bp} "
          f"(total {args.policy}: "
          f"{(bp if args.policy == 'packed' else bw) * n / 2**20:.0f} MiB)")

    tr = random_trace(cfg, refs_per_core=args.refs, seed=0)
    t0 = time.time()
    r = sim.run(cfg, tr, max_cycles=args.max_cycles, chunk=args.max_cycles)
    wall = time.time() - t0
    completed = int("aborted" not in r
                    and (r["cycles"] == args.max_cycles or r["finished"] == n))
    print(f"ran {r['cycles']} cycles in {wall:.1f}s "
          f"({'completed' if completed else 'ABORTED: ' + str(r.get('aborted'))}, "
          f"{r['flits_delivered']:,} flits delivered)")

    rep = BenchReport("paper_scale", raw={
        "rows": args.rows, "cols": args.cols, "nodes": n,
        "refs": args.refs, "policy": args.policy, "wall_s": round(wall, 2),
        "stats": {k: int(v) for k, v in r.items() if isinstance(v, int)}})
    tags = {"mesh": f"{args.rows}x{args.cols}", "policy": args.policy}
    rep.add("paper_scale.completed", completed, unit="flag",
            direction="higher", tags=tags)
    rep.add("paper_scale.state_bytes_per_node.wide", bw, unit="B/node",
            direction="lower", tags={"mesh": tags["mesh"]})
    rep.add("paper_scale.state_bytes_per_node.packed", bp, unit="B/node",
            direction="lower", tags={"mesh": tags["mesh"]})
    rep.add("paper_scale.wall_s", round(wall, 2), unit="s",
            direction="lower", gate=False, tags=tags)
    rep.add("paper_scale.node_cycles_per_sec",
            round(n * r["cycles"] / wall), unit="node*cyc/s",
            direction="higher", gate=False, tags=tags)
    return rep


BENCH = Benchmark(
    area="paper_scale",
    title="Paper-scale smoke: 208x208 (43k cores) completes under packed "
          "state",
    add_args=add_args,
    run=run_bench,
    smoke={"max_cycles": 32},
    gated=True,
)


def main(argv=None) -> BenchReport:
    enable_compile_cache()
    return bench_main(BENCH, argv)


if __name__ == "__main__":
    main()
