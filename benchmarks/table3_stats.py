"""Paper Table 3: per-application traffic/cache statistics.

    PYTHONPATH=src python benchmarks/table3_stats.py [--smoke] [--out f]

The paper reports request/reply/trap/redirection/dir-search/memory counts
for 5 application traces at 10,000 simulated cores.  CPU budget here runs
the same table at a configurable mesh (default 16x16; pass --rows/--cols
for larger).  Every emitted metric is a deterministic counter, so the
area gates cleanly (zero slack) wherever a baseline is committed.
"""
from __future__ import annotations

import sys

sys.path.insert(0, "src")

from repro.bench import BenchReport, Benchmark, bench_main      # noqa: E402
from repro.core.engine import enable_compile_cache              # noqa: E402
from repro.bench.collect import health_metrics                  # noqa: E402
from repro.core import SimConfig, run                           # noqa: E402
from repro.core.workloads import TRACE_APPS, app_trace          # noqa: E402

COLS = ("req_made", "req_rcvd", "reply_sent", "reply_rcvd", "trap",
        "redirection", "dir_search", "mem_req", "migrations")


def add_args(ap) -> None:
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--cols", type=int, default=16)
    ap.add_argument("--refs", type=int, default=100)


def run_bench(args) -> BenchReport:
    """Contract entry: the per-application statistics table."""
    results = {}
    print(f"{'app':10s} " + " ".join(f"{c:>10s}" for c in COLS))
    for app in TRACE_APPS:
        cfg = SimConfig(rows=args.rows, cols=args.cols, addr_bits=20,
                        centralized_directory=False, migrate_threshold=2)
        stats = run(cfg, app_trace(cfg, app, args.refs, seed=1), chunk=8)
        results[app] = stats
        print(f"{app:10s} " + " ".join(f"{stats[c]:>10d}" for c in COLS))
        assert stats["finished"] == 1, app
    rep = BenchReport("table3", meta={"params": {
        "mesh": f"{args.rows}x{args.cols}", "refs": args.refs}},
        raw=results)
    mesh = {"mesh": f"{args.rows}x{args.cols}"}
    for app, stats in results.items():
        rep.add(f"table3.{app}.cycles", stats["cycles"], unit="cycles",
                direction="lower", tags={**mesh, "app": app})
        rep.add(f"table3.{app}.traps", stats["trap"], unit="count",
                direction="lower", tags={**mesh, "app": app})
    rep.extend(health_metrics(list(results.values()), "table3.net",
                              tags=mesh))
    return rep


BENCH = Benchmark(
    area="table3",
    title="Paper Table 3: per-application traffic/cache statistics",
    add_args=add_args,
    run=run_bench,
    smoke={"rows": 8, "cols": 8, "refs": 60},
    gated=False,
)


def main(argv=None) -> BenchReport:
    enable_compile_cache()
    return bench_main(BENCH, argv)


if __name__ == "__main__":
    main()
