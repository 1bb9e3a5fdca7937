"""Paper Figure 6: serial vs parallel simulation wall time vs core count.

    PYTHONPATH=src python benchmarks/fig6_scaling.py [--smoke] [--out f]

The paper: serial C++ grows rapidly with core count; the GPU version is
~25x faster at 2,000 cores.  Here: serial numpy golden model vs the
vectorized JAX simulator on the same host.  Trace length follows the paper
(N x M references, M fixed), so work grows with core count.  The gated
metric per mesh size is the serial/vector *speedup* (same-host ratio)
plus the deterministic completion cycles; raw walls ride along ungated.
"""
from __future__ import annotations

import sys
import time

sys.path.insert(0, "src")

from repro.bench import BenchReport, Benchmark, bench_main      # noqa: E402
from repro.core.engine import enable_compile_cache              # noqa: E402
from repro.bench.collect import (                               # noqa: E402
    count_metric, ratio_metric, timing_metric)
from repro.core import SimConfig, run                           # noqa: E402
from repro.core.ref_serial import SerialSim                     # noqa: E402
from repro.core.workloads import app_trace                      # noqa: E402


def one(rows: int, cols: int, refs: int, serial_limit: int):
    cfg = SimConfig(rows=rows, cols=cols, addr_bits=18,
                    centralized_directory=False)
    tr = app_trace(cfg, "matmul", refs, seed=1)
    n = cfg.num_nodes

    run(cfg, tr, chunk=8)                 # warm the compile cache
    t0 = time.time()
    stats = run(cfg, tr, chunk=8)
    t_vec = time.time() - t0

    t_ser = None
    if n <= serial_limit:
        t0 = time.time()
        SerialSim(cfg, tr).run()
        t_ser = time.time() - t0
    return {"cores": n, "cycles": stats["cycles"], "vector_s": round(t_vec, 2),
            "serial_s": round(t_ser, 2) if t_ser else None,
            "speedup": round(t_ser / t_vec, 1) if t_ser else None}


def parse_sizes(text: str):
    """``"4x4,8x8"`` → ``[(4, 4), (8, 8)]``."""
    out = []
    for item in text.split(","):
        r, c = item.lower().split("x")
        out.append((int(r), int(c)))
    return out


def add_args(ap) -> None:
    ap.add_argument("--sizes", default="4x4,8x8,16x16,32x32",
                    help="comma list of ROWSxCOLS mesh sizes to scale over")
    ap.add_argument("--refs", type=int, default=50)
    ap.add_argument("--serial-limit", type=int, default=300,
                    help="skip the serial golden model above this many cores")


def run_bench(args) -> BenchReport:
    """Contract entry: one row per mesh size, serial-vs-vector."""
    rows = []
    print(f"{'cores':>7s} {'cycles':>8s} {'vector_s':>9s} {'serial_s':>9s} "
          f"{'speedup':>8s}")
    for r, c in parse_sizes(args.sizes):
        res = one(r, c, args.refs, args.serial_limit)
        rows.append(res)
        print(f"{res['cores']:>7d} {res['cycles']:>8d} "
              f"{res['vector_s']:>9.2f} "
              f"{res['serial_s'] if res['serial_s'] else '—':>9} "
              f"{res['speedup'] if res['speedup'] else '—':>8}")
    rep = BenchReport("fig6", meta={"params": {
        "sizes": args.sizes, "refs": args.refs,
        "serial_limit": args.serial_limit}}, raw={"rows": rows})
    for res in rows:
        tag = {"cores": str(res["cores"])}
        rep.extend([
            count_metric(f"fig6.{res['cores']}.cycles", res["cycles"],
                         unit="cycles", tags=tag),
            timing_metric(f"fig6.{res['cores']}.vector_s", res["vector_s"],
                          tags=tag),
        ])
        if res["speedup"]:
            rep.extend([ratio_metric(f"fig6.{res['cores']}.speedup",
                                     res["speedup"], tags=tag)])
    return rep


BENCH = Benchmark(
    area="fig6",
    title="Paper Fig. 6: serial golden model vs vectorized sim scaling",
    add_args=add_args,
    run=run_bench,
    smoke={"sizes": "4x4,8x8", "refs": 30},
    gated=False,
)


def main(argv=None) -> BenchReport:
    enable_compile_cache()
    return bench_main(BENCH, argv)


if __name__ == "__main__":
    main()
