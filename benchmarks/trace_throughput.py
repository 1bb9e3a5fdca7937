"""Trace-synthesis throughput: vectorized ``app_trace`` vs the per-node loop.

    PYTHONPATH=src python benchmarks/trace_throughput.py [--smoke] [--out f]

Times the vectorized generator at the target mesh (default 256x256 =
65,536 cores) against the historical per-node-loop generator
``app_trace_loop`` (timed at a smaller mesh and extrapolated linearly —
the loop *is* linear in nodes — unless ``--full-loop`` is given), and
reports trace synthesis as a fraction of end-to-end setup (synthesis +
state init).  Emits the ``BENCH_trace.json`` report: the gated metric is
the synth *speedup* (a same-host ratio, portable across machines); raw
walls ride along ungated.
"""
from __future__ import annotations

import sys
import time

sys.path.insert(0, "src")

from repro.bench import BenchReport, Benchmark, bench_main      # noqa: E402
from repro.core.engine import enable_compile_cache              # noqa: E402
from repro.bench.collect import (                               # noqa: E402
    count_metric, ratio_metric, timing_metric)
from repro.core import SimConfig                                # noqa: E402
from repro.core.trace import app_trace, app_trace_loop          # noqa: E402


def add_args(ap) -> None:
    ap.add_argument("--trace-rows", type=int, default=256)
    ap.add_argument("--trace-cols", type=int, default=256)
    ap.add_argument("--trace-refs", type=int, default=200)
    ap.add_argument("--trace-app", default="matmul")
    ap.add_argument("--loop-rows", type=int, default=64)
    ap.add_argument("--loop-cols", type=int, default=64)
    ap.add_argument("--full-loop", action="store_true",
                    help="time the loop generator at the full target mesh "
                         "instead of extrapolating from --loop-rows/cols")


def bench_trace(args) -> dict:
    """The measurement (kept payload-shaped for reuse): vectorized synth
    at the ``args`` target mesh, loop synth (extrapolated), state init."""
    from repro.core.state import init_state
    cfg = SimConfig(rows=args.trace_rows, cols=args.trace_cols,
                    centralized_directory=False)
    t0 = time.time()
    tr = app_trace(cfg, args.trace_app, args.trace_refs, seed=0)
    vec_s = time.time() - t0

    t0 = time.time()
    s = init_state(cfg, tr)
    s.st.block_until_ready()
    init_s = time.time() - t0

    if args.full_loop:
        loop_cfg, scale = cfg, 1.0
    else:
        loop_cfg = SimConfig(rows=args.loop_rows, cols=args.loop_cols,
                             centralized_directory=False)
        scale = cfg.num_nodes / loop_cfg.num_nodes
    t0 = time.time()
    app_trace_loop(loop_cfg, args.trace_app, args.trace_refs, seed=0)
    loop_s = (time.time() - t0) * scale

    return {
        "nodes": cfg.num_nodes,
        "refs_per_core": args.trace_refs,
        "vectorized_synth_s": round(vec_s, 3),
        "loop_synth_s" + ("" if args.full_loop else "_extrapolated"):
            round(loop_s, 3),
        "synth_speedup": round(loop_s / vec_s, 1),
        "state_init_s": round(init_s, 3),
        "trace_fraction_of_setup": round(vec_s / (vec_s + init_s), 3),
        "loop_trace_fraction_of_setup": round(loop_s / (loop_s + init_s), 3),
    }


def run_bench(args) -> BenchReport:
    """Contract entry: run :func:`bench_trace`, emit the report."""
    raw = bench_trace(args)
    tags = {"mesh": f"{args.trace_rows}x{args.trace_cols}",
            "app": args.trace_app}
    rep = BenchReport("trace", meta={
        "params": {"refs": args.trace_refs,
                   "loop_mesh": f"{args.loop_rows}x{args.loop_cols}",
                   "full_loop": bool(args.full_loop)}}, raw=raw)
    rep.add("trace.nodes", raw["nodes"], unit="cores", direction="higher",
            tags=tags)
    rep.extend([
        # extra slack: the smoke-tier vectorized synth is ~0.05s, so the
        # ratio is noisy — the gate only needs to catch a collapse back
        # toward loop speed (speedup ~1), not a 30% wobble
        ratio_metric("trace.synth_speedup", raw["synth_speedup"],
                     slack=0.7, tags=tags),
        timing_metric("trace.vectorized_synth_s",
                      raw["vectorized_synth_s"], tags=tags),
        timing_metric("trace.state_init_s", raw["state_init_s"], tags=tags),
        timing_metric(
            "trace.refs_per_sec",
            raw["nodes"] * args.trace_refs / raw["vectorized_synth_s"],
            unit="refs/s", direction="higher", tags=tags),
        ratio_metric("trace.fraction_of_setup",
                     raw["trace_fraction_of_setup"], unit="ratio",
                     direction="lower", gate=False, tags=tags),
    ])
    return rep


BENCH = Benchmark(
    area="trace",
    title="Vectorized trace synthesis vs the per-node loop generator",
    add_args=add_args,
    run=run_bench,
    smoke={"trace_rows": 64, "trace_cols": 64, "trace_refs": 50,
           "loop_rows": 16, "loop_cols": 16},
)


def main(argv=None) -> BenchReport:
    enable_compile_cache()
    return bench_main(BENCH, argv)


if __name__ == "__main__":
    main()
