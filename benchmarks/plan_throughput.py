"""Execution-plan benchmarks: planned sweeps, backend shoot-out, wedge.

    PYTHONPATH=src python benchmarks/plan_throughput.py [--smoke] [--out f]

Part 1 — planned mixed-shape sweep: a manifest mixing two mesh shapes runs
through ``compile_plan``/``execute_plan`` (one compiled program per shape
bucket) vs the same scenarios as sequential solo ``run()`` calls, with a
bit-exactness cross-check, so no speedup is ever bought with wrong numbers.

Part 2 — backend shoot-out: ONE bucket (B scenarios of one mesh shape)
forced through each backend — vmapped ``sweep``, spatial ``sharded``
(B sequential spatial runs), composed ``scenario x row x col`` — on this
host's devices, with wall-clock per backend, the planner's own pick, and
a cross-backend bit-equality check.  Backends that are structurally
impossible here (one device, indivisible mesh) degrade to ``sweep`` and
are reported with the planner's note.

Part 3 — the former S14 ejection-bar wedge (16x16 / loop:matmul / seed 0
/ refs 20) as a tracked scenario: its completion cycles/time and drop
counts are gated so the livelock fix can never silently rot.

Emits ``BENCH_plan.json``: gated metrics are the deterministic counters
(bucket/compile counts, wedge completion cycles, drops) plus the
plan-vs-sequential speedup ratio; raw walls and per-backend
scenarios/sec ride along ungated.  (Trace synthesis moved to
``benchmarks/trace_throughput.py`` / ``BENCH_trace.json``.)
"""
from __future__ import annotations

import sys
import time

sys.path.insert(0, "src")

from repro.core import engine                              # noqa: E402

engine.expose_host_devices()   # before anything imports jax

from repro.bench import BenchReport, Benchmark, bench_main  # noqa: E402
from repro.bench.collect import (                           # noqa: E402
    count_metric, flag_metric, health_metrics, ratio_metric, timing_metric)
from repro.core import SimConfig, run                       # noqa: E402
from repro.core.workloads import app_trace_loop, resolve_trace  # noqa: E402


def bench_plan(args) -> dict:
    base = SimConfig(centralized_directory=False, max_cycles=args.max_cycles)
    seeds = range(args.seeds_per_shape)
    scenarios = [engine.make_scenario(base, r, c, args.app, s, args.refs)
                 for (r, c) in ((args.rows_a, args.cols_a),
                                (args.rows_b, args.cols_b))
                 for s in seeds]

    t0 = time.time()
    ref = []
    for sc in scenarios:
        tr = resolve_trace(sc.cfg, sc.app, sc.refs_per_core, sc.seed)
        ref.append(run(sc.cfg, tr, chunk=args.chunk))
    seq_s = time.time() - t0

    plan = engine.compile_plan(scenarios)
    t0 = time.time()
    got = engine.execute_plan(plan, chunk=args.chunk)
    plan_s = time.time() - t0
    mismatches = [i for i, (a, b) in enumerate(zip(ref, got)) if a != b]

    return {
        "plan": plan.describe(),
        "n_scenarios": len(scenarios),
        "bit_identical": not mismatches,
        "mismatched_scenarios": mismatches,
        "sequential_s": round(seq_s, 2),
        "planned_s": round(plan_s, 2),
        "speedup": round(seq_s / plan_s, 2),
        "all_finished": all(r.get("finished") for r in got),
        "scenario_stats": got,
    }


def bench_backends(args) -> dict:
    """Force one bucket through sweep / sharded / composed and compare."""
    import jax
    base = SimConfig(rows=args.bk_rows, cols=args.bk_cols,
                     centralized_directory=False,
                     max_cycles=args.max_cycles)
    scenarios = [engine.make_scenario(base, app=args.app, seed=s,
                                      refs_per_core=args.refs)
                 for s in range(args.bk_batch)]
    out = {"rows": args.bk_rows, "cols": args.bk_cols,
           "batch": args.bk_batch, "devices": len(jax.devices())}
    results = {}
    for force in ("sweep", "sharded", "composed"):
        if force == "sharded":
            # sharded has no batch axis: B sequential spatial plans
            plans = [engine.compile_plan([sc], force_backend="sharded")
                     for sc in scenarios]
        else:
            plans = [engine.compile_plan(scenarios, force_backend=force)]
        # warm compile caches out of the timed region
        for p in plans:
            engine.execute_plan(p, chunk=args.chunk,
                                sharded_chunk=args.sharded_chunk)
        t0 = time.time()
        res = []
        for p in plans:
            res.extend(engine.execute_plan(p, chunk=args.chunk,
                                           sharded_chunk=args.sharded_chunk))
        dt = time.time() - t0
        b0 = plans[0].buckets[0]
        out[force] = {
            "wall_s": round(dt, 2),
            "scenarios_per_sec": round(len(scenarios) / dt, 3),
            "effective_backend": b0.backend,
            **({"grid": list(b0.grid)} if b0.backend != "sweep" else {}),
            **({"note": b0.note} if b0.note else {}),
        }
        results[force] = res
    auto = engine.compile_plan(scenarios).buckets[0]
    out["planner_pick"] = auto.backend
    # sharded runs with dir_layout="home"; healthy stats are still
    # bit-identical across backends, which is the point of the check
    out["bit_identical_across_backends"] = (
        results["sweep"] == results["sharded"] == results["composed"])
    return out


def bench_wedge(args) -> dict:
    """The former S14 ejection-bar wedge (ROADMAP: 16x16 / matmul / seed 0
    / refs 20, loop-trace generator) as a tracked scenario: with the
    pending-completion queue it *completes*, so the perf trajectory now
    records its completion time (cycles + wall) instead of an abort time.
    The pc_depth=1 escape hatch is timed next to it for the abort
    baseline."""
    cfg = SimConfig(rows=16, cols=16, centralized_directory=False,
                    max_cycles=max(args.max_cycles, 200_000))
    sc = engine.make_scenario(cfg, app="loop:matmul", seed=0,
                              refs_per_core=20)
    plan = engine.compile_plan([sc])
    engine.execute_plan(plan, chunk=16)          # warm the compile cache
    t0 = time.time()
    (st,) = engine.execute_plan(plan, chunk=16)
    wall = time.time() - t0

    import dataclasses
    cfg1 = dataclasses.replace(cfg, pc_depth=1, livelock_window=256,
                               max_cycles=30_000)
    tr = app_trace_loop(cfg1, "matmul", 20, 0)
    run(cfg1, tr, chunk=16)                      # warm
    t0 = time.time()
    st1 = run(cfg1, tr, chunk=16)
    wall1 = time.time() - t0

    return {
        "scenario": "16x16/loop:matmul/seed0/refs20 (former ROADMAP wedge)",
        "finished": bool(st.get("finished")),
        "completion_cycles": st.get("cycles"),
        "completion_wall_s": round(wall, 2),
        "send_drops_recovered": st.get("send_drop"),
        "stray_responses": st.get("stray"),
        "stats": st,
        "pc_depth_1_baseline": {
            "aborted": st1.get("aborted"),
            "abort_cycles": st1.get("cycles"),
            "abort_wall_s": round(wall1, 2),
        },
    }


def add_args(ap) -> None:
    ap.add_argument("--skip-plan", action="store_true")
    ap.add_argument("--skip-backends", action="store_true")
    ap.add_argument("--skip-wedge", action="store_true")
    ap.add_argument("--bk-rows", type=int, default=16)
    ap.add_argument("--bk-cols", type=int, default=16)
    ap.add_argument("--bk-batch", type=int, default=4,
                    help="scenarios in the backend shoot-out bucket")
    ap.add_argument("--sharded-chunk", type=int, default=64)
    ap.add_argument("--rows-a", type=int, default=8)
    ap.add_argument("--cols-a", type=int, default=8)
    ap.add_argument("--rows-b", type=int, default=16)
    ap.add_argument("--cols-b", type=int, default=16)
    ap.add_argument("--seeds-per-shape", type=int, default=3)
    ap.add_argument("--app", default="equake",
                    help="equake/refs=25 is verified deadlock-free at 16x16")
    ap.add_argument("--refs", type=int, default=25)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--max-cycles", type=int, default=20_000)


def run_bench(args) -> BenchReport:
    """Contract entry: run the three parts, emit ``BENCH_plan.json``
    metrics, and hard-fail on any cross-check divergence."""
    rep = BenchReport("plan", meta={"params": {
        "shapes": [f"{args.rows_a}x{args.cols_a}",
                   f"{args.rows_b}x{args.cols_b}"],
        "seeds_per_shape": args.seeds_per_shape, "app": args.app,
        "refs": args.refs, "bk_batch": args.bk_batch,
        "bk_mesh": f"{args.bk_rows}x{args.bk_cols}"}})

    if not args.skip_plan:
        p = bench_plan(args)
        stats = p.pop("scenario_stats")
        rep.raw["planned_sweep"] = p
        tags = {"app": args.app}
        rep.extend([
            count_metric("plan.n_scenarios", p["n_scenarios"],
                         direction="higher", tags=tags),
            count_metric("plan.n_buckets", p["plan"]["n_buckets"],
                         unit="compiles", tags=tags),
            flag_metric("plan.bit_identical", p["bit_identical"]),
            flag_metric("plan.all_finished", p["all_finished"]),
            timing_metric("plan.sequential_s", p["sequential_s"]),
            timing_metric("plan.planned_s", p["planned_s"]),
            ratio_metric("plan.speedup", p["speedup"], tags=tags),
        ])
        rep.extend(health_metrics(stats, "plan.net", tags=tags))

    if not args.skip_backends:
        b = bench_backends(args)
        rep.raw["backend_shootout"] = b
        tags = {"mesh": f"{args.bk_rows}x{args.bk_cols}",
                "batch": str(args.bk_batch)}
        for backend in ("sweep", "sharded", "composed"):
            rep.add(f"plan.backend.{backend}.scenarios_per_sec",
                    b[backend]["scenarios_per_sec"], unit="scen/s",
                    direction="higher", gate=False,
                    tags={**tags,
                          "effective": b[backend]["effective_backend"]})
        rep.extend([
            flag_metric("plan.backend.bit_identical",
                        b["bit_identical_across_backends"]),
            count_metric("plan.backend.devices", b["devices"],
                         unit="devices", direction="higher", gate=False),
        ])

    if not args.skip_wedge:
        w = bench_wedge(args)
        wstats = w.pop("stats")
        rep.raw["livelock_wedge"] = w
        tags = {"scenario": "16x16/loop:matmul/0/20"}
        rep.extend([
            flag_metric("plan.wedge.finished", w["finished"], tags=tags),
            count_metric("plan.wedge.completion_cycles",
                         w["completion_cycles"], unit="cycles", tags=tags),
            timing_metric("plan.wedge.completion_wall_s",
                          w["completion_wall_s"], tags=tags),
        ])
        rep.extend(health_metrics([wstats], "plan.wedge.net", tags=tags))

    if not args.skip_plan and \
            rep.raw["planned_sweep"]["mismatched_scenarios"]:
        raise SystemExit("planned sweep diverged from sequential runs")
    if not args.skip_backends and \
            not rep.raw["backend_shootout"]["bit_identical_across_backends"]:
        raise SystemExit("backends diverged on the same scenarios")
    if not args.skip_wedge and not rep.raw["livelock_wedge"]["finished"]:
        raise SystemExit("former wedge scenario no longer completes")
    return rep


BENCH = Benchmark(
    area="plan",
    title="Execution-plan layer: mixed-shape sweep, backend shoot-out, "
          "wedge completion",
    add_args=add_args,
    run=run_bench,
    smoke={"rows_a": 4, "cols_a": 4, "rows_b": 8, "cols_b": 8,
           "seeds_per_shape": 2, "refs": 10, "bk_rows": 8, "bk_cols": 8,
           "bk_batch": 2},
)


def main(argv=None) -> BenchReport:
    engine.enable_compile_cache()
    return bench_main(BENCH, argv)


if __name__ == "__main__":
    main()
