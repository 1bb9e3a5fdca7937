"""Sweep-engine throughput: batched ``run_sweep`` vs a sequential ``run()`` loop.

    PYTHONPATH=src python benchmarks/sweep_throughput.py [--smoke] [--out f]

The default scenario set is a *deflection-policy sweep* (the realistic
use of a sweep engine, cf. the Ausavarungnirun-style studies): every
scenario carries a distinct (migration on/off, migrate-threshold,
centralized/distributed directory) policy.  Policy knobs are *static*
jit arguments on the solo path, so the sequential loop pays one fresh
XLA compile per distinct policy plus one device-loop dispatch per
scenario; ``run_sweep`` carries the knobs as traced per-scenario state
and pays ONE compile and ONE device loop for the whole batch.

Reported numbers:
  * cold_*: end-to-end sweep latency including compilation — the
    headline metric (a sweep is a one-shot batch job; this is what a
    user waits for, and it is where the engine's one-program design
    pays off).
  * warm_*: steady-state loop-only throughput with all compile caches
    hot.  The sweep shards its scenario axis over every core (exposed
    as XLA host devices), so the one compiled program fills the machine
    while the sequential loop runs one scenario at a time; on wide
    accelerators the same batch rides the hardware's parallel width.

The run also cross-checks that batched stats are bit-identical to the
sequential ones, so no speedup is ever bought with wrong numbers.

Emits ``BENCH_sweep.json``: gated metrics are the cold/warm speedup
ratios, the compile counts (distinct configs) and the deterministic
cycle/health counters; raw walls and scenarios/sec ride along ungated.
"""
from __future__ import annotations

import dataclasses
import sys
import time

sys.path.insert(0, "src")

# expose every core as an XLA host device BEFORE jax loads: run_sweep
# shards the scenario axis across them, so the one compiled program fills
# the machine (the sequential baseline keeps its usual single device)
from repro.core.engine import (                            # noqa: E402
    enable_compile_cache, expose_host_devices)

expose_host_devices()

from repro.bench import BenchReport, Benchmark, bench_main  # noqa: E402
from repro.bench.collect import (                           # noqa: E402
    count_metric, flag_metric, health_metrics, ratio_metric, timing_metric)
from repro.core import SimConfig                            # noqa: E402
from repro.core.sweep import (                              # noqa: E402
    ScenarioSpec, SweepSpec, run_sequential, run_sweep)


def policy_axis(n: int):
    """Migration-policy sensitivity axis: base, migration-off, then a
    fine-grained threshold scan — ``n`` *distinct* SimConfigs, i.e. ``n``
    fresh compiles on the solo path (policy knobs are static jit args
    there; the sweep engine carries them as traced state).  (A
    centralized-directory point is deliberately absent: at 256 nodes the
    node-0 hotspot blows past max_cycles, as the paper itself observes.)
    """
    pols = [dict(), dict(migration_enabled=False)]     # base: mig on, thr 3
    thr = 1
    while len(pols) < n:
        if thr != 3:                                   # 3 == base threshold
            pols.append(dict(migrate_threshold=thr))
        thr += 1
    return tuple(pols[:n])


def build_spec(cfg: SimConfig, apps, seeds, refs: int,
               n_policies: int) -> SweepSpec:
    if n_policies <= 0:
        return SweepSpec.cross(cfg, apps, seeds, refs)
    scenarios = tuple(
        ScenarioSpec(apps[i % len(apps)], seeds[i % len(seeds)], refs, **pol)
        for i, pol in enumerate(policy_axis(n_policies)))
    return SweepSpec(cfg, scenarios)


def add_args(ap) -> None:
    ap.add_argument("--rows", type=int, default=16)
    ap.add_argument("--cols", type=int, default=16)
    # default workload: one app so scenario lengths are near-uniform (the
    # batch finishes at max-of-B cycles; a straggler app would stretch it)
    # — pure policy sensitivity sweeps are the canonical use anyway.
    # equake/refs=25 is verified deadlock-free at 16x16 (see ROADMAP on
    # the protocol deadlock some (cfg, trace) combos hit).
    ap.add_argument("--apps", default="equake")
    ap.add_argument("--seeds", default="0,1")
    ap.add_argument("--refs", type=int, default=25)
    ap.add_argument("--chunk", type=int, default=8)
    ap.add_argument("--max-cycles", type=int, default=20_000,
                    help="per-scenario cycle cap: bounds the cost of a "
                         "deadlocked/saturated scenario in BOTH paths")
    ap.add_argument("--n-policies", type=int, default=32,
                    help="size of the policy sensitivity axis; 0 = plain "
                         "apps x seeds sweep with one shared policy")


def run_bench(args) -> BenchReport:
    """Contract entry: cold + warm sequential-vs-sweep comparison with
    the bit-exactness cross-check; emits ``BENCH_sweep.json`` metrics."""
    cfg = SimConfig(rows=args.rows, cols=args.cols,
                    centralized_directory=False)
    cfg = dataclasses.replace(cfg, max_cycles=args.max_cycles)
    spec = build_spec(cfg, args.apps.split(","),
                      [int(x) for x in args.seeds.split(",")],
                      args.refs, n_policies=args.n_policies)
    n_cfgs = len({sc.resolve_cfg(cfg) for sc in spec.scenarios})

    # cold: first call of each path compiles (the two paths use disjoint
    # jit cache entries — batched state shapes differ from solo ones)
    t0 = time.time()
    ref = run_sequential(spec, chunk=args.chunk)
    cold_seq = time.time() - t0
    t0 = time.time()
    got = run_sweep(spec, chunk=args.chunk)
    cold_sweep = time.time() - t0
    mismatches = [i for i, (a, b) in enumerate(zip(ref, got)) if a != b]

    # warm: loop-only, all compiles cached
    t0 = time.time()
    run_sequential(spec, chunk=args.chunk)
    warm_seq = time.time() - t0
    t0 = time.time()
    run_sweep(spec, chunk=args.chunk)
    warm_sweep = time.time() - t0

    raw = {
        "nodes": cfg.num_nodes,
        "n_scenarios": spec.size,
        "n_distinct_configs": n_cfgs,
        "refs_per_core": args.refs,
        "chunk": args.chunk,
        "bit_identical": not mismatches,
        "mismatched_scenarios": mismatches,
        "cold_sequential_s": round(cold_seq, 2),
        "cold_sweep_s": round(cold_sweep, 2),
        "cold_sequential_scenarios_per_sec": round(spec.size / cold_seq, 3),
        "cold_sweep_scenarios_per_sec": round(spec.size / cold_sweep, 3),
        "speedup": round(cold_seq / cold_sweep, 2),   # cold, end-to-end
        "warm_sequential_s": round(warm_seq, 2),
        "warm_sweep_s": round(warm_sweep, 2),
        "warm_speedup": round(warm_seq / warm_sweep, 2),
        "max_cycles_simulated": max(r["cycles"] for r in got),
        "all_finished": all(r["finished"] for r in got),
    }

    tags = {"mesh": f"{args.rows}x{args.cols}", "apps": args.apps}
    rep = BenchReport("sweep", meta={"params": {
        "refs": args.refs, "chunk": args.chunk,
        "n_policies": args.n_policies, "seeds": args.seeds}}, raw=raw)
    rep.extend([
        count_metric("sweep.n_scenarios", raw["n_scenarios"],
                     direction="higher", tags=tags),
        count_metric("sweep.n_distinct_configs", raw["n_distinct_configs"],
                     unit="compiles", direction="higher", tags=tags),
        flag_metric("sweep.bit_identical", raw["bit_identical"]),
        flag_metric("sweep.all_finished", raw["all_finished"]),
        ratio_metric("sweep.cold_speedup", raw["speedup"], tags=tags),
        ratio_metric("sweep.warm_speedup", raw["warm_speedup"], tags=tags),
        timing_metric("sweep.cold_sequential_s", raw["cold_sequential_s"]),
        timing_metric("sweep.cold_sweep_s", raw["cold_sweep_s"]),
        timing_metric("sweep.warm_sequential_s", raw["warm_sequential_s"]),
        timing_metric("sweep.warm_sweep_s", raw["warm_sweep_s"]),
        timing_metric("sweep.cold_scenarios_per_sec",
                      raw["cold_sweep_scenarios_per_sec"], unit="scen/s",
                      direction="higher", tags=tags),
        count_metric("sweep.max_cycles_simulated",
                     raw["max_cycles_simulated"], unit="cycles", tags=tags),
    ])
    rep.extend(health_metrics(got, "sweep.net", tags=tags))
    if mismatches:
        raise SystemExit("batched sweep diverged from sequential runs")
    return rep


BENCH = Benchmark(
    area="sweep",
    title="Batched policy sweep vs sequential solo loop (cold + warm)",
    add_args=add_args,
    run=run_bench,
    smoke={"rows": 8, "cols": 8, "refs": 15, "n_policies": 4},
)


def main(argv=None) -> BenchReport:
    enable_compile_cache()
    return bench_main(BENCH, argv)


if __name__ == "__main__":
    main()
