"""NoC-simulation launcher (the paper's workload).

    PYTHONPATH=src python -m repro.launch.simulate --rows 16 --cols 16 \
        --app matmul --refs 100

Every mode (except ``--serial``) routes through the execution-plan layer
(:mod:`repro.core.engine`): scenarios are bucketed by structural config,
each bucket compiles once, and a cost model picks the batched-sweep,
spatially-sharded or composed backend per bucket (see
``docs/architecture.md``).

Batched multi-scenario sweep (one compiled program for all scenarios):
    ... --sweep --apps matmul,equake,mgrid --seeds 0,1
Force a backend for any planner mode (each degrades to ``sweep`` with an
explanatory note when structurally impossible on this host):
    ... --backend sharded
    ... --sweep --apps matmul --seeds 0,1,2,3 --backend composed
Heterogeneous plan — mixed mesh shapes/apps/knobs from a manifest (a JSON
file, inline JSON, or the compact ROWSxCOLS[:APP][:SEED[:REFS]] grammar;
APP is any workload-registry source spec):
    ... --plan manifest.json
    ... --plan '8x8:matmul:0:50;16x16:equake:1:50'
    ... --plan '8x8:hotspot:frac=0.8,hot=2:0:50'
Scenario zoo — run a registered family (repro.core.zoo) end to end:
    ... --zoo patterns-small
    ... --zoo patterns-tiny:refs=8,seeds=0
    ... --zoo list

``docs/cli.md`` is generated from this parser by
``scripts/gen_cli_docs.py`` (CI fails on drift) — keep flag help strings
self-contained.  The ``--app`` help and error text are generated from
the traffic-generator registry, so new generators appear automatically.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
import warnings

from repro.core.workloads import source_summary

BACKENDS = ("auto", "sweep", "sharded", "composed")


def build_parser() -> argparse.ArgumentParser:
    """The launcher's argparse tree (also the source of ``docs/cli.md``)."""
    ap = argparse.ArgumentParser(
        prog="python -m repro.launch.simulate",
        description="Bufferless-NoC simulator launcher: solo runs, batched "
                    "sweeps and heterogeneous execution plans, all through "
                    "the repro.core.engine planner.")
    ap.add_argument("--rows", type=int, default=16,
                    help="simulated mesh rows")
    ap.add_argument("--cols", type=int, default=16,
                    help="simulated mesh columns")
    ap.add_argument("--app", default="matmul",
                    help="workload source spec, dispatched through the "
                         "traffic-generator registry (repro.core.workloads); "
                         + source_summary())
    ap.add_argument("--refs", type=int, default=100,
                    help="memory references per core")
    ap.add_argument("--seed", type=int, default=0,
                    help="trace-synthesis seed")
    ap.add_argument("--centralized", action="store_true",
                    help="paper-default centralized directory (hot spot!)")
    ap.add_argument("--no-migration", action="store_true",
                    help="disable L2 block migration")
    ap.add_argument("--pc-depth", type=int, default=None,
                    help="pending-completion queue depth per node (default: "
                         "SimConfig.pc_depth).  1 = the paper's single S14 "
                         "completion register (can livelock under S14 "
                         "backpressure); >1 enables the ejection guarantee "
                         "(docs/architecture.md)")
    ap.add_argument("--eject-age-threshold", type=int, default=None,
                    help="guaranteed-ejection age threshold (default: "
                         "SimConfig.eject_age_threshold): with an occupied "
                         "pending-completion queue, only flits that have "
                         "deflected at least this many times eject into the "
                         "spare capacity")
    ap.add_argument("--pallas-router", action="store_true",
                    help="run phase-2 arbitration through the Pallas router "
                         "kernel (interpret mode off-TPU) instead of the "
                         "XLA reference oracle")
    ap.add_argument("--serial", action="store_true",
                    help="run the golden-model serial simulator instead of "
                         "the planner")
    ap.add_argument("--backend", choices=BACKENDS, default="auto",
                    help="pin the planner's backend for every bucket: "
                         "'sweep' (vmapped scenario batch), 'sharded' (2-D "
                         "spatial shard_map), 'composed' (batched shard_map "
                         "over a scenario x rows x cols device mesh); "
                         "'auto' lets the cost model choose.  A pinned "
                         "backend that is structurally impossible degrades "
                         "to sweep with a note")
    ap.add_argument("--sharded", action="store_true",
                    help="DEPRECATED legacy alias for --backend sharded "
                         "(emits a DeprecationWarning; will be removed)")
    ap.add_argument("--sweep", action="store_true",
                    help="batched sweep mode: run the --apps x --seeds "
                         "cross-product as one plan (default backend: "
                         "sweep; combine with --backend to override)")
    ap.add_argument("--plan", default=None, metavar="MANIFEST",
                    help="scenario manifest: JSON file path, inline JSON, or "
                         "compact 'ROWSxCOLS[:APP][:SEED[:REFS]];...' items "
                         "(APP = any registry source spec); mixed mesh "
                         "shapes allowed (repro.core.engine)")
    ap.add_argument("--zoo", default=None, metavar="FAMILY",
                    help="run a registered scenario-zoo family "
                         "(repro.core.zoo) through the planner: 'FAMILY' or "
                         "'FAMILY:refs=N,seeds=0+1,meshes=4x4+8x8'; "
                         "'--zoo list' prints the registered families and "
                         "exits")
    ap.add_argument("--apps", default=None,
                    help="comma list of apps for --sweep (default: --app)")
    ap.add_argument("--seeds", default=None,
                    help="comma list of seeds for --sweep (default: --seed)")
    ap.add_argument("--state-dtype", choices=("wide", "packed"),
                    default="wide",
                    help="SimState storage layout: 'wide' stores every "
                         "field as int32; 'packed' narrows each field to "
                         "the smallest dtype its config-derived bounds "
                         "allow (int8/int16), roughly halving resident "
                         "state bytes with bit-identical results (compute "
                         "still happens in int32; see docs/architecture.md)")
    ap.add_argument("--mem-budget", default=None, metavar="BYTES",
                    help="per-device resident-state budget for the planner "
                         "(bytes, optional K/M/G/T suffix, e.g. '512M'; "
                         "default: $REPRO_MEM_BUDGET or unlimited).  "
                         "Candidate backends over budget are dropped — "
                         "composed re-tiles toward deeper spatial splits — "
                         "and a plan that cannot fit fails fast with the "
                         "required bytes in the error")
    ap.add_argument("--chunk", type=int, default=8,
                    help="simulated cycles per device-loop termination check")
    ap.add_argument("--max-cycles", type=int, default=200_000,
                    help="hard cycle cap per scenario")
    ap.add_argument("--json", default=None,
                    help="also write the result payload to this file")
    return ap


def main(argv=None) -> None:
    """Launcher entry point; ``argv`` defaults to ``sys.argv[1:]``
    (injectable for tests)."""
    ap = build_parser()
    args = ap.parse_args(argv)

    if args.sharded:
        warnings.warn(
            "--sharded is deprecated and will be removed; "
            "use --backend sharded instead",
            DeprecationWarning, stacklevel=2)
        print("warning: --sharded is deprecated; use --backend sharded",
              file=sys.stderr)

    if args.zoo == "list":
        from repro.core.zoo import zoo_summary
        print(zoo_summary())
        return

    modes = [m for m in ("serial", "sweep", "plan", "zoo") if getattr(args, m)]
    if len(modes) > 1:
        ap.error(f"choose at most one of --serial/--sweep/--plan/--zoo "
                 f"(got {modes})")
    if args.serial and (args.sharded or args.backend != "auto"):
        ap.error("--serial does not route through the planner; "
                 "--backend/--sharded do not apply")
    if args.sharded and args.backend not in ("auto", "sharded"):
        ap.error(f"--sharded conflicts with --backend {args.backend}")

    from repro.core import SimConfig
    kw = {}
    if args.pc_depth is not None:
        kw["pc_depth"] = args.pc_depth
    if args.eject_age_threshold is not None:
        kw["eject_age_threshold"] = args.eject_age_threshold
    cfg = SimConfig(rows=args.rows, cols=args.cols,
                    centralized_directory=args.centralized,
                    migration_enabled=not args.no_migration,
                    max_cycles=args.max_cycles,
                    use_pallas_router=args.pallas_router,
                    state_dtype_policy=args.state_dtype, **kw)

    if args.serial:
        from repro.core.ref_serial import SerialSim
        from repro.core.trace import resolve_trace
        tr = resolve_trace(cfg, args.app, args.refs, args.seed)
        t0 = time.time()
        stats = SerialSim(cfg, tr).run()
        stats["wall_s"] = round(time.time() - t0, 2)
        stats["nodes"] = cfg.num_nodes
        print(json.dumps(stats, indent=1))
        if args.json:
            with open(args.json, "w") as f:
                json.dump(stats, f)
        return

    from repro.core import engine
    if args.sweep or args.plan or args.zoo:
        engine.expose_host_devices()
    engine.enable_compile_cache()

    force = args.backend if args.backend != "auto" else None
    if args.sharded:
        force = "sharded"
    if args.zoo:
        from repro.core.zoo import expand_zoo
        scenarios = expand_zoo(args.zoo, base=cfg)
    elif args.plan:
        scenarios = engine.load_manifest(args.plan, base=cfg)
    elif args.sweep:
        apps = (args.apps or args.app).split(",")
        seeds = [int(x) for x in (args.seeds or str(args.seed)).split(",")]
        scenarios = [engine.make_scenario(cfg, app=a, seed=s,
                                          refs_per_core=args.refs)
                     for a in apps for s in seeds]
        force = force or "sweep"
    else:
        scenarios = [engine.make_scenario(cfg, app=args.app, seed=args.seed,
                                          refs_per_core=args.refs)]

    plan = engine.compile_plan(
        scenarios, force_backend=force,
        mem_budget=engine.parse_mem_budget(args.mem_budget))
    t0 = time.time()
    per_scenario = engine.execute_plan(plan, chunk=args.chunk)
    dt = time.time() - t0

    # payload schema follows the *mode*, not the scenario count: --sweep,
    # --plan and --zoo always emit the {plan, scenarios, ...} form, even
    # for a single scenario
    if not (args.sweep or args.plan or args.zoo):
        payload = dict(per_scenario[0])
        payload["wall_s"] = round(dt, 2)
        payload["nodes"] = scenarios[0].cfg.num_nodes
        payload["backend"] = plan.buckets[0].backend
        if plan.buckets[0].note:
            payload["backend_note"] = plan.buckets[0].note
    else:
        payload = {
            **({"zoo": args.zoo} if args.zoo else {}),
            "plan": plan.describe(),
            "scenarios": [
                {"rows": sc.cfg.rows, "cols": sc.cfg.cols, "app": sc.app,
                 "seed": sc.seed, **st}
                for sc, st in zip(scenarios, per_scenario)],
            "n_scenarios": len(scenarios),
            "wall_s": round(dt, 2),
            "scenarios_per_sec": round(len(scenarios) / dt, 3),
        }
    print(json.dumps(payload, indent=1))
    if args.json:
        with open(args.json, "w") as f:
            json.dump(payload, f)


if __name__ == "__main__":
    main()
