"""Benchmark harness: every benchmark through the one entry contract.

    PYTHONPATH=src python benchmarks/run.py --smoke --out-dir results
    PYTHONPATH=src python benchmarks/run.py --only plan,sweep,trace
    PYTHONPATH=src python benchmarks/run.py --list

Each module under ``benchmarks/`` registers a ``BENCH``
(:class:`repro.bench.contract.Benchmark`) and is invoked uniformly —
same ``--smoke/--out/--json`` flags, same ``BenchReport`` output — in a
fresh subprocess (several benchmarks must set ``XLA_FLAGS`` device
exposure *before* jax loads, which only a clean interpreter guarantees).
``--out-dir`` collects one ``BENCH_<area>.json`` per area: the files
``scripts/bench_gate.py`` diffs against the committed repo-root
baselines.
"""
from __future__ import annotations

import argparse
import os
import subprocess
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))

BENCH_DIR = Path(__file__).resolve().parent
REPO_ROOT = BENCH_DIR.parent

#: area -> benchmark file; the single source the harness, the gate and
#: CI share.  Order is execution order (cheap first).
AREA_FILES = {
    "trace": "trace_throughput.py",
    "sweep": "sweep_throughput.py",
    "plan": "plan_throughput.py",
    "fig6": "fig6_scaling.py",
    "table3": "table3_stats.py",
    "table4": "table4_memory.py",
    "paper_scale": "paper_scale.py",
}

#: areas with committed repo-root BENCH_<area>.json baselines —
#: ``scripts/bench_gate.py --smoke`` runs and diffs exactly these.
#: ``paper_scale`` also has a committed baseline but is gated by its own
#: dedicated CI job (a 43k-core mesh is minutes of work, not seconds):
#: ``bench_gate.py --smoke --areas paper_scale``.
GATED_AREAS = ("trace", "sweep", "plan", "table4")


def load_bench(area: str):
    """Import ``benchmarks/<file>`` for ``area`` and return its ``BENCH``
    registration (metadata only — running happens in a subprocess)."""
    import importlib.util
    path = BENCH_DIR / AREA_FILES[area]
    spec = importlib.util.spec_from_file_location(f"bench_{area}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    bench = mod.BENCH
    assert bench.area == area, (bench.area, area)
    return bench


def invoke(area: str, smoke: bool = False, out: str | None = None,
           extra: list[str] | None = None) -> int:
    """Run one benchmark uniformly in a subprocess; returns its exit code."""
    cmd = [sys.executable, str(BENCH_DIR / AREA_FILES[area])]
    if smoke:
        cmd.append("--smoke")
    if out:
        cmd += ["--out", out]
    cmd += extra or []
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO_ROOT / "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return subprocess.run(cmd, cwd=REPO_ROOT, env=env).returncode


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Uniform benchmark harness over the BENCH registry.")
    ap.add_argument("--only", default=None,
                    help="comma list of areas to run (default: all); "
                         "'gated' = the baseline-gated set "
                         + ",".join(GATED_AREAS))
    ap.add_argument("--smoke", action="store_true",
                    help="run every benchmark at its smoke tier")
    ap.add_argument("--out-dir", default=None, metavar="DIR",
                    help="write one BENCH_<area>.json per area here "
                         "(pass '.' to refresh the committed baselines)")
    ap.add_argument("--list", action="store_true",
                    help="list registered benchmarks and exit")
    args = ap.parse_args(argv)

    if args.list:
        for area in AREA_FILES:
            b = load_bench(area)
            mark = "*" if area in GATED_AREAS else " "
            print(f" {mark} {area:<9s} {AREA_FILES[area]:<22s} {b.title}")
        print(" (* = gated against a committed BENCH_<area>.json baseline)")
        return 0

    if args.only == "gated":
        areas = list(GATED_AREAS)
    elif args.only:
        areas = [a.strip() for a in args.only.split(",")]
        unknown = [a for a in areas if a not in AREA_FILES]
        if unknown:
            ap.error(f"unknown areas {unknown}; known: {list(AREA_FILES)}")
    else:
        areas = list(AREA_FILES)

    if args.out_dir:
        Path(args.out_dir).mkdir(parents=True, exist_ok=True)

    failed = []
    for area in areas:
        out = str(Path(args.out_dir) / f"BENCH_{area}.json") \
            if args.out_dir else None
        print(f"\n== {area} ({AREA_FILES[area]}"
              f"{', smoke' if args.smoke else ''}) ==", flush=True)
        t0 = time.time()
        rc = invoke(area, smoke=args.smoke, out=out)
        print(f"-- {area}: exit {rc} in {time.time() - t0:.1f}s --",
              flush=True)
        if rc:
            failed.append(area)
    if failed:
        print(f"\nFAILED areas: {failed}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
