#!/usr/bin/env python3
"""Read the control at a cell's own size: the numbers a run compares,
with the control put in the program's place.

    python3 chipbench/control_readings.py --workload paper208-equake \\
        --seeds 7001 7002 7003

For each seed it builds the answers a run of the cell would check (in
``advance`` mode the statistics after the window's slice
``check_slice``; in
``batches`` mode the eight lanes of the window's first batch), computes
them with the reference and with the control
(:mod:`chipbench.reference.control`), and prints the comparison the
harness would make, one JSON line per seed.  Host code only: it needs no
chip.  ``PERF.md`` gives the readings the limits were set from.
"""
from __future__ import annotations

import argparse
import json
import sys
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT)]

from chipbench import harness  # noqa: E402

#: reference and control runs at a time, each a serial Python process
WORKERS = 8


def answers_of(cell: harness.Cell, seed: int):
    trf = cell.traffic
    refs = int(trf["refs_per_core"])
    if trf["mode"] == "advance":
        (src,) = trf["sources"]
        return [dict(source=src, seed=seed, refs=refs,
                     max_cycles=(1 + int(trf["check_slice"]))
                     * int(trf["slice_cycles"]))]
    per = int(trf["seeds_per_batch"])
    return [dict(source=src, seed=per + j,
                 refs=refs, max_cycles=None)
            for src in trf["sources"] for j in range(per)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    sim = cell.config["sim"]
    jobs = [(seed, a, control) for seed in args.seeds
            for a in answers_of(cell, seed) for control in (False, True)]
    with ProcessPoolExecutor(WORKERS,
                             mp_context=get_context("spawn")) as pool:
        futs = [pool.submit(harness.reference_stats, sim, a["source"],
                            a["seed"], a["refs"], a["max_cycles"], control)
                for _, a, control in jobs]
        got = [f.result() for f in futs]
    for seed in args.seeds:
        ref = [g for (s, _, c), g in zip(jobs, got) if s == seed and not c]
        ctl = [g for (s, _, c), g in zip(jobs, got) if s == seed and c]
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": harness.compare(ctl, ref),
                          "limits": harness.LIMITS}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
