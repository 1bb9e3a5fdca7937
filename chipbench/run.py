#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is an entry of ``BENCHMARK.json``.  The run sets up the cell's
program on the chips (compile or cache load, trace synthesis, state
placement, one warm slice or batch), measures a window of ``--seconds``,
then checks what the window produced against the plain reference
(``chipbench/reference``).  ``--trace 1`` profiles the window and reports
the cell's per-layer metrics instead of its end-to-end ones.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and ``breakdown`` when traced), and
``checks``, each number compared with its limit; the same numbers end
standard error.  A run without a TPU, or with fewer chips than the cell
needs, exits non-zero and prints no result.

For a rehearsal off the chip: ``--config <file> --traffic <file>
--allow-cpu`` runs a pair of files that need not be a cell.

The chips are held by a child process for the device stage only; this
process never imports JAX, and runs the reference once the child has
exited.
"""
import time

T0 = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", help="a cell of BENCHMARK.json")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True,
                    help="length of the measured window")
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--config", help="rehearsal: a configuration file name")
    ap.add_argument("--traffic", help="rehearsal: a traffic file name")
    ap.add_argument("--allow-cpu", action="store_true",
                    help="rehearsal: run on a CPU backend")
    ap.add_argument("--device-stage", action="store_true",
                    help=argparse.SUPPRESS)
    return ap.parse_args(argv)


def device_child(args) -> None:
    """The device stage; its record is the last line of its stdout."""
    from chipbench import harness
    cell = harness.load_cell(args.workload, args.config, args.traffic)
    record = harness.device_stage(cell, args.seed, args.seconds,
                                  bool(args.trace), args.allow_cpu)
    print(json.dumps(record), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import harness
    try:
        if args.device_stage:
            device_child(args)
            return 0
        cell = harness.load_cell(args.workload, args.config, args.traffic)
        argv = sys.argv[1:] if argv is None else list(argv)
        child = subprocess.run(
            [sys.executable, str(Path(__file__).resolve()), *argv,
             "--device-stage"], stdout=subprocess.PIPE, text=True)
        if child.returncode != 0:
            print(f"device stage failed (exit {child.returncode})",
                  file=sys.stderr)
            return 1
        record = json.loads(child.stdout.strip().splitlines()[-1])
        print("device stage done: "
              f"window {record['window_wall_s']:.3f} s, "
              f"compiles in window {record['compiles_in_window']}, "
              f"answers {len(record['answers'])}",
              file=sys.stderr, flush=True)
        out = harness.host_stage(cell, record, args.seed, bool(args.trace),
                                 T0)
    except harness.BenchError as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    for k, v in out["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
