"""Quickstart: simulate a 16x16-core bufferless LCMP on one device.

    PYTHONPATH=src python examples/quickstart.py
"""
import sys

sys.path.insert(0, "src")

from repro.core import SimConfig, run
from repro.core.ref_serial import SerialSim
from repro.core.workloads import app_trace


def main() -> None:
    cfg = SimConfig(rows=8, cols=8, addr_bits=18, migrate_threshold=2)
    trace = app_trace(cfg, "matmul", refs_per_core=60, seed=1)

    print("== vectorized JAX simulator (the paper's GPU version, TPU-form) ==")
    stats = run(cfg, trace, chunk=8)
    for k in ("cycles", "req_made", "reply_sent", "trap", "redirection",
              "migrations", "dir_search", "l1_hits", "l1_misses",
              "deflections", "injected"):
        print(f"  {k:14s} {stats[k]}")

    print("== serial golden model (the paper's C++ version) ==")
    ref = SerialSim(cfg, trace).run()
    same = all(ref[k] == stats[k] for k in ref)
    print(f"  identical statistics: {same}")
    assert same


if __name__ == "__main__":
    main()
