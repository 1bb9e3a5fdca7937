"""Scale demo: simulate a large LCMP, sharded across all local devices.

The paper's headline is 43,000 simulated cores on one GTX 690; the sharded
simulator tiles the router grid over a device mesh (halo-exchange
collectives), so the same binary scales from a laptop to a 512-chip pod.

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python examples/simulate_large_noc.py --rows 64 --cols 64
"""
import argparse
import sys
import time

sys.path.insert(0, "src")

import jax

from repro.core.config import SimConfig
from repro.core.sharded import ShardedSim
from repro.core.workloads import app_trace


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=64)
    ap.add_argument("--cols", type=int, default=64)
    ap.add_argument("--refs", type=int, default=40)
    ap.add_argument("--app", default="mgrid")
    args = ap.parse_args()

    n_dev = len(jax.devices())
    rt = 1
    for cand in range(int(n_dev ** 0.5), 0, -1):
        if n_dev % cand == 0 and args.rows % cand == 0 \
                and args.cols % (n_dev // cand) == 0:
            rt = cand
            break
    mesh = jax.make_mesh((rt, n_dev // rt), ("data", "model"))
    print(f"simulating {args.rows}x{args.cols} = {args.rows*args.cols} cores "
          f"over {n_dev} devices (tiles {rt}x{n_dev//rt})")

    cfg = SimConfig(rows=args.rows, cols=args.cols, addr_bits=20,
                    centralized_directory=False, dir_layout="home")
    trace = app_trace(cfg, args.app, args.refs, seed=1)
    sim = ShardedSim(cfg, trace, mesh)
    t0 = time.time()
    stats = sim.run(chunk=128)
    dt = time.time() - t0
    print(f"finished={stats['finished']} cycles={stats['cycles']} "
          f"wall={dt:.1f}s")
    for k in ("req_made", "trap", "redirection", "migrations",
              "deflections", "injected"):
        print(f"  {k:12s} {stats[k]}")


if __name__ == "__main__":
    main()
