"""Calibrate the planner's cost-model constants on the actual host.

    PYTHONPATH=src python benchmarks/calibrate_cost_model.py --emit cost_model.json

The execution-plan layer (``repro.core.engine``) costs its three backends
with three constants — ``halo_overhead``, ``shard_fixed`` and
``batch_fixed`` (see :class:`repro.core.engine.CostConstants`).  The
shipped defaults are CPU-calibrated guesses; this harness *measures* them
by timing the real compiled step programs:

1. **dense** — the vmapped driver (``sim._run_jit``) at two mesh sizes
   gives the per-node-cycle unit cost the whole model is denominated in.
2. **sharded** — the spatial ``shard_map`` step at the same two mesh
   sizes and a fixed tile count: per-cycle time is
   ``(n/tiles * halo_overhead + shard_fixed) * unit``, linear in ``n``,
   so the slope yields ``halo_overhead`` and the intercept
   ``shard_fixed``.
3. **composed** — the batched step with the scenario axis sharded
   (``batch_shards = 2``) and TWO scenarios per shard isolates
   ``batch_fixed`` — the incremental fixed cost per additional local
   scenario vmapped through a tile — as the residual over the sharded
   prediction.  Skipped (constant left at its default, and flagged in
   the metadata) when the host has fewer than 4 devices.

``--emit FILE`` writes a JSON constants file round-trippable through
:func:`repro.core.engine.load_cost_constants`; point ``REPRO_COST_MODEL``
at it (or call ``load_cost_constants``) to make every subsequent
``compile_plan`` use the measured values instead of the guesses.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import platform
import sys
import time

sys.path.insert(0, "src")

from repro.core import engine                              # noqa: E402

engine.expose_host_devices()   # before anything imports jax

import jax                                                 # noqa: E402
import jax.numpy as jnp                                    # noqa: E402
import numpy as np                                         # noqa: E402

from repro.core.config import SimConfig                    # noqa: E402
from repro.core.sharded import ShardedSim                  # noqa: E402
from repro.core.sim import _run_jit                        # noqa: E402
from repro.core.state import init_state                    # noqa: E402
from repro.core.trace import random_trace                  # noqa: E402
from jax.sharding import Mesh                              # noqa: E402


def _cfg(rows: int) -> SimConfig:
    # home-sharded directory everywhere so dense and sharded time the
    # same semantics; a huge refs count keeps the sim busy past the
    # timing window, and livelock_window=0 disables the early-abort
    # monitor (we are timing throughput, not finishing runs)
    return SimConfig(rows=rows, cols=rows, centralized_directory=False,
                     dir_layout="home", livelock_window=0)


def _best_of(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def time_dense(rows: int, refs: int, cycles: int, chunk: int,
               reps: int) -> float:
    """Seconds per simulated cycle of the dense vmapped driver."""
    cfg = _cfg(rows)
    s = init_state(cfg, random_trace(cfg, refs, seed=0))
    cap = jnp.asarray(cycles, jnp.int32)

    def go():
        out, _ = _run_jit(s, cfg, cap, chunk)
        out.cycle.block_until_ready()
        assert int(out.cycle) == cycles, "workload finished inside the " \
            "timing window; raise --refs"

    go()                       # compile + warm
    return _best_of(go, reps) / cycles


def time_step(sim: ShardedSim, cycles: int, reps: int) -> float:
    """Seconds per simulated cycle of a (possibly composed) sharded step."""
    step = sim.build_step(cycles)

    def go():
        out = step(sim.state, *sim.geo)
        out.cycle.block_until_ready()
        return out

    out = go()                 # compile + warm (state NOT advanced: the
    # timed calls reuse sim.state).  Like time_dense: a sim that finishes
    # inside the window would freeze into a no-op and poison the fit.
    assert int(np.min(np.asarray(out.cycle))) == cycles, \
        "workload finished inside the timing window; raise --refs"
    return _best_of(go, reps) / cycles


def calibrate(args) -> dict:
    ndev = len(jax.devices())
    n1, n2 = args.rows_small ** 2, args.rows_large ** 2
    nt = max(d for d in range(1, min(ndev, 4) + 1)
             if args.rows_small % d == 0 and args.rows_large % d == 0
             and d <= ndev)
    meas = {"devices": ndev, "spatial_tiles": nt,
            "cycles": args.cycles, "reps": args.reps}

    t_d1 = time_dense(args.rows_small, args.refs, args.cycles,
                      args.chunk, args.reps)
    t_d2 = time_dense(args.rows_large, args.refs, args.cycles,
                      args.chunk, args.reps)
    unit = (t_d1 / n1 + t_d2 / n2) / 2          # s per node-cycle
    meas.update(dense_s_per_cycle={str(n1): t_d1, str(n2): t_d2},
                unit_s_per_node_cycle=unit)

    defaults = engine.CostConstants()
    if nt <= 1:
        # single device: no collective to measure — keep the defaults
        meas["note"] = "single device; sharded/composed not measurable"
        return {"constants": defaults, "meta": meas}

    def sharded_sim(rows):
        cfg = _cfg(rows)
        tr = random_trace(cfg, args.refs, seed=0)
        mesh = Mesh(np.asarray(jax.devices()[:nt]).reshape(1, nt),
                    ("data", "model"))
        return ShardedSim(cfg, tr, mesh)

    y1 = time_step(sharded_sim(args.rows_small), args.cycles, args.reps)
    y2 = time_step(sharded_sim(args.rows_large), args.cycles, args.reps)
    meas["sharded_s_per_cycle"] = {str(n1): y1, str(n2): y2}

    halo = (y2 - y1) / ((n2 - n1) / nt) / unit
    halo = max(halo, 1.0)      # a tile step can't beat the dense per-node cost
    fixed = max(y1 / unit - n1 / nt * halo, 0.0)

    batch_fixed = defaults.batch_fixed
    if ndev >= 2 * nt:
        # 4 scenarios over batch_shards=2 -> local batch of 2: the
        # residual over the sharded prediction is (local_b - 1) = 1
        # batch_fixed units
        cfg = _cfg(args.rows_large)
        tr = np.stack([random_trace(cfg, args.refs, seed=s)
                       for s in range(4)])
        mesh = Mesh(np.asarray(jax.devices()[:2 * nt]).reshape(2, 1, nt),
                    ("scenario", "data", "model"))
        sim = ShardedSim(cfg, tr, mesh, batch_axes=("scenario",))
        y3 = time_step(sim, args.cycles, args.reps)
        meas["composed_s_per_cycle_localb2"] = {str(n2): y3}
        batch_fixed = max(y3 / unit - 2 * n2 / nt * halo - fixed, 0.0)
    else:
        meas["note"] = (f"{ndev} device(s) < {2 * nt}: batch_fixed not "
                        "measurable, default kept")

    return {"constants": engine.CostConstants(
        halo_overhead=round(halo, 3), shard_fixed=round(fixed, 1),
        batch_fixed=round(batch_fixed, 1)), "meta": meas}


def main() -> None:
    engine.enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows-small", type=int, default=16,
                    help="smaller calibration mesh edge (rows == cols)")
    ap.add_argument("--rows-large", type=int, default=32,
                    help="larger calibration mesh edge")
    ap.add_argument("--refs", type=int, default=100_000,
                    help="refs per core; must outlast the timing window")
    ap.add_argument("--cycles", type=int, default=256,
                    help="simulated cycles per timed program call")
    ap.add_argument("--chunk", type=int, default=64,
                    help="dense-driver chunk (cycles per termination check)")
    ap.add_argument("--reps", type=int, default=3,
                    help="timing repetitions (best-of)")
    ap.add_argument("--emit", default=None, metavar="FILE",
                    help="write the constants file the planner loads via "
                         "REPRO_COST_MODEL / engine.load_cost_constants")
    args = ap.parse_args()

    res = calibrate(args)
    c = res["constants"]
    meta = {"platform": platform.platform(),
            "jax_backend": jax.default_backend(),
            "argv": sys.argv[1:], **res["meta"]}
    print(json.dumps({**dataclasses.asdict(c), "meta": meta}, indent=1))
    if args.emit:
        engine.save_cost_constants(args.emit, c, meta=meta)
        print(f"wrote {args.emit}; planner picks it up via "
              f"REPRO_COST_MODEL={args.emit}", file=sys.stderr)


if __name__ == "__main__":
    main()
