"""Window mode ``advance``: one simulation carried through fixed slices.

The traffic file names one trace source, its references per core,
``slice_cycles`` and ``check_slice``.  Set-up synthesizes the trace from the seed
(``workloads.resolve_trace``), places the state and advances one slice,
which compiles the slice program or loads it from the cache.  The window
then advances slice after slice, each ending in a device-to-host copy of
the statistics, until ``--seconds`` have passed; the slice in progress at
the deadline is finished and counted, and the window runs at least
``check_slice`` slices.

Two backends: ``dense`` drives ``sim._run_jit`` with the state carried
from slice to slice; ``sharded`` drives ``sharded.ShardedSim`` over the
configuration's tile grid.  Both compile one program per slice length,
and every slice has the same length.

End to end: ``node_cycles_per_s``, simulated nodes times cycles advanced
in the window over the window's wall time.  The answer checked against
the reference is the statistics after the window's slice ``check_slice``,
at cycle ``(1 + check_slice) * slice_cycles``: late enough that fills,
replies and write-backs have begun across the mesh.
"""
from __future__ import annotations

import time
from typing import Dict


class Dense:
    """``sim._run_jit`` with its state carried between slices."""

    def __init__(self, cfg, trace, slice_cycles: int, win):
        import jax.numpy as jnp
        from repro.core import sim
        from repro.core.state import init_state
        self.cfg, self.slice, self.win = cfg, slice_cycles, win
        self.sim, self.jnp = sim, jnp
        self.state = init_state(cfg, trace)
        self.cap = 0

    def advance(self) -> Dict[str, int]:
        import jax
        self.cap += self.slice
        with self.win.span("chipbench.slice"):
            self.state, aux = self.sim._run_jit(
                self.state, self.cfg, self.jnp.asarray(self.cap, self.jnp.int32),
                self.slice)
            jax.block_until_ready(self.state.cycle)
        with self.win.span("chipbench.readback"):
            (stats,) = self.sim.stats_list(self.state, aux)
        return stats


class Sharded:
    """``sharded.ShardedSim`` over the configuration's tile grid."""

    def __init__(self, cfg, trace, slice_cycles: int, win):
        import jax
        import numpy as np
        from jax.sharding import Mesh
        from repro.core.sharded import ShardedSim
        rt, ct = win.cell.config["tiles"]
        devs = np.asarray(jax.devices()[: rt * ct]).reshape(rt, ct)
        self.sim = ShardedSim(cfg, trace, Mesh(devs, ("data", "model")))
        self.slice, self.win = slice_cycles, win
        self.cap = 0

    def advance(self) -> Dict[str, int]:
        self.cap += self.slice
        with self.win.span("chipbench.slice"):
            return self.sim.run(self.cap, chunk=self.slice)


BACKENDS = {"dense": Dense, "sharded": Sharded}


def run(win, cfg) -> Dict:
    from repro.core.workloads import resolve_trace
    trf = win.cell.traffic
    (source,) = trf["sources"]
    refs, slice_cycles = int(trf["refs_per_core"]), int(trf["slice_cycles"])
    check = int(trf["check_slice"])
    trace = resolve_trace(cfg, source, refs, win.seed)
    drv = BACKENDS[win.cell.config["backend"]](cfg, trace, slice_cycles, win)
    warm = drv.advance()

    answers, slices, failed = [], 0, 0
    last = warm
    with win.window():
        window_start = time.time()
        t0 = time.perf_counter()
        while True:
            stats = drv.advance()
            slices += 1
            failed += "aborted" in stats
            if slices == check or (stats["finished"] or "aborted" in stats
                                   ) and not answers:
                answers.append(dict(source=source, seed=win.seed, refs=refs,
                                    max_cycles=drv.cap, stats=stats))
            last = stats
            if stats["finished"] or "aborted" in stats:
                break
            if slices >= check and time.perf_counter() - t0 >= win.seconds:
                break
        window_s = time.perf_counter() - t0
    cycles = last["cycles"] - warm["cycles"]
    return dict(
        window_start=window_start,
        window_wall_s=window_s,
        attempted=slices,
        failed=failed,
        answers=answers,
        end_to_end={"node_cycles_per_s": cfg.num_nodes * cycles / window_s},
        window_cycles=cycles,
        nodes=cfg.num_nodes,
    )
