"""Window mode ``batches``: whole batches run to completion, one after
another, through ``engine.execute_plan``.

The traffic file names the trace sources, ``seeds_per_batch``, the
references per core and the in-graph termination ``chunk``.  Batch ``k``
of a run holds every source at trace seeds ``seeds_per_batch * k + j``
for ``j < seeds_per_batch``, all in one compile bucket, in a lane order
drawn from ``--seed``.  So every seed gets the same work: the cycles a
batch needs to finish differ from one set of traces to the next, and
the rate would follow the traces rather than the program.  Set-up runs the first chunk of batch 0,
which compiles the batch program or loads it from the cache.  The window then runs batches 1, 2,
... until ``--seconds`` have passed; the batch in progress at the
deadline is finished and counted.

End to end: ``scenarios_per_s``, scenarios that finished (``finished``
1, no ``aborted``) over the time from the window's start to the end of
its last batch.  A scenario that aborts or reaches its cycle cap is
attempted and failed.  The answers checked against the reference are
the window's scenarios, each with its statistics and its lane (position
in the batch).
"""
from __future__ import annotations

import random
import time
from typing import Dict, List


def run(win, cfg) -> Dict:
    from repro.core import engine
    cell, trf = win.cell, win.cell.traffic
    sources, refs = trf["sources"], int(trf["refs_per_core"])
    per, chunk = int(trf["seeds_per_batch"]), int(trf["chunk"])
    backend = cell.config["backend"]

    def batch(k: int):
        scs = [engine.make_scenario(cfg, app=src, refs_per_core=refs,
                                    seed=per * k + j)
               for src in sources for j in range(per)]
        random.Random(f"{win.seed}:{k}").shuffle(scs)
        plan = engine.compile_plan(scs, ndev=cell.chips, force_backend=backend)
        (bucket,) = plan.buckets
        if bucket.backend != backend or "fell back" in bucket.note:
            raise RuntimeError(f"planned {bucket.backend} ({bucket.note!r}), "
                               f"the configuration states {backend}")
        return scs, plan

    # set-up runs batch 0's first chunk only: the cycle cap is a traced
    # value, so this is the window's program, loaded or compiled
    scs, plan = batch(0)
    engine.execute_plan(plan, max_cycles=chunk, chunk=chunk)

    answers: List[Dict] = []
    batches: List[List[int]] = []
    k = 0
    with win.window():
        window_start = time.time()
        t0 = time.perf_counter()
        while True:
            k += 1
            with win.span("chipbench.batch_setup"):
                scs, plan = batch(k)
            with win.span("chipbench.batch"):
                got = engine.execute_plan(plan, chunk=chunk)
            batches.append([st["cycles"] for st in got])
            answers += [dict(source=sc.app, seed=sc.seed, refs=refs,
                             max_cycles=None, stats=st, lane=lane)
                        for lane, (sc, st) in enumerate(zip(scs, got))]
            if time.perf_counter() - t0 >= win.seconds:
                break
        window_s = time.perf_counter() - t0
    done = sum(a["stats"]["finished"] == 1 and "aborted" not in a["stats"]
               for a in answers)
    return dict(
        window_start=window_start,
        window_wall_s=window_s,
        attempted=len(answers),
        failed=len(answers) - done,
        answers=answers,
        end_to_end={"scenarios_per_s": done / window_s},
        batch_cycles=batches,
    )
