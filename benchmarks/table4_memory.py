"""Paper Table 4: cache configuration vs maximum simulatable core count.

    PYTHONPATH=src python benchmarks/table4_memory.py [--out f]

The paper's limit is GPU global memory (43k cores on a GTX 690, dropping
to 30k with migration metadata, 2k with big caches).  Here: exact
simulator-state bytes per simulated core for each cache configuration —
under both state-dtype policies (``wide`` = all-int32 storage, ``packed``
= narrowest dtype the config bounds allow) — and the implied maximum
cores per 16 GiB TPU v5e chip and per 512-chip job.

Three measurement layers, cross-checked against each other:

* per paper row: ``jax.eval_shape`` over ``init_state`` (dtype-aware),
  with migration metadata elided for the paper's "without" row;
* a representative sweep config: the analytic
  :func:`repro.core.state.state_bytes` estimator the planner uses;
* the same config *materialized*, measured as actual live device-buffer
  bytes via ``jax.live_arrays()`` — if the analytic number ever drifts
  from what the runtime really allocates, this benchmark fails.

``bytes_per_core`` is a pure function of the state layout, so the
metrics gate at zero slack: any state-struct growth shows up here first.
"""
from __future__ import annotations

import dataclasses
import sys

sys.path.insert(0, "src")

import jax                                                      # noqa: E402
import numpy as np                                              # noqa: E402

from repro.bench import BenchReport, Benchmark, bench_main      # noqa: E402
from repro.core.engine import enable_compile_cache              # noqa: E402
from repro.core import SimConfig                                # noqa: E402
from repro.core.config import CacheConfig                       # noqa: E402
from repro.core.state import init_state, state_bytes            # noqa: E402

CONFIGS = [
    ("L1 128x4, L2 512x8 (paper row 1)", CacheConfig(128, 4, 32, 512, 8, 64), True),
    ("L1 128x4, L2 128x4 (paper row 2)", CacheConfig(128, 4, 32, 128, 4, 64), True),
    ("L1 32x2,  L2 32x2 + migration", CacheConfig(32, 2, 32, 32, 2, 64), True),
    ("L1 32x2,  L2 32x2 no migration", CacheConfig(32, 2, 32, 32, 2, 64), False),
]

HBM = 16 * 2**30

#: migration metadata leaves elided for the paper's "without" row
_MIG_LEAVES = ("l2_last", "l2_streak", "fwd_tag", "fwd_dst", "fwd_ptr")

#: the representative config for the packed-vs-wide headline numbers:
#: a 16x16 sweep mesh whose bounds let every narrowable field narrow
#: (node ids and tags fit int16; at the paper-scale 208x208 mesh the id
#: fields are forced back to int32 and the ratio lands higher)
REP = dict(rows=16, cols=16, addr_bits=14, max_cycles=8192,
           dir_layout="home", centralized_directory=False)
REP_REFS = 200


def bytes_per_core(cache: CacheConfig, migration: bool,
                   policy: str = "wide", refs: int = 200) -> int:
    cfg = SimConfig(rows=4, cols=4, cache=cache, addr_bits=16,
                    migration_enabled=migration,
                    centralized_directory=False, dir_layout="home",
                    state_dtype_policy=policy)
    tr = np.zeros((cfg.num_nodes, refs), np.int32)
    st = jax.eval_shape(lambda t: init_state(cfg, t), tr)
    total = 0
    for name, leaf in st._asdict().items():
        n = int(np.prod(leaf.shape)) * leaf.dtype.itemsize
        if not migration and name in _MIG_LEAVES:
            continue   # migration metadata elided (paper's "without")
        total += n
    return total // cfg.num_nodes


def live_bytes_per_node(cfg: SimConfig, refs: int = REP_REFS) -> int:
    """Actually allocate the state and count the new live device buffers
    (``jax.live_arrays``) — the runtime's answer, not the estimator's."""
    tr = np.zeros((cfg.num_nodes, refs), np.int32)
    before = {id(a) for a in jax.live_arrays()}
    st = jax.block_until_ready(init_state(cfg, tr))
    live = sum(a.nbytes for a in jax.live_arrays() if id(a) not in before)
    del st
    return live // cfg.num_nodes


def add_args(ap) -> None:
    pass   # the table is parameter-free (configs are the paper's rows)


def run_bench(args) -> BenchReport:
    """Contract entry: state bytes/core per cache config (both dtype
    policies) + implied caps, plus analytic-vs-live cross-check at the
    representative config."""
    rows = []
    print(f"{'config':38s} {'wide':>8s} {'packed':>8s} "
          f"{'max cores/chip':>15s} {'max cores/512':>14s}")
    for name, cache, mig in CONFIGS:
        b = bytes_per_core(cache, mig, "wide")
        bp = bytes_per_core(cache, mig, "packed")
        per_chip = HBM // bp
        rows.append({"config": name, "bytes_per_core": b,
                     "bytes_per_core_packed": bp,
                     "max_per_chip": per_chip,
                     "max_512": per_chip * 512})
        print(f"{name:38s} {b:>8d} {bp:>8d} {per_chip:>15,d} "
              f"{per_chip*512:>14,d}")
    print("\npaper (GTX 690, 2 GiB/GPU): 2,000 / 10,000 / 30,000 / 43,000")

    rep_w = SimConfig(state_dtype_policy="wide", **REP)
    rep_p = SimConfig(state_dtype_policy="packed", **REP)
    n = rep_w.num_nodes
    est_w = state_bytes(rep_w, trace_len=REP_REFS) // n
    est_p = state_bytes(rep_p, trace_len=REP_REFS) // n
    live_w = live_bytes_per_node(rep_w)
    live_p = live_bytes_per_node(rep_p)
    ratio = est_p / est_w
    print(f"\nrepresentative 16x16 sweep config, bytes/node:")
    print(f"  wide   analytic {est_w:>6d}  live {live_w:>6d}")
    print(f"  packed analytic {est_p:>6d}  live {live_p:>6d}"
          f"   ratio {ratio:.3f}")
    if (est_w, est_p) != (live_w, live_p):
        raise AssertionError(
            f"state_bytes estimator drifted from live buffers: "
            f"analytic (wide {est_w}, packed {est_p}) vs "
            f"live (wide {live_w}, packed {live_p})")

    rep = BenchReport("table4", raw={
        "rows": rows,
        "representative": {"config": REP, "refs": REP_REFS,
                           "wide": est_w, "packed": est_p,
                           "live_wide": live_w, "live_packed": live_p,
                           "ratio": ratio}})
    for i, row in enumerate(rows):
        rep.add(f"table4.row{i}.bytes_per_core", row["bytes_per_core"],
                unit="B/core", direction="lower",
                tags={"config": row["config"], "policy": "wide"})
        rep.add(f"table4.row{i}.bytes_per_core_packed",
                row["bytes_per_core_packed"],
                unit="B/core", direction="lower",
                tags={"config": row["config"], "policy": "packed"})
        rep.add(f"table4.row{i}.max_per_chip", row["max_per_chip"],
                unit="cores", direction="higher", gate=False,
                tags={"config": row["config"]})
    rep.add("table4.state_bytes_per_node.wide", est_w,
            unit="B/node", direction="lower", tags={"config": "rep-16x16"})
    rep.add("table4.state_bytes_per_node.packed", est_p,
            unit="B/node", direction="lower", tags={"config": "rep-16x16"})
    rep.add("table4.live_bytes_per_node.wide", live_w,
            unit="B/node", direction="lower", tags={"config": "rep-16x16"})
    rep.add("table4.live_bytes_per_node.packed", live_p,
            unit="B/node", direction="lower", tags={"config": "rep-16x16"})
    rep.add("table4.packed_wide_ratio", round(ratio, 4),
            unit="x", direction="lower", tags={"config": "rep-16x16"})
    return rep


BENCH = Benchmark(
    area="table4",
    title="Paper Table 4: simulator-state bytes/core vs max simulated cores",
    add_args=add_args,
    run=run_bench,
    gated=True,
)


def main(argv=None) -> BenchReport:
    enable_compile_cache()
    return bench_main(BENCH, argv)


if __name__ == "__main__":
    main()
