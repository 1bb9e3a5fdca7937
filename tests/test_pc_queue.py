"""Pending-completion queue + ejection guarantee (the S14 livelock fix).

Covers the contract from three sides:

* **Compatibility** — ``pc_depth=1`` is the paper-faithful single S14
  completion register: bit-identical stats to the pre-queue seed
  semantics (golden dicts recorded before the refactor) on healthy runs.
* **Queue mechanics** — FIFO ordering and capacity at the unit level
  (phase1a serves the head; deliver appends at the tail; a full queue
  parks completions in the ROB and promotes them as it drains).
* **The livelock itself** — the exact ROADMAP wedge (16x16 / matmul /
  seed 0 / refs 20 via the loop-trace generator) runs to completion at
  the default depth with serial/vector bit-parity, including at the
  cycle where the ``pc_depth=1`` model wedges.
"""
import dataclasses
import json

import numpy as np

from repro.core.config import MSG_DA, MSG_DU, SimConfig
from repro.core.ref_serial import SerialSim
from repro.core.sim import VectorSim, run
from repro.core.workloads import app_trace, app_trace_loop

# Golden stats captured on the pre-queue seed semantics (single S14
# register).  pc_depth=1 must reproduce them bit-for-bit.
GOLDEN_DISTRIBUTED = json.loads("""
{"req_made": 73, "req_rcvd": 73, "reply_sent": 65, "reply_rcvd": 65,
 "trap": 8, "redirection": 0, "dir_search": 202, "dir_update": 129,
 "mem_req": 137, "migrations": 0, "migrations_done": 0, "l1_hits": 188,
 "l1_misses": 212, "l2_local_hits": 10, "l2_local_misses": 202,
 "wb_sent": 0, "wb_rcvd": 0, "wb_miss": 0, "flits_delivered": 851,
 "deflections": 41, "hops": 2142, "injected": 851, "send_drop": 0,
 "l2_install_drop": 0, "stray": 0, "cycles": 1311, "finished": 1}
""")
GOLDEN_CENTRALIZED = json.loads("""
{"req_made": 94, "req_rcvd": 94, "reply_sent": 83, "reply_rcvd": 83,
 "trap": 11, "redirection": 0, "dir_search": 230, "dir_update": 136,
 "mem_req": 147, "migrations": 0, "migrations_done": 0, "l1_hits": 240,
 "l1_misses": 240, "l2_local_hits": 10, "l2_local_misses": 230,
 "wb_sent": 3, "wb_rcvd": 3, "wb_miss": 0, "flits_delivered": 1003,
 "deflections": 286, "hops": 3346, "injected": 1003, "send_drop": 0,
 "l2_install_drop": 0, "stray": 0, "cycles": 1162, "finished": 1}
""")


def _wedge_cfg(**kw) -> SimConfig:
    return SimConfig(rows=16, cols=16, centralized_directory=False, **kw)


def test_pc_depth_1_bit_identical_to_seed_semantics():
    """The compatibility escape hatch: depth 1 == the pre-queue register."""
    cfg = SimConfig(rows=4, cols=4, addr_bits=14,
                    centralized_directory=False, pc_depth=1)
    got = run(cfg, app_trace(cfg, "equake", 25, seed=1))
    assert got == GOLDEN_DISTRIBUTED, {
        k: (GOLDEN_DISTRIBUTED[k], got.get(k))
        for k in GOLDEN_DISTRIBUTED if got.get(k) != GOLDEN_DISTRIBUTED[k]}

    cfg2 = SimConfig(rows=4, cols=4, addr_bits=14, migrate_threshold=2,
                     pc_depth=1)
    got2 = run(cfg2, app_trace(cfg2, "matmul", 30, seed=1))
    assert got2 == GOLDEN_CENTRALIZED


def test_healthy_run_identical_across_depths():
    """On a run that never saturates S14, the queue is invisible: every
    depth (including the escape hatch) produces the same stats."""
    cfg = SimConfig(rows=4, cols=4, addr_bits=14,
                    centralized_directory=False)
    tr = app_trace(cfg, "equake", 25, seed=1)
    ref = run(dataclasses.replace(cfg, pc_depth=1), tr)
    for depth in (2, 4, 8):
        got = run(dataclasses.replace(cfg, pc_depth=depth), tr)
        assert got == ref, depth


# ---------------------------------------------------------------------------
# queue mechanics at the unit level (serial golden model = the spec)
# ---------------------------------------------------------------------------

def _idle_serial(depth: int, rob_slots: int = 4) -> SerialSim:
    # eject_age_threshold pinned explicitly: these unit tests exercise
    # the age-gating mechanics, which must not depend on the tuned
    # default (0 since the zoo_tune sweep — benchmarks/zoo_thresholds.json)
    cfg = SimConfig(rows=2, cols=2, addr_bits=14, pc_depth=depth,
                    rob_slots=rob_slots, centralized_directory=False,
                    eject_age_threshold=8)
    return SerialSim(cfg, np.full((4, 1), -1, np.int64))


def test_fifo_head_service_order():
    """phase1a serves completions in arrival (FIFO) order."""
    ss = _idle_serial(depth=4)
    # three directory updates queued at node 0: DU(osrc=owner) writes the
    # directory, so service order is observable through dir_loc
    ss.pending[0] = [(MSG_DU, 1, 5, 7), (MSG_DU, 2, 6, 7), (MSG_DU, 3, -1, 7)]
    ss.phase1a(0)
    assert ss.dir_loc[7] == 5 and len(ss.pending[0]) == 2
    ss.phase1a(0)
    assert ss.dir_loc[7] == 6 and len(ss.pending[0]) == 1
    ss.phase1a(0)   # delete: osrc < 0 and dir_loc[7] != src -> unchanged
    assert ss.dir_loc[7] == 6 and not ss.pending[0]


def test_capacity_overflow_parks_in_rob_and_promotes():
    """A completion arriving at a full queue parks in the ROB and is
    promoted (smallest (src, pkt) first) as the queue drains."""
    from repro.core.config import PORT_E
    from repro.core.ref_serial import Flit

    ss = _idle_serial(depth=2)
    cfg = ss.cfg
    ss.pending[0] = [(MSG_DU, 1, -1, 3), (MSG_DU, 1, -1, 4)]   # full
    # an old single-flit DA arrives at node 0 — queue full, age over the
    # threshold: it must still eject (parking path)
    f = Flit(age=cfg.eject_age_threshold + 5, src=3, dst=0, osrc=3,
             typ=MSG_DA, tag=9, pkt=17, fid=0, nfl=1)
    ss.inp[0][PORT_E] = f
    out, eject, defl = ss.phase2(0)
    assert eject is not None and eject[1] is f
    ss.phase3({0: {}, 1: {}, 2: {}, 3: {}},
              {0: eject, 1: None, 2: None, 3: None},
              {0: {}, 1: {}, 2: {}, 3: {}})
    assert len(ss.pending[0]) == 2            # still full
    assert ss.rob[0] == [[3, 17, MSG_DA, 9, 3, 1, 1]]   # parked
    # drain one completion -> the parked DA promotes into the tail
    ss.phase1a(0)
    ss.phase3({n: {} for n in range(4)}, {n: None for n in range(4)},
              {n: {} for n in range(4)})
    assert not ss.rob[0]
    assert ss.pending[0][-1] == (MSG_DA, 3, 3, 9)


def test_full_queue_bars_young_flits_but_not_old():
    """Age-threshold guaranteed ejection: an occupied queue rejects young
    flits (paper-faithful bar) and accepts aged ones."""
    from repro.core.config import PORT_E
    from repro.core.ref_serial import Flit

    ss = _idle_serial(depth=4)
    thr = ss.cfg.eject_age_threshold
    ss.pending[0] = [(MSG_DU, 1, -1, 3)]      # occupied, not full
    young = Flit(age=thr - 1, src=3, dst=0, osrc=3, typ=MSG_DA, tag=9,
                 pkt=1, fid=0, nfl=1)
    ss.inp[0][PORT_E] = young
    _, eject, _ = ss.phase2(0)
    assert eject is None
    young.age = thr                           # now old enough
    _, eject, _ = ss.phase2(0)
    assert eject is not None


def test_depth1_register_still_bars_all_ejection():
    """pc_depth=1 keeps the seed's S14 bar: an occupied register blocks
    ejection regardless of age."""
    from repro.core.config import PORT_E
    from repro.core.ref_serial import Flit

    ss = _idle_serial(depth=1)
    ss.pending[0] = [(MSG_DU, 1, -1, 3)]
    f = Flit(age=10_000, src=3, dst=0, osrc=3, typ=MSG_DA, tag=9,
             pkt=1, fid=0, nfl=1)
    ss.inp[0][PORT_E] = f
    _, eject, _ = ss.phase2(0)
    assert eject is None


# ---------------------------------------------------------------------------
# the ROADMAP wedge itself
# ---------------------------------------------------------------------------

def test_former_wedge_completes_at_default_depth():
    """The exact ROADMAP repro (16x16 / matmul / seed 0 / refs 20 via the
    loop-trace generator) runs to completion instead of aborting, and the
    livelock detector stays quiet while watching it."""
    cfg = _wedge_cfg(max_cycles=200_000)
    assert cfg.pc_depth > 1          # the fix is on by default
    tr = app_trace_loop(cfg, "matmul", 20, 0)
    st = run(cfg, tr, chunk=16)
    assert st["finished"] == 1, st
    assert "aborted" not in st
    # the drain guarantee + retry actually exercised (drops recovered)
    assert st["cycles"] < 50_000


def test_wedge_serial_vector_parity_past_the_wedge_cycle():
    """Serial and vectorized models stay in lockstep THROUGH the cycles
    where the pc_depth=1 model wedges (~cycle 277 the hotspot queue
    freezes; livelock detected ~3.8k): compare FSM/queue state cycle by
    cycle over the critical window, then full-run stats."""
    cfg = _wedge_cfg(max_cycles=200_000)
    tr = app_trace_loop(cfg, "matmul", 20, 0)
    ss = SerialSim(cfg, tr)
    vs = VectorSim(cfg, tr)
    check_at = {250, 300, 500, 1000, 2000}    # brackets the old wedge
    for cyc in range(1, 2001):
        ss.step()
        vs.step()
        if cyc in check_at:
            s = vs.state
            assert np.array_equal(ss.st, np.asarray(s.st)), cyc
            assert np.array_equal(ss.tr_ptr, np.asarray(s.tr_ptr)), cyc
            assert np.array_equal(
                np.array([len(q) for q in ss.sendq]),
                np.asarray(s.q_size)), cyc
            assert np.array_equal(
                np.array([len(p) for p in ss.pending]),
                np.asarray((s.pc[:, :, 0] > 0).sum(axis=1))), cyc
    ref = ss.run()                             # continue to completion
    got = run(cfg, tr, chunk=16)
    assert ref == got, {k: (ref.get(k), got.get(k))
                        for k in set(ref) | set(got)
                        if ref.get(k) != got.get(k)}
    assert ref["finished"] == 1


def test_wedge_still_wedges_at_depth_1():
    """Regression guard for the guard: the pathology is real — with the
    escape hatch the same (cfg, trace) still livelocks and the detector
    still aborts it (tests/test_detectors.py asserts the diagnostics)."""
    cfg = _wedge_cfg(pc_depth=1, livelock_window=256, max_cycles=30_000)
    tr = app_trace_loop(cfg, "matmul", 20, 0)
    st = run(cfg, tr, chunk=16)
    assert st.get("aborted") == "livelock" and st["finished"] == 0


def test_eject_age_threshold_is_a_traced_knob():
    """eject_age_threshold rides as per-scenario traced state: one
    compiled sweep varies it per scenario, matching solo runs; pc_depth
    is structural and must split planner buckets."""
    from repro.core import engine
    from repro.core.sweep import ScenarioSpec, SweepSpec, run_sweep

    cfg = SimConfig(rows=4, cols=4, addr_bits=14,
                    centralized_directory=False)
    spec = SweepSpec(cfg, (
        ScenarioSpec("matmul", 3, 25, eject_age_threshold=0),
        ScenarioSpec("matmul", 3, 25, eject_age_threshold=64),
        ScenarioSpec("matmul", 3, 25),
    ))
    got = run_sweep(spec, chunk=8)
    traces = spec.traces()
    for b, sc in enumerate(spec.scenarios):
        solo = run(sc.resolve_cfg(cfg), traces[b])
        assert got[b] == solo, (b, {
            k: (got[b].get(k), solo.get(k))
            for k in solo if got[b].get(k) != solo.get(k)})

    # knob does not split buckets; pc_depth does
    scs = [engine.make_scenario(cfg, app="matmul", seed=0, refs_per_core=5,
                                eject_age_threshold=t) for t in (0, 8, 64)]
    plan = engine.compile_plan(scs, ndev=1)
    assert len(plan.buckets) == 1
    scs2 = scs + [engine.make_scenario(cfg, app="matmul", seed=0,
                                       refs_per_core=5, pc_depth=2)]
    plan2 = engine.compile_plan(scs2, ndev=1)
    assert len(plan2.buckets) == 2
