"""Dev harness: lockstep-compare SerialSim vs VectorSim, report first divergence."""
import sys

import numpy as np

sys.path.insert(0, "src")

from repro.core.config import SimConfig, CacheConfig
from repro.core.ref_serial import SerialSim, STAT_NAMES
from repro.core.sim import VectorSim
from repro.core.workloads import resolve_trace
from repro.core import state as S


def serial_snapshot(ss: SerialSim):
    n = ss.cfg.num_nodes
    inp = np.zeros((n, 4, S.NUM_F), np.int64)
    for node in range(n):
        for p, f in enumerate(ss.inp[node]):
            if f is not None:
                inp[node, p] = [1, f.age, f.src, f.dst, f.osrc, f.typ, f.tag,
                                f.pkt, f.fid, f.nfl]
    qsize = np.array([len(q) for q in ss.sendq])
    pc = np.zeros((n, ss.cfg.pc_depth, 5), np.int64)
    for node in range(n):
        for i, (t, src, osrc, tag) in enumerate(ss.pending[node]):
            pc[node, i] = [1, t, src, osrc, tag]
    rob_counts = np.array([len(r) for r in ss.rob])
    return dict(st=ss.st.copy(), ctr=ss.ctr.copy(), tr_ptr=ss.tr_ptr.copy(),
                pend=ss.pend_addr.copy(), inp=inp, qsize=qsize, pc=pc,
                rob_counts=rob_counts,
                l1_tag=ss.l1_tag.copy(), l2_tag=ss.l2_tag.copy(),
                l1_lru=ss.l1_lru.copy(), l2_lru=ss.l2_lru.copy(),
                l1_owner=ss.l1_owner.copy(),
                l2_mig=ss.l2_mig.copy(), l2_streak=ss.l2_streak.copy(),
                dir=ss.dir_loc.copy(),
                fwd_tag=ss.fwd_tag.copy(), fwd_dst=ss.fwd_dst.copy(),
                qfid=ss.q_fid.copy(),
                stats=np.array([ss.stats[k] for k in STAT_NAMES]))


def vector_snapshot(vs: VectorSim):
    s = vs.state
    rob_counts = np.sum(np.asarray(s.rob[:, :, S.R_NFL]) > 0, axis=1)
    return dict(st=np.asarray(s.st), ctr=np.asarray(s.ctr),
                tr_ptr=np.asarray(s.tr_ptr), pend=np.asarray(s.pend_addr),
                inp=np.asarray(s.inp), qsize=np.asarray(s.q_size),
                pc=np.asarray(s.pc), rob_counts=rob_counts,
                l1_tag=np.asarray(s.l1_tag), l2_tag=np.asarray(s.l2_tag),
                l1_lru=np.asarray(s.l1_lru), l2_lru=np.asarray(s.l2_lru),
                l1_owner=np.asarray(s.l1_owner),
                l2_mig=np.asarray(s.l2_mig), l2_streak=np.asarray(s.l2_streak),
                dir=np.asarray(s.dir_loc)[:-1],
                fwd_tag=np.asarray(s.fwd_tag), fwd_dst=np.asarray(s.fwd_dst),
                qfid=np.asarray(s.q_fid),
                stats=np.asarray(s.stats))


def compare(a, b, cycle):
    for k in a:
        av, bv = np.asarray(a[k]), np.asarray(b[k])
        if av.shape != bv.shape:
            print(f"cycle {cycle}: SHAPE mismatch {k}: {av.shape} vs {bv.shape}")
            return k
        if not np.array_equal(av, bv):
            idx = np.argwhere(av != bv)
            print(f"cycle {cycle}: MISMATCH {k} at {idx[:8].tolist()}")
            for i in idx[:8]:
                print(f"   serial={av[tuple(i)]} vector={bv[tuple(i)]}")
            if k == "stats":
                for i in idx[:20]:
                    print(f"   stat {STAT_NAMES[i[0]]}: serial={av[tuple(i)]} vector={bv[tuple(i)]}")
            return k
    return None


def main(rows=4, cols=4, refs=40, seed=1, app="matmul", cycles=4000, **kw):
    cfg = SimConfig(rows=rows, cols=cols, addr_bits=14,
                    migrate_threshold=2, **kw)
    tr = resolve_trace(cfg, app, refs, seed)
    ss = SerialSim(cfg, tr)
    vs = VectorSim(cfg, tr)
    bad = compare(serial_snapshot(ss), vector_snapshot(vs), -1)
    if bad:
        return
    for cyc in range(cycles):
        ss.step()
        vs.step()
        bad = compare(serial_snapshot(ss), vector_snapshot(vs), cyc)
        if bad:
            print(f"diverged at cycle {cyc} on {bad}")
            return
        if ss.finished():
            print(f"finished identically at cycle {cyc}, "
                  f"stats match: {ss.stats['injected']} flits injected, "
                  f"{ss.stats['trap']} traps, {ss.stats['migrations']} migrations")
            return
    print(f"no divergence in {cycles} cycles (not finished)")


if __name__ == "__main__":
    import argparse
    ap = argparse.ArgumentParser()
    ap.add_argument("--rows", type=int, default=4)
    ap.add_argument("--cols", type=int, default=4)
    ap.add_argument("--refs", type=int, default=40)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--app", default="matmul")
    ap.add_argument("--cycles", type=int, default=4000)
    ap.add_argument("--distdir", action="store_true")
    a = ap.parse_args()
    main(a.rows, a.cols, a.refs, a.seed, a.app, a.cycles,
         centralized_directory=not a.distdir)
