"""Vectorized phases 2 & 3: bufferless deflection routing (paper §4, §6.2.1).

Phase 2 (arbitration): per router — eject the oldest deliverable flit (S11),
optionally admit an injection flit (S12), then assign output ports in
age-priority order with PMDR preference lists (S9), deflecting losers.
The per-router age sort is a branch-free greedy loop over 5 candidate slots
evaluated for all routers at once (the TPU-native form of the paper's
"Priority Sort" block, Fig. 3).

Phase 3 (transfer): a pure gather — input port p of node n reads the
opposite output port of its neighbour in direction p.  This gather is the
only cross-node dataflow in the whole simulator; the sharded version
replaces it with a tile-local shift + ``ppermute`` halo exchange
(:mod:`repro.core.sharded`), sharing `deliver` for the ROB/completion step.
"""
from __future__ import annotations

from typing import NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np

from .config import NUM_PORTS, SimConfig
from .state import (
    F_AGE, F_DST, F_FID, F_NFL, F_OSRC, F_PKT, F_SRC, F_TAG, F_TYP, F_VALID,
    NUM_F, Q_DST, Q_NFL, Q_OSRC, Q_PKT, Q_TAG, Q_TYP,
    R_CNT, R_NFL, R_OSRC, R_PKT, R_SRC, R_TAG, R_TYP,
    P_OSRC, P_SRC, P_TAG, P_TYP, P_VALID,
    Geometry, NodeCtx, SimState, bump,
)

I32 = jnp.int32
#: larger than any node id or packet counter (pkt wraps at 2**30)
BIG = np.int32(1 << 30)


class ArbResult(NamedTuple):
    out: jnp.ndarray        # (Nl, 4, NUM_F) outgoing flits (age already bumped)
    ej_port: jnp.ndarray    # (Nl,)
    has_ej: jnp.ndarray     # (Nl,) bool
    n_deflected: jnp.ndarray
    n_injected: jnp.ndarray


def rob_accepts(s: SimState, flits: jnp.ndarray) -> jnp.ndarray:
    """S10 vectorized: (Nl, P) bool — can each flit be ejected into the ROB."""
    nfl = flits[..., F_NFL]
    src = flits[..., F_SRC]
    pkt = flits[..., F_PKT]
    rob_valid = s.rob[:, :, R_NFL] > 0                      # (Nl, K)
    m = (rob_valid[:, None, :]
         & (s.rob[:, None, :, R_SRC] == src[:, :, None])
         & (s.rob[:, None, :, R_PKT] == pkt[:, :, None]))   # (Nl, P, K)
    has_match = jnp.any(m, axis=-1)
    has_free = jnp.any(~rob_valid, axis=-1)
    return (nfl == 1) | has_match | has_free[:, None]


def phase2(s: SimState, cfg: SimConfig, ctx: NodeCtx) -> Tuple[SimState, ArbResult]:
    n = ctx.node_id.shape[0]
    node = jnp.arange(n, dtype=I32)
    nid = ctx.node_id
    vp = ctx.valid_port
    r = ctx.node_r
    c = ctx.node_c

    inp = s.inp
    valid_in = inp[:, :, F_VALID] > 0

    # ---- ejection (S11): oldest (age desc, port asc) deliverable flit.
    #      S14 + ejection guarantee (pc_depth > 1): with an *empty*
    #      pending-completion queue any deliverable flit may eject (the
    #      paper's behaviour); once the queue is occupied, only flits aged
    #      past the guaranteed-ejection threshold (knob_ej_age) eject —
    #      into spare queue capacity while a slot is free, and into a free
    #      ROB slot (buffered ejection: the completion *parks* and is
    #      promoted into the queue as it drains, see `deliver`) when the
    #      queue is full.  Parking is what breaks the S14 livelock: an
    #      ejection frees an input port, which is the only thing that lets
    #      a saturated node inject, drain its send queue and un-defer its
    #      completion handler.  pc_depth=1 keeps the paper's exact
    #      single-register bar (no ejection while occupied). ----
    acc = rob_accepts(s, inp)
    pc_cnt = jnp.sum((s.pc[:, :, P_VALID] > 0).astype(I32), axis=1)
    pc_empty = pc_cnt == 0
    if cfg.pc_depth > 1:
        pc_has_slot = pc_cnt < cfg.pc_depth
        rob_free = jnp.any(s.rob[:, :, R_NFL] == 0, axis=1)
        # single-flit packets need a free ROB slot to park in; a
        # completing multi-flit packet parks in its own (matched) slot
        park_ok = (inp[:, :, F_NFL] > 1) | rob_free[:, None]
        old_enough = inp[:, :, F_AGE] >= s.knob_ej_age
        ej_ok = (pc_empty[:, None]
                 | (old_enough & (pc_has_slot[:, None] | park_ok)))
    else:
        ej_ok = pc_empty[:, None]
    want_ej = (valid_in & (inp[:, :, F_DST] == nid[:, None]) & acc & ej_ok)
    ej_key = jnp.where(want_ej,
                       inp[:, :, F_AGE] * 4 + (3 - jnp.arange(4, dtype=I32)),
                       -1)
    ej_port = jnp.argmax(ej_key, axis=1).astype(I32)
    has_ej = jnp.max(ej_key, axis=1) >= 0
    is_ej = (jnp.arange(4, dtype=I32)[None, :] == ej_port[:, None]) & has_ej[:, None]
    remaining = valid_in & ~is_ej

    # ---- injection (S12) ----
    n_rem = jnp.sum(remaining.astype(I32), axis=1)
    n_vp = jnp.sum(vp.astype(I32), axis=1)
    qp = cfg.send_queue
    head = s.q_desc[node, s.q_head % qp]                     # (Nl, 6)
    can_inj = (s.q_size > 0) & (n_rem < n_vp)
    inj = jnp.stack([
        can_inj.astype(I32), jnp.zeros(n, I32), nid, head[:, Q_DST],
        head[:, Q_OSRC], head[:, Q_TYP], head[:, Q_TAG], head[:, Q_PKT],
        s.q_fid, head[:, Q_NFL],
    ], axis=-1)

    cand = jnp.concatenate(
        [jnp.where(remaining[:, :, None], inp, 0), inj[:, None, :]], axis=1)
    cand_valid = cand[:, :, F_VALID] > 0                     # (Nl, 5)

    # ---- age-priority arbitration (paper Fig. 3 "Priority Sort" + port
    #      selection) — shared oracle / Pallas kernel, see repro.kernels ----
    from repro.kernels import ops as kops
    dst = cand[:, :, F_DST]
    dst_r = jnp.where(dst >= 0, dst // cfg.cols, 0)
    dst_c = jnp.where(dst >= 0, dst % cfg.cols, 0)
    dr_ = dst_r - r[:, None]
    dc_ = dst_c - c[:, None]
    ports = jnp.arange(4, dtype=I32)
    wanted_eject = cand_valid & (dst == nid[:, None])
    assigned, deflect = kops.arbitrate(
        cand[:, :, F_AGE], cand_valid, wanted_eject, dc_, dr_, vp,
        backend="pallas" if cfg.use_pallas_router else "ref")

    # ---- scatter candidates to their output ports (ports are distinct) ----
    new_age = cand[:, :, F_AGE] + deflect.astype(I32)
    cand = cand.at[:, :, F_AGE].set(new_age)
    oh = ((assigned[:, :, None] == ports[None, None, :])
          & cand_valid[:, :, None])                          # (Nl, 5, 4)
    out = jnp.einsum("nsp,nsf->npf", oh.astype(I32), cand)
    out = out.at[:, :, F_VALID].set(jnp.any(oh, axis=1).astype(I32))

    # ---- pop the send queue on injection ----
    injected = can_inj
    q_fid = s.q_fid + injected.astype(I32)
    pkt_done = injected & (q_fid >= head[:, Q_NFL])
    q_head = jnp.where(pkt_done, (s.q_head + 1) % qp, s.q_head)
    q_size = jnp.where(pkt_done, s.q_size - 1, s.q_size)
    q_fid = jnp.where(pkt_done, 0, q_fid)

    stats = bump(s.stats, "injected", injected)
    n_defl = jnp.sum((deflect & cand_valid).astype(I32))
    stats = bump(stats, "deflections", n_defl)
    s = s._replace(q_head=q_head, q_size=q_size, q_fid=q_fid, stats=stats)
    return s, ArbResult(out, ej_port, has_ej, n_defl, jnp.sum(injected.astype(I32)))


def transfer_global(cfg: SimConfig, geo: Geometry, out: jnp.ndarray) -> jnp.ndarray:
    """Single-device phase-3 transfer: global neighbour gather."""
    vp = jnp.asarray(geo.valid_port)
    gn = jnp.asarray(geo.gather_node)                        # (N, 4)
    gp = jnp.asarray(geo.gather_port)                        # (4,)
    moved = out[gn, gp[None, :]]                             # (N, 4, F)
    return jnp.where(vp[:, :, None], moved, 0)


def deliver(s: SimState, cfg: SimConfig, ctx: NodeCtx, arb: ArbResult,
            inp_next: jnp.ndarray) -> SimState:
    """Shared phase-3 tail: hop stats, ejection into ROB, completions.

    Per-node order (identical in :class:`repro.core.ref_serial.SerialSim`):

    1. *Promotion* — if the pending-completion queue has a free slot and
       the ROB holds a parked completion (a slot whose count reached its
       flit total while the queue was full), the parked completion with
       the smallest ``(src, pkt)`` moves to the queue tail and its ROB
       slot is freed.
    2. *Ejected flit* — a single-flit packet (or the flit completing a
       multi-flit packet) becomes a pending completion: appended at the
       queue tail when a slot is free, otherwise *parked* in the ROB
       (its own slot for multi-flit packets; a fresh slot for singles —
       phase2's ejection gate guaranteed one exists).

    At ``pc_depth=1`` nothing ever parks (phase2 only ejects into an
    empty queue), so both steps reduce to the seed's single-register
    behaviour bit-identically.
    """
    n = ctx.node_id.shape[0]
    node = jnp.arange(n, dtype=I32)
    depth = cfg.pc_depth

    stats = bump(s.stats, "hops", arb.out[:, :, F_VALID])

    # ---- promotion: oldest parked completion -> pending-queue tail ----
    rob = s.rob
    rob_valid = rob[:, :, R_NFL] > 0
    pc_cnt = jnp.sum((s.pc[:, :, P_VALID] > 0).astype(I32), axis=1)
    parked = rob_valid & (rob[:, :, R_CNT] >= rob[:, :, R_NFL])
    # deterministic, model-independent pick: smallest (src, pkt).  pkt is
    # a per-source counter, so the pair is unique among parked slots.
    src_k = jnp.where(parked, rob[:, :, R_SRC], BIG)
    min_src = jnp.min(src_k, axis=1)
    pkt_k = jnp.where(parked & (rob[:, :, R_SRC] == min_src[:, None]),
                      rob[:, :, R_PKT], BIG)
    psel = jnp.argmin(pkt_k, axis=1).astype(I32)
    can_prom = jnp.any(parked, axis=1) & (pc_cnt < depth)
    prow = rob[node, psel]
    prom_pc = jnp.stack([jnp.ones(n, I32), prow[:, R_TYP], prow[:, R_SRC],
                         prow[:, R_OSRC], prow[:, R_TAG]], axis=-1)
    tail0 = jnp.clip(pc_cnt, 0, depth - 1)
    pc = s.pc.at[node, tail0].set(
        jnp.where(can_prom[:, None], prom_pc, s.pc[node, tail0]))
    rob = rob.at[node, psel].set(jnp.where(can_prom[:, None], 0, prow))
    pc_cnt = pc_cnt + can_prom.astype(I32)

    # ---- ejection into ROB / pending queue ----
    f = s.inp[node, arb.ej_port]                             # (Nl, F) pre-arb flit
    he = arb.has_ej
    stats = bump(stats, "flits_delivered", he)
    single = he & (f[:, F_NFL] == 1)
    multi = he & (f[:, F_NFL] > 1)

    rob_valid = rob[:, :, R_NFL] > 0                         # post promotion
    m = (rob_valid & (rob[:, :, R_SRC] == f[:, None, F_SRC])
         & (rob[:, :, R_PKT] == f[:, None, F_PKT]))          # (Nl, K)
    has_match = jnp.any(m, axis=1)
    match_idx = jnp.argmax(m, axis=1).astype(I32)
    free_idx = jnp.argmax(~rob_valid, axis=1).astype(I32)
    slot = jnp.where(has_match, match_idx, free_idx)
    newslot = multi & ~has_match
    init_row = jnp.stack([f[:, F_SRC], f[:, F_PKT], f[:, F_TYP], f[:, F_TAG],
                          f[:, F_OSRC], f[:, F_NFL], jnp.zeros(n, I32)], axis=-1)
    cur = rob[node, slot]
    row = jnp.where(newslot[:, None], init_row, cur)
    cnt = row[:, R_CNT] + multi.astype(I32)
    row = row.at[:, R_CNT].set(cnt)
    complete_m = multi & (cnt >= row[:, R_NFL])
    full_row = row                    # snapshot before the zeroing below

    completion = single | complete_m
    to_pc = completion & (pc_cnt < depth)
    to_park = completion & ~to_pc
    # a completed slot is freed when its completion enters the queue, and
    # kept (count == total: the "parked" marker) when the queue is full
    row = jnp.where((complete_m & ~to_park)[:, None], 0, row)
    rob = rob.at[node, slot].set(jnp.where(multi[:, None], row, cur))

    # park a single-flit completion in a fresh slot (guaranteed free by
    # phase2's ejection gate)
    rob_valid2 = rob[:, :, R_NFL] > 0
    park_idx = jnp.argmax(~rob_valid2, axis=1).astype(I32)
    park_row = jnp.stack([f[:, F_SRC], f[:, F_PKT], f[:, F_TYP], f[:, F_TAG],
                          f[:, F_OSRC], jnp.ones(n, I32), jnp.ones(n, I32)],
                         axis=-1)
    single_park = single & to_park
    rob = rob.at[node, park_idx].set(
        jnp.where(single_park[:, None], park_row, rob[node, park_idx]))

    row_pc = jnp.stack([
        to_pc.astype(I32),
        jnp.where(single, f[:, F_TYP], full_row[:, R_TYP]),
        jnp.where(single, f[:, F_SRC], full_row[:, R_SRC]),
        jnp.where(single, f[:, F_OSRC], full_row[:, R_OSRC]),
        jnp.where(single, f[:, F_TAG], full_row[:, R_TAG]),
    ], axis=-1)
    row_pc = row_pc * to_pc[:, None].astype(I32)
    tail = jnp.clip(pc_cnt, 0, depth - 1)
    pc = pc.at[node, tail].set(
        jnp.where(to_pc[:, None], row_pc, pc[node, tail]))

    return s._replace(inp=inp_next, rob=rob, pc=pc, stats=stats)


def phase3(s: SimState, cfg: SimConfig, geo: Geometry, ctx: NodeCtx,
           arb: ArbResult) -> SimState:
    return deliver(s, cfg, ctx, arb, transfer_global(cfg, geo, arb.out))
