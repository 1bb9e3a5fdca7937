"""Vectorized phase 1: LSPD cache / directory / migration FSM.

Implements rules S1..S14 of :mod:`repro.core.ref_serial` as masked dense
array ops over all (local) nodes at once.  Every function takes a
:class:`repro.core.state.NodeCtx` carrying *global* node identity as arrays,
so the same code runs on the whole mesh (single device) or on a tile of it
(inside ``shard_map``).  Directory accesses are always performed by the
tag's home node, which makes the ``home`` directory layout fully local.
"""
from __future__ import annotations

from typing import List, NamedTuple, Tuple

import jax.numpy as jnp
import numpy as np

from .config import (
    FLITS_OF,
    INSTALL_L1_ONLY,
    INSTALL_L2,
    MSG_B2,
    MSG_DA,
    MSG_DR,
    MSG_DU,
    MSG_MIG_ACK,
    MSG_NACK,
    MSG_RA,
    MSG_REQ,
    MSG_REQ_FWD,
    MSG_WB,
    ST_DONE,
    ST_IDLE,
    ST_L1_WAIT,
    ST_L2_WAIT,
    ST_WAIT_DATA,
    ST_WAIT_DIR,
    ST_WAIT_MEM,
    SimConfig,
)
from .state import (
    NodeCtx,
    P_OSRC,
    P_SRC,
    P_TAG,
    P_TYP,
    P_VALID,
    SimState,
    bump,
)

I32 = jnp.int32
# numpy constants: importing the simulator must not initialize a jax
# backend (a process that has one holds the chip)
FLITS_TABLE = np.asarray(FLITS_OF, np.int32)
BIG = np.int32(1 << 30)


class Desc(NamedTuple):
    """A packet descriptor slot: one potential enqueue per node."""

    valid: jnp.ndarray  # (Nl,) bool
    typ: jnp.ndarray
    dst: jnp.ndarray
    osrc: jnp.ndarray
    tag: jnp.ndarray


def empty_desc(n: int) -> Desc:
    z = jnp.zeros(n, I32)
    return Desc(jnp.zeros(n, bool), z, z, z, z)


def merge_desc(a: Desc, b: Desc) -> Desc:
    """Merge two descriptor sets with disjoint valid masks."""
    pick = b.valid
    return Desc(a.valid | b.valid,
                jnp.where(pick, b.typ, a.typ),
                jnp.where(pick, b.dst, a.dst),
                jnp.where(pick, b.osrc, a.osrc),
                jnp.where(pick, b.tag, a.tag))


def dir_home_v(cfg: SimConfig, tag: jnp.ndarray,
               central=None) -> jnp.ndarray:
    """Home node of a directory entry.  ``central`` is the traced
    per-scenario knob (``SimState.knob_central``); ``None`` falls back to
    the static config (solo-run callers outside the stepped phases)."""
    home = jnp.where(tag >= 0, tag % cfg.num_nodes, 0)
    if central is None:
        if cfg.centralized_directory:
            return jnp.zeros_like(tag)
        return home
    return jnp.where(central > 0, jnp.zeros_like(tag), home)


def dir_read(dir_loc: jnp.ndarray, cfg: SimConfig, tag: jnp.ndarray,
             mask) -> jnp.ndarray:
    """Directory lookup — only ever executed by the tag's home node."""
    if cfg.dir_layout == "flat":
        idx = jnp.where(mask & (tag >= 0), tag, dir_loc.shape[0] - 1)
        return dir_loc[idx]
    row = jnp.arange(tag.shape[0], dtype=I32)
    col = jnp.where(mask & (tag >= 0), tag // cfg.num_nodes,
                    dir_loc.shape[1] - 1)
    return dir_loc[row, col]


def dir_write(dir_loc: jnp.ndarray, cfg: SimConfig, tag: jnp.ndarray,
              val: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    # masked-off rows are routed to the sink slot and write its current
    # value back, so the sink stays at its initial -1 without a separate
    # full-array reset (dir_read discards sink values via the same mask)
    eff = mask & (tag >= 0)
    if cfg.dir_layout == "flat":
        sink = dir_loc.shape[0] - 1
        idx = jnp.where(eff, tag, sink)
        return dir_loc.at[idx].set(jnp.where(eff, val, dir_loc[idx]))
    row = jnp.arange(tag.shape[0], dtype=I32)
    sink = dir_loc.shape[1] - 1
    col = jnp.where(eff, tag // cfg.num_nodes, sink)
    return dir_loc.at[row, col].set(jnp.where(eff, val, dir_loc[row, col]))


# --------------------------------------------------------------------------
# cache probes
# --------------------------------------------------------------------------

def l2_probe(s: SimState, cfg: SimConfig, tag2: jnp.ndarray):
    """Returns (set_idx, hit_way, hit) for an L2 associative probe."""
    ca = cfg.cache
    node = jnp.arange(tag2.shape[0], dtype=I32)
    si = jnp.where(tag2 >= 0, tag2 % ca.l2_sets, 0)
    tags = s.l2_tag[node, si]                     # (Nl, W2)
    hm = (tags == tag2[:, None]) & (tag2[:, None] >= 0)
    return si, jnp.argmax(hm, axis=1).astype(I32), jnp.any(hm, axis=1)


def l1_probe(s: SimState, cfg: SimConfig, addr: jnp.ndarray):
    ca = cfg.cache
    node = jnp.arange(addr.shape[0], dtype=I32)
    tag1 = jnp.where(addr >= 0, addr >> ca.l1_shift, -1)
    si = jnp.where(tag1 >= 0, tag1 % ca.l1_sets, 0)
    tags = s.l1_tag[node, si]
    hm = (tags == tag1[:, None]) & (tag1[:, None] >= 0)
    return tag1, si, jnp.argmax(hm, axis=1).astype(I32), jnp.any(hm, axis=1)


# --------------------------------------------------------------------------
# installs (S3, S5)
# --------------------------------------------------------------------------

class L2Install(NamedTuple):
    l2_tag: jnp.ndarray
    l2_mig: jnp.ndarray
    l2_last: jnp.ndarray
    l2_streak: jnp.ndarray
    ok: jnp.ndarray            # install succeeded (or already present)
    did: jnp.ndarray           # wrote a new block (touch needed)
    touch_set: jnp.ndarray
    touch_way: jnp.ndarray
    desc_duv: Desc             # remote victim dir delete
    desc_dun: Desc             # remote new-owner dir update
    dirw_vic: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]   # tag, val, mask
    dirw_new: Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]
    n_local_updates: jnp.ndarray
    n_drops: jnp.ndarray


def install_l2(s: SimState, cfg: SimConfig, ctx: NodeCtx, mask: jnp.ndarray,
               tag2: jnp.ndarray) -> L2Install:
    """S5 — masked L2 install with victim eviction + directory maintenance."""
    ca = cfg.cache
    n = ctx.node_id.shape[0]
    node = jnp.arange(n, dtype=I32)
    nid = ctx.node_id
    si, hw, present_any = l2_probe(s, cfg, jnp.where(mask, tag2, -1))
    present = mask & present_any
    need = mask & ~present

    tags = s.l2_tag[node, si]                        # (Nl, W2)
    migf = s.l2_mig[node, si]
    lru = s.l2_lru[node, si]
    inv = tags < 0
    has_inv = jnp.any(inv, axis=1)
    inv_way = jnp.argmax(inv, axis=1).astype(I32)
    lru_key = lru + migf * BIG
    lru_way = jnp.argmin(lru_key, axis=1).astype(I32)
    all_mig = jnp.all(migf > 0, axis=1)
    vic_way = jnp.where(has_inv, inv_way, lru_way)
    fail = need & ~has_inv & all_mig
    do = need & ~fail
    vic_valid = do & ~has_inv
    vtag = tags[node, vic_way]

    # victim directory delete (S4)
    homev = dir_home_v(cfg, vtag, s.knob_central)
    vlocal = vic_valid & (homev == nid)
    vremote = vic_valid & ~vlocal
    cur_v = dir_read(s.dir_loc, cfg, vtag, vlocal)
    vval = jnp.where(cur_v == nid, -1, cur_v)
    desc_duv = Desc(vremote, jnp.full(n, MSG_DU, I32), homev,
                    jnp.full(n, -1, I32), vtag)

    # write the new block
    upd = do
    l2_tag = s.l2_tag.at[node, si, vic_way].set(
        jnp.where(upd, tag2, s.l2_tag[node, si, vic_way]))
    l2_mig = s.l2_mig.at[node, si, vic_way].set(
        jnp.where(upd, 0, s.l2_mig[node, si, vic_way]))
    l2_last = s.l2_last.at[node, si, vic_way].set(
        jnp.where(upd, -1, s.l2_last[node, si, vic_way]))
    l2_streak = s.l2_streak.at[node, si, vic_way].set(
        jnp.where(upd, 0, s.l2_streak[node, si, vic_way]))

    # new-owner directory update
    homen = dir_home_v(cfg, tag2, s.knob_central)
    nlocal = do & (homen == nid)
    nremote = do & ~nlocal
    desc_dun = Desc(nremote, jnp.full(n, MSG_DU, I32), homen, nid, tag2)

    return L2Install(
        l2_tag, l2_mig, l2_last, l2_streak,
        ok=present | do, did=do,
        touch_set=si, touch_way=vic_way,
        desc_duv=desc_duv, desc_dun=desc_dun,
        dirw_vic=(vtag, vval, vlocal),
        dirw_new=(tag2, nid, nlocal),
        n_local_updates=jnp.sum(vlocal.astype(I32)) + jnp.sum(nlocal.astype(I32)),
        n_drops=jnp.sum(fail.astype(I32)),
    )


class L1Install(NamedTuple):
    l1_tag: jnp.ndarray
    l1_owner: jnp.ndarray
    touch_set: jnp.ndarray
    touch_way: jnp.ndarray
    touch: jnp.ndarray         # mask: a touch happened
    desc_wb: Desc
    n_wb_sent: jnp.ndarray
    n_wb_miss: jnp.ndarray


def install_l1(s: SimState, cfg: SimConfig, ctx: NodeCtx, mask: jnp.ndarray,
               addr: jnp.ndarray, owner: jnp.ndarray) -> L1Install:
    """S3 — masked L1 install with victim write-back."""
    ca = cfg.cache
    n = ctx.node_id.shape[0]
    node = jnp.arange(n, dtype=I32)
    nid = ctx.node_id
    tag1, si, hw, present_any = l1_probe(s, cfg, jnp.where(mask, addr, -1))
    present = mask & present_any
    need = mask & ~present

    tags = s.l1_tag[node, si]
    lru = s.l1_lru[node, si]
    inv = tags < 0
    has_inv = jnp.any(inv, axis=1)
    inv_way = jnp.argmax(inv, axis=1).astype(I32)
    lru_way = jnp.argmin(lru, axis=1).astype(I32)
    vic_way = jnp.where(has_inv, inv_way, lru_way)
    vic_valid = need & ~has_inv
    vtag1 = tags[node, vic_way]
    vowner = s.l1_owner[node, si, vic_way]
    vtag2 = jnp.where(vtag1 >= 0, vtag1 >> (ca.l2_shift - ca.l1_shift), -1)

    # local write-back: does our own L2 still hold the victim's block?
    wb_local = vic_valid & (vowner == nid)
    _, _, l2has = l2_probe(s, cfg, jnp.where(wb_local, vtag2, -1))
    n_wb_miss = jnp.sum((wb_local & ~l2has).astype(I32))
    wb_remote = vic_valid & (vowner >= 0) & (vowner != nid)
    desc_wb = Desc(wb_remote, jnp.full(n, MSG_WB, I32), vowner, nid, vtag2)

    way = jnp.where(present, hw, vic_way)
    w = present | need
    l1_tag = s.l1_tag.at[node, si, way].set(
        jnp.where(w, tag1, s.l1_tag[node, si, way]))
    l1_owner = s.l1_owner.at[node, si, way].set(
        jnp.where(w, owner, s.l1_owner[node, si, way]))
    return L1Install(l1_tag, l1_owner, si, way, w, desc_wb,
                     jnp.sum(wb_remote.astype(I32)), n_wb_miss)


# --------------------------------------------------------------------------
# send-queue commit (S2)
# --------------------------------------------------------------------------

def commit_queue(s: SimState, cfg: SimConfig, descs: List[Desc]):
    """Append descriptors (in slot order = serial enqueue order) to the
    per-node packet ring buffer; whole packets are dropped when full.

    Single batched scatter: descriptor d_i lands at ring offset equal to
    the number of earlier accepted descriptors; rejected/invalid rows are
    routed to the sink slot (index ``send_queue``) so indices never
    collide.  (Perf iteration C1: was 3 sequential full-array scatter
    rounds per phase — 2x the q_desc HBM traffic of the batched form.)
    """
    n = s.q_size.shape[0]
    node = jnp.arange(n, dtype=I32)
    qp = cfg.send_queue
    q_size, pkt_ctr = s.q_size, s.pkt_ctr

    offs, accs, rows = [], [], []
    off = jnp.zeros(n, I32)
    drops = jnp.zeros((), I32)
    for d in descs:
        ok = d.valid & (q_size + off < qp)
        drops = drops + jnp.sum((d.valid & ~ok).astype(I32))
        pkt = (pkt_ctr + off) & (cfg.pkt_wrap - 1)
        nfl = jnp.asarray(FLITS_TABLE)[jnp.clip(d.typ, 0, len(FLITS_OF) - 1)]
        rows.append(jnp.stack([d.typ, d.dst, d.osrc, d.tag, pkt, nfl],
                              axis=-1))
        offs.append(off)
        accs.append(ok)
        off = off + ok.astype(I32)

    acc = jnp.stack(accs, axis=1)                       # (N, D)
    pos = jnp.stack([(s.q_head + q_size + o) % qp for o in offs], axis=1)
    pos = jnp.where(acc, pos, qp)                       # sink slot
    row = jnp.stack(rows, axis=1)                       # (N, D, 6)
    # rejected rows land in the sink slot (index qp); it is never read —
    # injection only indexes q_head % qp — so it is left dirty on purpose
    # (zeroing it cost a full q_desc rewrite per commit)
    q_desc = s.q_desc.at[node[:, None], pos].set(row)
    stats = bump(s.stats, "send_drop", drops)
    return s._replace(q_desc=q_desc, q_size=q_size + off,
                      pkt_ctr=pkt_ctr + off, stats=stats)


# --------------------------------------------------------------------------
# phase 1a — inbound completion handlers
# --------------------------------------------------------------------------

#: S14 — worst-case packets a handler may enqueue, by message type
#: (REQ, RA, NACK, DA, DR, DU, WB, B2, MIG_ACK, REQ_FWD)
NEED_TABLE = np.asarray([2, 1, 0, 1, 1, 0, 0, 3, 0, 2], np.int32)


def _l1_install_would_wb(s: SimState, cfg: SimConfig, ctx: NodeCtx,
                         mask: jnp.ndarray, addr: jnp.ndarray) -> jnp.ndarray:
    """Need probe: would :func:`install_l1` send a remote victim
    write-back?  Pure reads — mirrors install_l1's victim selection
    (first invalid way, else LRU) without the install scatters; must stay
    in sync with it (and with ``ref_serial._exact_need``'s RA branch)."""
    node = jnp.arange(addr.shape[0], dtype=I32)
    _, si, _, present_any = l1_probe(s, cfg, jnp.where(mask, addr, -1))
    need_i = mask & ~present_any
    tags = s.l1_tag[node, si]
    has_inv = jnp.any(tags < 0, axis=1)
    lru_way = jnp.argmin(s.l1_lru[node, si], axis=1).astype(I32)
    vowner = s.l1_owner[node, si, lru_way]
    return need_i & ~has_inv & (vowner >= 0) & (vowner != ctx.node_id)


def _l2_install_du_count(s: SimState, cfg: SimConfig, ctx: NodeCtx,
                         mask: jnp.ndarray, tag2: jnp.ndarray) -> jnp.ndarray:
    """Need probe: how many remote directory updates (DU packets) would
    :func:`install_l2` enqueue?  Pure reads — mirrors install_l2's
    victim selection (invalid way, else non-migrating LRU, else fail)
    without the install scatters; must stay in sync with it (and with
    ``ref_serial._exact_need``'s B2 branch)."""
    node = jnp.arange(tag2.shape[0], dtype=I32)
    nid = ctx.node_id
    si, _, present_any = l2_probe(s, cfg, jnp.where(mask, tag2, -1))
    need_i = mask & ~present_any
    tags = s.l2_tag[node, si]
    migf = s.l2_mig[node, si]
    has_inv = jnp.any(tags < 0, axis=1)
    lru_key = s.l2_lru[node, si] + migf * BIG
    lru_way = jnp.argmin(lru_key, axis=1).astype(I32)
    all_mig = jnp.all(migf > 0, axis=1)
    do = need_i & ~(~has_inv & all_mig)           # install fails when every
    vic_valid = do & ~has_inv                     # way is pinned migrating
    vtag = tags[node, lru_way]
    duv = vic_valid & (dir_home_v(cfg, vtag, s.knob_central) != nid)
    dun = do & (dir_home_v(cfg, tag2, s.knob_central) != nid)
    return duv.astype(I32) + dun.astype(I32)


def phase1a(s: SimState, cfg: SimConfig, ctx: NodeCtx) -> SimState:
    n = ctx.node_id.shape[0]
    node = jnp.arange(n, dtype=I32)
    nid = ctx.node_id
    stats = s.stats

    # the handler always serves the *head* of the pending-completion queue
    # (FIFO; depth 1 = the paper's single S14 register)
    head = s.pc[:, 0]
    pc_valid = head[:, P_VALID] > 0
    typ = head[:, P_TYP]
    src = head[:, P_SRC]
    osrc = head[:, P_OSRC]
    tag = head[:, P_TAG]

    p_req = pc_valid & ((typ == MSG_REQ) | (typ == MSG_REQ_FWD))
    p_ra = pc_valid & (typ == MSG_RA)
    p_da = pc_valid & (typ == MSG_DA)
    p_dr = pc_valid & (typ == MSG_DR)
    p_b2 = pc_valid & (typ == MSG_B2)
    p_wb = pc_valid & (typ == MSG_WB)
    p_ack = pc_valid & (typ == MSG_MIG_ACK)

    # shared L2 probe on the completion tag (masked by the head's message
    # type, not by the fire decision — the exact-need gate below must see
    # the probe before deciding whether the handler fires this cycle)
    probe_mask = p_req | p_wb | p_ack
    si, hw, l2hit_any = l2_probe(s, cfg, jnp.where(probe_mask, tag, -1))

    # S14: backpressure — defer until the send queue can hold the response.
    # pc_depth=1 (the paper's single completion register) gates on the
    # worst-case NEED table, bit-identical to the seed semantics.  With a
    # queue (pc_depth > 1) the head is gated on the EXACT number of
    # packets this handler will enqueue — the drain-from-head half of the
    # ejection guarantee: a head whose response actually fits never
    # blocks the queue (the worst-case table could wedge a node whose
    # send queue hovers one slot short of the worst case forever).
    if cfg.pc_depth > 1:
        req_hit_p = p_req & l2hit_any
        mig_ok_p = (req_hit_p & (s.knob_mig > 0) & (osrc != nid)
                    & (s.l2_mig[node, si, hw] == 0))
        streak_p = jnp.where(s.l2_last[node, si, hw] == osrc,
                             s.l2_streak[node, si, hw] + 1, 1)
        trig_p = mig_ok_p & (streak_p >= s.knob_mig_thr)
        ra_ok_p = p_ra & (s.st == ST_WAIT_DATA)
        ra_wb_p = _l1_install_would_wb(s, cfg, ctx, ra_ok_p, s.pend_addr)
        b2_du_p = _l2_install_du_count(s, cfg, ctx, p_b2, tag)
        dr_req_p = p_dr & (s.st == ST_WAIT_DIR) & (osrc >= 0)
        need = (p_req.astype(I32) + trig_p.astype(I32)        # RA/NACK/FWD + B2
                + ra_wb_p.astype(I32)                         # RA victim WB
                + p_da.astype(I32)                            # DR reply
                + dr_req_p.astype(I32)                        # REQ to owner
                + p_b2.astype(I32)                            # MIG_ACK
                + b2_du_p)                                    # install_l2 DUs
    else:
        need = jnp.asarray(NEED_TABLE)[jnp.clip(typ, 0, 9)]
    valid = pc_valid & (s.q_size + need <= cfg.send_queue)
    if cfg.pc_depth > 1:
        # guaranteed drain: a FULL queue must make progress every cycle
        # (its node cannot eject, so it may never get to inject and free
        # send-queue space on its own) — the head fires even without
        # space; response packets that do not fit are dropped whole by
        # commit_queue (send_drop) and recovered by the requester's
        # req_timeout retry.
        pc_full = (jnp.sum((s.pc[:, :, P_VALID] > 0).astype(I32), axis=1)
                   >= cfg.pc_depth)
        valid = valid | (pc_valid & pc_full)

    is_req = valid & p_req
    is_ra = valid & p_ra
    is_nack = valid & (typ == MSG_NACK)
    is_da = valid & p_da
    is_dr = valid & p_dr
    is_du = valid & (typ == MSG_DU)
    is_wb = valid & p_wb
    is_b2 = valid & p_b2
    is_ack = valid & p_ack
    l2hit = (is_req | is_wb | is_ack) & l2hit_any

    d0 = empty_desc(n)
    d1 = empty_desc(n)
    d2 = empty_desc(n)

    st, ctr, imode = s.st, s.ctr, s.install_mode
    l2_tag, l2_mig = s.l2_tag, s.l2_mig
    l2_last, l2_streak = s.l2_last, s.l2_streak
    fwd_tag, fwd_dst, fwd_ptr = s.fwd_tag, s.fwd_dst, s.fwd_ptr
    dir_loc = s.dir_loc

    # ---- REQ / REQ_FWD: remote access service + migration trigger ----
    req_hit = is_req & l2hit
    req_miss = is_req & ~l2hit
    stats = bump(stats, "req_rcvd", is_req)
    stats = bump(stats, "reply_sent", req_hit)
    d0 = merge_desc(d0, Desc(req_hit, jnp.full(n, MSG_RA, I32), osrc, osrc, tag))

    mig_ok = (req_hit & (s.knob_mig > 0) & (osrc != nid)
              & (l2_mig[node, si, hw] == 0))
    streak_new = jnp.where(l2_last[node, si, hw] == osrc,
                           l2_streak[node, si, hw] + 1, 1)
    l2_last = l2_last.at[node, si, hw].set(
        jnp.where(mig_ok, osrc, l2_last[node, si, hw]))
    l2_streak = l2_streak.at[node, si, hw].set(
        jnp.where(mig_ok, streak_new, l2_streak[node, si, hw]))
    trig = mig_ok & (streak_new >= s.knob_mig_thr)
    l2_mig = l2_mig.at[node, si, hw].set(
        jnp.where(trig, 1, l2_mig[node, si, hw]))
    d1 = merge_desc(d1, Desc(trig, jnp.full(n, MSG_B2, I32), osrc, nid, tag))
    stats = bump(stats, "migrations", trig)

    fwd_hm = (fwd_tag == tag[:, None]) & req_miss[:, None]
    fwd_found = jnp.any(fwd_hm, axis=1)
    fwd_to = fwd_dst[node, jnp.argmax(fwd_hm, axis=1)]
    redir = req_miss & fwd_found & (fwd_to >= 0) & (fwd_to != nid)
    trap = req_miss & ~redir
    d0 = merge_desc(d0, Desc(redir, jnp.full(n, MSG_REQ_FWD, I32), fwd_to, osrc, tag))
    d0 = merge_desc(d0, Desc(trap, jnp.full(n, MSG_NACK, I32), osrc, osrc, tag))
    stats = bump(stats, "redirection", redir)
    stats = bump(stats, "trap", trap)

    # ---- RA (data reply) ----
    ra_ok = is_ra & (st == ST_WAIT_DATA)
    stats = bump(stats, "reply_rcvd", ra_ok)
    stats = bump(stats, "stray", is_ra & ~ra_ok)
    ins1 = install_l1(s, cfg, ctx, ra_ok, s.pend_addr, src)
    l1_tag_, l1_owner_ = ins1.l1_tag, ins1.l1_owner
    d0 = merge_desc(d0, ins1.desc_wb)
    stats = bump(stats, "wb_sent", ins1.n_wb_sent)
    stats = bump(stats, "wb_miss", ins1.n_wb_miss)
    st = jnp.where(ra_ok, ST_IDLE, st)

    # ---- NACK (trap reply) ----
    nk_ok = is_nack & (st == ST_WAIT_DATA)
    stats = bump(stats, "stray", is_nack & ~nk_ok)
    st = jnp.where(nk_ok, ST_WAIT_MEM, st)
    ctr = jnp.where(nk_ok, cfg.mem_cycles, ctr)
    imode = jnp.where(nk_ok, INSTALL_L1_ONLY, imode)
    stats = bump(stats, "mem_req", nk_ok)

    # ---- DA (directory lookup at home, S6 reserve-on-miss) ----
    stats = bump(stats, "dir_search", is_da)
    owner0 = dir_read(dir_loc, cfg, tag, is_da)
    reserve = is_da & ((owner0 < 0) | (owner0 == osrc))
    owner_rep = jnp.where(reserve, -1, owner0)
    d0 = merge_desc(d0, Desc(is_da, jnp.full(n, MSG_DR, I32), osrc, owner_rep, tag))

    # ---- DR (directory reply) ----
    dr_ok = is_dr & (st == ST_WAIT_DIR)
    stats = bump(stats, "stray", is_dr & ~dr_ok)
    dr_owner = osrc
    dr_req = dr_ok & (dr_owner >= 0)
    dr_mem = dr_ok & (dr_owner < 0)
    d0 = merge_desc(d0, Desc(dr_req, jnp.full(n, MSG_REQ, I32), dr_owner, nid, tag))
    stats = bump(stats, "req_made", dr_req)
    st = jnp.where(dr_req, ST_WAIT_DATA, st)
    if cfg.pc_depth > 1:   # arm the transaction timeout (see phase1b)
        ctr = jnp.where(dr_req, cfg.req_timeout, ctr)
    st = jnp.where(dr_mem, ST_WAIT_MEM, st)
    ctr = jnp.where(dr_mem, cfg.mem_cycles, ctr)
    imode = jnp.where(dr_mem, INSTALL_L2, imode)
    stats = bump(stats, "mem_req", dr_mem)

    # ---- DU (directory update) ----
    stats = bump(stats, "dir_update", is_du)
    du_cur = dir_read(dir_loc, cfg, tag, is_du)
    du_val = jnp.where(osrc < 0,
                       jnp.where(du_cur == src, -1, du_cur),
                       osrc)

    # ---- WB (L1 victim write-back arriving at the block's L2 home) ----
    wb_hit = is_wb & l2hit
    stats = bump(stats, "wb_rcvd", is_wb)
    stats = bump(stats, "wb_miss", is_wb & ~l2hit)

    # ---- B2 (migration arrival) ----
    stats = bump(stats, "migrations_done", is_b2)
    s_tmp = s._replace(l2_tag=l2_tag, l2_mig=l2_mig, l2_last=l2_last,
                       l2_streak=l2_streak)
    ins2 = install_l2(s_tmp, cfg, ctx, is_b2, tag)
    l2_tag, l2_mig = ins2.l2_tag, ins2.l2_mig
    l2_last, l2_streak = ins2.l2_last, ins2.l2_streak
    d0 = merge_desc(d0, ins2.desc_duv)
    d1 = merge_desc(d1, ins2.desc_dun)
    ack_osrc = jnp.where(ins2.ok, nid, -1)
    d2 = merge_desc(d2, Desc(is_b2, jnp.full(n, MSG_MIG_ACK, I32), src, ack_osrc, tag))
    stats = bump(stats, "dir_update", ins2.n_local_updates)
    stats = bump(stats, "l2_install_drop", ins2.n_drops)

    # ---- MIG_ACK (S13) ----
    ak_succ = is_ack & (osrc >= 0) & l2hit & (l2_mig[node, si, hw] > 0)
    l2_tag = l2_tag.at[node, si, hw].set(
        jnp.where(ak_succ, -1, l2_tag[node, si, hw]))
    l2_mig = l2_mig.at[node, si, hw].set(
        jnp.where(ak_succ, 0, l2_mig[node, si, hw]))
    ak_ins = is_ack & (osrc >= 0)
    p = fwd_ptr % cfg.fwd_entries
    fwd_tag = fwd_tag.at[node, p].set(
        jnp.where(ak_ins, tag, fwd_tag[node, p]))
    fwd_dst = fwd_dst.at[node, p].set(
        jnp.where(ak_ins, osrc, fwd_dst[node, p]))
    fwd_ptr = jnp.where(ak_ins, p + 1, fwd_ptr)
    ak_fail = is_ack & (osrc < 0) & l2hit
    l2_mig = l2_mig.at[node, si, hw].set(
        jnp.where(ak_fail, 0, l2_mig[node, si, hw]))
    l2_streak = l2_streak.at[node, si, hw].set(
        jnp.where(ak_fail, 0, l2_streak[node, si, hw]))

    # ---- directory scatters (disjoint per entry — one handler per node,
    # same entry ⇒ same home ⇒ same node) ----
    mA = (is_da & reserve) | is_du | ins2.dirw_vic[2]
    idxA = jnp.where(is_da & reserve, tag,
                     jnp.where(is_du, tag, ins2.dirw_vic[0]))
    valA = jnp.where(is_da & reserve, osrc,
                     jnp.where(is_du, du_val, ins2.dirw_vic[1]))
    dir_loc = dir_write(dir_loc, cfg, idxA, valA, mA)
    dir_loc = dir_write(dir_loc, cfg, ins2.dirw_new[0], ins2.dirw_new[1],
                        ins2.dirw_new[2])

    # ---- single 1a LRU touch site (serial: ≤1 touch per node in 1a) ----
    l2touch = req_hit | wb_hit | ins2.did
    l1touch = ins1.touch
    any_touch = l2touch | l1touch
    clock = s.lru_clock + any_touch.astype(I32)
    tsi = jnp.where(ins2.did, ins2.touch_set, si)
    twy = jnp.where(ins2.did, ins2.touch_way, hw)
    l2_lru = s.l2_lru.at[node, tsi, twy].set(
        jnp.where(l2touch, clock, s.l2_lru[node, tsi, twy]))
    l1_lru = s.l1_lru.at[node, ins1.touch_set, ins1.touch_way].set(
        jnp.where(l1touch, clock, s.l1_lru[node, ins1.touch_set, ins1.touch_way]))

    s = s._replace(
        st=st, ctr=ctr, install_mode=imode, lru_clock=clock,
        l1_tag=l1_tag_, l1_owner=l1_owner_, l1_lru=l1_lru,
        l2_tag=l2_tag, l2_lru=l2_lru, l2_mig=l2_mig, l2_last=l2_last,
        l2_streak=l2_streak, dir_loc=dir_loc,
        fwd_tag=fwd_tag, fwd_dst=fwd_dst, fwd_ptr=fwd_ptr,
        # pop the served head: shift the queue down one slot (depth 1:
        # this zeroes the register, exactly the old behaviour)
        pc=jnp.where(valid[:, None, None],
                     jnp.concatenate([s.pc[:, 1:],
                                      jnp.zeros_like(s.pc[:, :1])], axis=1),
                     s.pc),
        stats=stats,
    )
    return commit_queue(s, cfg, [d0, d1, d2])


# --------------------------------------------------------------------------
# phase 1b — trace-driven FSM
# --------------------------------------------------------------------------

def _next_addr(s: SimState, cfg: SimConfig):
    m = s.trace.shape[1]
    node = jnp.arange(s.trace.shape[0], dtype=I32)
    ptr = jnp.clip(s.tr_ptr, 0, m - 1)
    # trace is the one leaf widen_state leaves in storage dtype (read-only
    # (N, M) block) — widen after the gather, not the whole array
    a = s.trace[node, ptr].astype(I32)
    exhausted = (s.tr_ptr >= m) | (a < 0)
    return jnp.where(exhausted, -1, a), exhausted


def phase1b(s: SimState, cfg: SimConfig, ctx: NodeCtx) -> SimState:
    n = ctx.node_id.shape[0]
    ca = cfg.cache
    node = jnp.arange(n, dtype=I32)
    nid = ctx.node_id
    stats = s.stats
    st, ctr = s.st, s.ctr

    d0 = empty_desc(n)
    d1 = empty_desc(n)
    d2 = empty_desc(n)

    addr, exhausted = _next_addr(s, cfg)

    # S14: per-state send-queue space requirements gate FSM "fire" points
    space = cfg.send_queue - s.q_size

    # ---- IDLE: consume one trace address ----
    idle = st == ST_IDLE
    go_done = idle & exhausted
    consume = idle & ~exhausted
    tag1, si1, hw1, l1hit_any = l1_probe(s, cfg, jnp.where(consume, addr, -1))
    l1hit = consume & l1hit_any
    l1miss = consume & ~l1hit_any
    stats = bump(stats, "l1_hits", l1hit)
    stats = bump(stats, "l1_misses", l1miss)
    tr_ptr = s.tr_ptr + consume.astype(I32)
    pend_addr = jnp.where(l1miss, addr, s.pend_addr)
    st = jnp.where(go_done, ST_DONE, st)
    st = jnp.where(l1miss, ST_L1_WAIT, st)
    ctr = jnp.where(l1miss, cfg.l1_miss_cycles, ctr)

    # ---- L1_WAIT: countdown then local-L2 probe / directory ----
    l1w = (s.st == ST_L1_WAIT)
    ctr = jnp.where(l1w, ctr - 1, ctr)
    l1w_fire0 = l1w & (ctr <= 0)
    l1w_fire = l1w_fire0 & (space >= 1)
    ctr = jnp.where(l1w_fire0 & ~l1w_fire, 1, ctr)
    tag2 = jnp.where(s.pend_addr >= 0, s.pend_addr >> ca.l2_shift, -1)
    _, _, l2hit_any = l2_probe(s, cfg, jnp.where(l1w_fire, tag2, -1))
    l2hit = l1w_fire & l2hit_any
    l2miss = l1w_fire & ~l2hit_any
    stats = bump(stats, "l2_local_hits", l2hit)
    stats = bump(stats, "l2_local_misses", l2miss)
    st = jnp.where(l2hit, ST_L2_WAIT, st)
    ctr = jnp.where(l2hit, cfg.l2_hit_cycles, ctr)

    home = dir_home_v(cfg, tag2, s.knob_central)
    inline = l2miss & (home == nid)           # S8
    remote = l2miss & ~inline
    stats = bump(stats, "dir_search", inline)
    owner0 = dir_read(s.dir_loc, cfg, tag2, inline)
    inl_req = inline & (owner0 >= 0) & (owner0 != nid)
    inl_mem = inline & ~inl_req
    d0 = merge_desc(d0, Desc(inl_req, jnp.full(n, MSG_REQ, I32), owner0, nid, tag2))
    stats = bump(stats, "req_made", inl_req)
    st = jnp.where(inl_req, ST_WAIT_DATA, st)
    st = jnp.where(inl_mem, ST_WAIT_MEM, st)
    ctr = jnp.where(inl_mem, cfg.mem_cycles, ctr)
    imode = jnp.where(inl_mem, INSTALL_L2, s.install_mode)
    stats = bump(stats, "mem_req", inl_mem)
    dir_loc = dir_write(s.dir_loc, cfg, tag2, nid, inl_mem)   # reserve (S6)

    d0 = merge_desc(d0, Desc(remote, jnp.full(n, MSG_DA, I32), home, nid, tag2))
    st = jnp.where(remote, ST_WAIT_DIR, st)
    if cfg.pc_depth > 1:   # arm the transaction timeout
        ctr = jnp.where(remote | inl_req, cfg.req_timeout, ctr)

    # ---- L2_WAIT: countdown then move block into L1 ----
    l2w = (s.st == ST_L2_WAIT)
    ctr = jnp.where(l2w, ctr - 1, ctr)
    l2w_fire0 = l2w & (ctr <= 0)
    l2w_fire = l2w_fire0 & (space >= 1)
    ctr = jnp.where(l2w_fire0 & ~l2w_fire, 1, ctr)
    si2f, hw2f, l2f_hit = l2_probe(s, cfg, jnp.where(l2w_fire, tag2, -1))
    l2f_touch = l2w_fire & l2f_hit

    # ---- WAIT_MEM: countdown then install ----
    wm = (s.st == ST_WAIT_MEM)
    ctr = jnp.where(wm, ctr - 1, ctr)
    wm_fire0 = wm & (ctr <= 0)
    wm_fire = wm_fire0 & (space >= 3)
    ctr = jnp.where(wm_fire0 & ~wm_fire, 1, ctr)
    wm_wait = wm & ~wm_fire0
    wm_l2 = wm_fire & (s.install_mode == INSTALL_L2)
    wm_l1o = wm_fire & (s.install_mode == INSTALL_L1_ONLY)

    s_mid = s._replace(dir_loc=dir_loc)
    ins2 = install_l2(s_mid, cfg, ctx, wm_l2, tag2)
    d0 = merge_desc(d0, ins2.desc_duv)
    d1 = merge_desc(d1, ins2.desc_dun)
    stats = bump(stats, "dir_update", ins2.n_local_updates)
    stats = bump(stats, "l2_install_drop", ins2.n_drops)
    dir_loc = dir_write(dir_loc, cfg, ins2.dirw_vic[0], ins2.dirw_vic[1],
                        ins2.dirw_vic[2])
    dir_loc = dir_write(dir_loc, cfg, ins2.dirw_new[0], ins2.dirw_new[1],
                        ins2.dirw_new[2])

    # ---- WAIT_DIR / WAIT_DATA transaction timeout (pc_depth > 1 only):
    #      restart with a fresh DA to the tag's home — retransmit-once
    #      recovery for responses the guaranteed drain had to drop; a
    #      stale duplicate response later lands in `stray` ----
    if cfg.pc_depth > 1:
        wt = (s.st == ST_WAIT_DIR) | (s.st == ST_WAIT_DATA)
        ctr = jnp.where(wt, ctr - 1, ctr)
        rt_fire0 = wt & (ctr <= 0)
        rt_fire = rt_fire0 & (space >= 1)
        ctr = jnp.where(rt_fire0 & ~rt_fire, 1, ctr)
        d0 = merge_desc(d0, Desc(rt_fire, jnp.full(n, MSG_DA, I32), home,
                                 nid, tag2))
        st = jnp.where(rt_fire, ST_WAIT_DIR, st)
        ctr = jnp.where(rt_fire, cfg.req_timeout, ctr)

    # ---- hit-under-miss (S7) in WAIT_DIR / WAIT_DATA / counting WAIT_MEM ----
    waiting = (s.st == ST_WAIT_DIR) | (s.st == ST_WAIT_DATA) | wm_wait
    h_addr, h_exh = _next_addr(s._replace(tr_ptr=tr_ptr), cfg)
    h_try = waiting & ~h_exh
    htag1, hsi, hhw, hum_hit_any = l1_probe(s, cfg, jnp.where(h_try, h_addr, -1))
    hum = h_try & hum_hit_any
    stats = bump(stats, "l1_hits", hum)
    tr_ptr = tr_ptr + hum.astype(I32)

    # ---- touch site 2 (first 1b touch: IDLE L1 hit | L2_WAIT L2 touch |
    #      install_l2 new-block touch | hit-under-miss L1 touch) ----
    t2_l1 = l1hit | hum
    t2_l2 = l2f_touch | ins2.did
    t2 = t2_l1 | t2_l2
    clock = s.lru_clock + t2.astype(I32)
    t2_l1_set = jnp.where(l1hit, si1, hsi)
    t2_l1_way = jnp.where(l1hit, hw1, hhw)
    l1_lru = s.l1_lru.at[node, t2_l1_set, t2_l1_way].set(
        jnp.where(t2_l1, clock, s.l1_lru[node, t2_l1_set, t2_l1_way]))
    t2_l2_set = jnp.where(l2f_touch, si2f, ins2.touch_set)
    t2_l2_way = jnp.where(l2f_touch, hw2f, ins2.touch_way)
    l2_lru = s.l2_lru.at[node, t2_l2_set, t2_l2_way].set(
        jnp.where(t2_l2, clock, s.l2_lru[node, t2_l2_set, t2_l2_way]))

    # ---- install_l1 (touch site 3): L2_WAIT refill, WAIT_MEM installs ----
    il1_mask = l2w_fire | wm_fire
    il1_owner = jnp.where(wm_l1o, -1, nid)
    s_mid2 = s._replace(
        l1_lru=l1_lru, l2_lru=l2_lru, lru_clock=clock,
        l2_tag=ins2.l2_tag, l2_mig=ins2.l2_mig, l2_last=ins2.l2_last,
        l2_streak=ins2.l2_streak,
    )
    ins1 = install_l1(s_mid2, cfg, ctx, il1_mask, s.pend_addr, il1_owner)
    d2 = merge_desc(d2, ins1.desc_wb)
    stats = bump(stats, "wb_sent", ins1.n_wb_sent)
    stats = bump(stats, "wb_miss", ins1.n_wb_miss)
    clock = clock + ins1.touch.astype(I32)
    l1_lru = l1_lru.at[node, ins1.touch_set, ins1.touch_way].set(
        jnp.where(ins1.touch, clock, l1_lru[node, ins1.touch_set, ins1.touch_way]))
    st = jnp.where(il1_mask, ST_IDLE, st)

    s = s._replace(
        st=st, ctr=ctr, tr_ptr=tr_ptr, pend_addr=pend_addr,
        install_mode=imode, lru_clock=clock,
        l1_tag=ins1.l1_tag, l1_lru=l1_lru, l1_owner=ins1.l1_owner,
        l2_tag=ins2.l2_tag, l2_lru=l2_lru, l2_mig=ins2.l2_mig,
        l2_last=ins2.l2_last, l2_streak=ins2.l2_streak,
        dir_loc=dir_loc, stats=stats,
    )
    return commit_queue(s, cfg, [d0, d1, d2])
