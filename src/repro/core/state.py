"""Vectorized simulator state (structure-of-arrays pytree).

The GPU version's ``struct Flit / Router / Core`` (paper §6.2.1) become dense
arrays over all N = rows*cols nodes — the TPU-native layout
(DESIGN.md §2).  All semantic rules S1..S13 are defined in
:mod:`repro.core.ref_serial`; this module only lays out state.

Storage layout is configurable (``SimConfig.state_dtype_policy``):
``"wide"`` keeps every leaf int32; ``"packed"`` gives each leaf the
smallest of int8/int16/int32 that holds its validated value bounds
(:func:`leaf_dtypes`).  All phase code computes in int32 either way —
:func:`widen_state` / :func:`narrow_state` cast at the cycle boundary
(docs/architecture.md "State layout and memory budget").

Statistics are carried as a base-2**30 (hi, lo) int32 pair (``stats_hi``,
``stats``) because jax has no int64 without the global x64 switch: the
low word is folded into the high word once per cycle
(:func:`fold_stats`), so the low word always equals ``total mod 2**30``
and counters cannot wrap at 43k nodes x long runs.  Hosts reconstruct
exact int64 totals with :func:`stats_totals`.

Flit field order (axis -1 of ``inp`` / arbitration candidates):
    0 VALID, 1 AGE, 2 SRC, 3 DST, 4 OSRC, 5 TYP, 6 TAG, 7 PKT, 8 FID, 9 NFL
Send-queue descriptor fields: 0 TYP, 1 DST, 2 OSRC, 3 TAG, 4 PKT, 5 NFL
ROB slot fields: 0 SRC, 1 PKT, 2 TYP, 3 TAG, 4 OSRC, 5 NFL, 6 CNT
Pending-completion slot fields: 0 VALID, 1 TYP, 2 SRC, 3 OSRC, 4 TAG
(the pending-completion state is a per-node FIFO of ``cfg.pc_depth`` such
slots, head at index 0 — depth 1 is the paper's single S14 register)
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import NUM_MSG_TYPES, NUM_PORTS, SimConfig
from .ref_serial import STAT_NAMES
from .tracing import span

# flit fields
F_VALID, F_AGE, F_SRC, F_DST, F_OSRC, F_TYP, F_TAG, F_PKT, F_FID, F_NFL = range(10)
NUM_F = 10
# queue descriptor fields
Q_TYP, Q_DST, Q_OSRC, Q_TAG, Q_PKT, Q_NFL = range(6)
NUM_Q = 6
# rob fields
R_SRC, R_PKT, R_TYP, R_TAG, R_OSRC, R_NFL, R_CNT = range(7)
NUM_R = 7
# pending fields
P_VALID, P_TYP, P_SRC, P_OSRC, P_TAG = range(5)
NUM_P = 5

STAT_INDEX = {k: i for i, k in enumerate(STAT_NAMES)}
NUM_STATS = len(STAT_NAMES)


class SimState(NamedTuple):
    # FSM (N,)
    st: jnp.ndarray
    ctr: jnp.ndarray
    tr_ptr: jnp.ndarray
    pend_addr: jnp.ndarray
    install_mode: jnp.ndarray
    pkt_ctr: jnp.ndarray
    lru_clock: jnp.ndarray
    # caches
    l1_tag: jnp.ndarray      # (N, S1, W1)
    l1_lru: jnp.ndarray
    l1_owner: jnp.ndarray
    l2_tag: jnp.ndarray      # (N, S2, W2)
    l2_lru: jnp.ndarray
    l2_mig: jnp.ndarray
    l2_last: jnp.ndarray
    l2_streak: jnp.ndarray
    # directory: (dir_entries + 1,) — last slot is a scatter sink
    dir_loc: jnp.ndarray
    # forwarding table
    fwd_tag: jnp.ndarray     # (N, Fe)
    fwd_dst: jnp.ndarray
    fwd_ptr: jnp.ndarray     # (N,)
    # network input ports
    inp: jnp.ndarray         # (N, 4, NUM_F)
    # send queue (packet ring buffer)
    q_desc: jnp.ndarray      # (N, Qp, NUM_Q)
    q_head: jnp.ndarray      # (N,)
    q_size: jnp.ndarray      # (N,)
    q_fid: jnp.ndarray       # (N,)  flit cursor of head packet
    # reorder buffer
    rob: jnp.ndarray         # (N, K, NUM_R)
    # pending-completion queue (head at slot 0; depth 1 = S14 register)
    pc: jnp.ndarray          # (N, pc_depth, NUM_P)
    # statistics + clock.  stats is the LOW word of a base-2**30 pair
    # (stats_hi carries the overflow folded out once per cycle); exact
    # int64 totals come from stats_totals(stats_hi, stats).
    stats: jnp.ndarray       # (NUM_STATS,) int32 — low word (total mod 2**30)
    stats_hi: jnp.ndarray    # (NUM_STATS,) int32 — high word (total div 2**30)
    cycle: jnp.ndarray       # () int32
    # workload (read-only during sim)
    trace: jnp.ndarray       # (N, M)
    # policy knobs as traced scalars so a batched sweep (repro.core.sweep)
    # can vary them per scenario inside ONE compiled program; initialized
    # from SimConfig so solo runs are unchanged.
    knob_mig: jnp.ndarray      # () int32 — migration enabled?
    knob_mig_thr: jnp.ndarray  # () int32 — migration streak threshold
    knob_central: jnp.ndarray  # () int32 — centralized directory?
    knob_ej_age: jnp.ndarray   # () int32 — guaranteed-ejection age threshold


class Geometry(NamedTuple):
    """Static (numpy) routing geometry, precomputed from the config."""

    valid_port: np.ndarray   # (N, 4) bool — does this port physically exist
    gather_node: np.ndarray  # (N, 4) int32 — node whose output feeds my input p
    gather_port: np.ndarray  # (4,) int32 — which output port of that node
    node_r: np.ndarray       # (N,)
    node_c: np.ndarray       # (N,)


class NodeCtx(NamedTuple):
    """Per-node identity/geometry as *arrays* (shardable: inside shard_map
    these are the local tile's slices; node ids stay global)."""

    node_id: jnp.ndarray     # (Nl,) global node id (r*C + c)
    node_r: jnp.ndarray      # (Nl,) global row
    node_c: jnp.ndarray      # (Nl,) global col
    valid_port: jnp.ndarray  # (Nl, 4) bool


def make_node_ctx(cfg: SimConfig) -> NodeCtx:
    geo = make_geometry(cfg.rows, cfg.cols)
    return NodeCtx(jnp.arange(cfg.num_nodes, dtype=jnp.int32),
                   jnp.asarray(geo.node_r), jnp.asarray(geo.node_c),
                   jnp.asarray(geo.valid_port))


def make_geometry(rows: int, cols: int) -> Geometry:
    n = rows * cols
    idx = np.arange(n)
    r, c = idx // cols, idx % cols
    valid = np.stack([r > 0, c < cols - 1, r < rows - 1, c > 0], axis=1)  # N,E,S,W
    # input port p receives the opposite output of the neighbour in direction p
    gnode = np.stack([idx - cols, idx + 1, idx + cols, idx - 1], axis=1)
    gnode = np.where(valid, gnode, 0).astype(np.int32)
    gport = np.array([2, 3, 0, 1], np.int32)  # S, W, N, E
    return Geometry(valid.astype(bool), gnode, gport,
                    r.astype(np.int32), c.astype(np.int32))


def dir_shape(cfg: SimConfig) -> Tuple[int, ...]:
    """Directory array shape. ``flat``: one global location array (+ sink
    slot).  ``home``: entry for tag t lives at (t % N, t // N) — row-sharded
    with the nodes, so every access is local to the tag's home node."""
    if cfg.dir_layout == "flat":
        return (cfg.dir_entries + 1,)
    assert not cfg.centralized_directory, \
        "home-sharded directory layout requires a distributed directory"
    per = -(-cfg.dir_entries // cfg.num_nodes)
    return (cfg.num_nodes, per + 1)


@span("repro.place_state")
def init_state(cfg: SimConfig, trace: np.ndarray) -> SimState:
    """Build the initial state.

    ``trace`` is ``(num_nodes, M)`` for a solo run, or ``(B, num_nodes, M)``
    for a batched sweep — every leaf then carries the same leading scenario
    axis ``B`` (see :mod:`repro.core.sweep`).
    """
    cfg.validate()
    if not hasattr(trace, "ndim"):   # keep tracers (eval_shape) intact
        trace = np.asarray(trace)
    if trace.ndim not in (2, 3) or trace.shape[-2] != cfg.num_nodes:
        raise ValueError(
            f"trace must be (num_nodes, M) or (B, num_nodes, M) with "
            f"num_nodes={cfg.num_nodes}, got shape {trace.shape}")
    batch = trace.shape[:-2]
    n = cfg.num_nodes
    ca = cfg.cache
    i32 = jnp.int32
    dt = leaf_dtypes(cfg, trace.shape[-1])
    z = lambda k, *s: jnp.zeros(batch + s, dt[k])
    neg = lambda k, *s: jnp.full(batch + s, -1, dt[k])
    knob = lambda v: jnp.full(batch, v, i32)
    return SimState(
        st=z("st", n), ctr=z("ctr", n), tr_ptr=z("tr_ptr", n),
        pend_addr=neg("pend_addr", n), install_mode=z("install_mode", n),
        pkt_ctr=z("pkt_ctr", n), lru_clock=z("lru_clock", n),
        l1_tag=neg("l1_tag", n, ca.l1_sets, ca.l1_ways),
        l1_lru=z("l1_lru", n, ca.l1_sets, ca.l1_ways),
        l1_owner=neg("l1_owner", n, ca.l1_sets, ca.l1_ways),
        l2_tag=neg("l2_tag", n, ca.l2_sets, ca.l2_ways),
        l2_lru=z("l2_lru", n, ca.l2_sets, ca.l2_ways),
        l2_mig=z("l2_mig", n, ca.l2_sets, ca.l2_ways),
        l2_last=neg("l2_last", n, ca.l2_sets, ca.l2_ways),
        l2_streak=z("l2_streak", n, ca.l2_sets, ca.l2_ways),
        dir_loc=jnp.full(batch + dir_shape(cfg), -1, dt["dir_loc"]),
        fwd_tag=neg("fwd_tag", n, cfg.fwd_entries),
        fwd_dst=neg("fwd_dst", n, cfg.fwd_entries),
        fwd_ptr=z("fwd_ptr", n),
        inp=z("inp", n, NUM_PORTS, NUM_F),
        # the last slot is an old scatter sink: never written or read
        # (appends are slot selects), kept so the state size is unchanged
        q_desc=z("q_desc", n, cfg.send_queue + 1, NUM_Q),
        q_head=z("q_head", n), q_size=z("q_size", n), q_fid=z("q_fid", n),
        rob=z("rob", n, cfg.rob_slots, NUM_R),
        pc=z("pc", n, cfg.pc_depth, NUM_P),
        stats=z("stats", NUM_STATS),
        stats_hi=z("stats_hi", NUM_STATS),
        cycle=z("cycle"),
        trace=jnp.asarray(trace, dt["trace"]),
        knob_mig=knob(int(cfg.migration_enabled)),
        knob_mig_thr=knob(cfg.migrate_threshold),
        knob_central=knob(int(cfg.centralized_directory)),
        knob_ej_age=knob(cfg.eject_age_threshold),
    )


# ---------------------------------------------------------------------------
# Narrow-dtype storage layout (SimConfig.state_dtype_policy)
# ---------------------------------------------------------------------------

#: leaves that stay int32 under every policy: the stats hi/lo pair (the
#: accumulator arithmetic needs int32 headroom), the clock, and the traced
#: knob scalars (the sweep layer swaps int32 vectors into them).
_PINNED_I32 = ("stats", "stats_hi", "cycle",
               "knob_mig", "knob_mig_thr", "knob_central", "knob_ej_age")


def _fit(lo: int, hi: int) -> np.dtype:
    """Smallest signed integer dtype holding the closed range [lo, hi]."""
    for dt in (np.int8, np.int16, np.int32):
        info = np.iinfo(dt)
        if lo >= info.min and hi <= info.max:
            return np.dtype(dt)
    raise ValueError(f"state value bounds [{lo}, {hi}] exceed int32")


@functools.lru_cache(maxsize=None)
def leaf_dtypes(cfg: SimConfig, trace_len: int) -> Dict[str, np.dtype]:
    """Per-leaf storage dtype map for ``cfg`` (keyed by SimState field).

    ``wide`` pins every leaf to int32 (the historical layout).  ``packed``
    derives each leaf's value bounds from the validated config — FSM
    states 0..6, tags ``<= (2**addr_bits - 1) >> shift``, node ids
    ``< num_nodes``, LRU clocks ``<= 3 * max_cycles + 4`` (at most three
    touch sites tick the clock per cycle), flit ages ``<= max_cycles``,
    packet ids ``< cfg.pkt_wrap`` — and picks the smallest of
    int8/int16/int32 that holds them (``-1`` sentinels included).  The
    map therefore *adapts*: node-id leaves widen back to int32 past
    32767 nodes, message payloads past ``addr_bits`` 15, LRU clocks past
    ``max_cycles`` ~10900.  Bounds the config cannot express (e.g. a
    migration streak past int16 saturation) are rejected by
    ``SimConfig.validate`` instead.
    """
    i32 = np.dtype(np.int32)
    out = {k: i32 for k in SimState._fields}
    if cfg.state_dtype_policy != "packed":
        return out
    n = cfg.num_nodes
    addr_max = (1 << cfg.addr_bits) - 1
    clk_max = 3 * cfg.max_cycles + 4
    ctr_max = max(cfg.mem_cycles, cfg.l2_hit_cycles, cfg.l1_miss_cycles,
                  cfg.req_timeout) + 1
    flits_max = 16          # longest packet (B2) — FLITS_OF
    # every value a flit/descriptor/ROB/pending slot can carry: a message
    # type, a node id, a tag or address payload, a packet id, an age, a
    # flit count, or a -1 sentinel
    msg_hi = max(addr_max, n - 1, cfg.pkt_wrap - 1, cfg.max_cycles,
                 flits_max, NUM_MSG_TYPES)
    out.update(
        st=_fit(0, 6),
        ctr=_fit(-2, ctr_max),
        tr_ptr=_fit(0, trace_len + 1),
        pend_addr=_fit(-1, addr_max),
        install_mode=_fit(0, 1),
        # pkt_ctr may wrap in a narrow dtype: safe, because consumers only
        # ever read it mod cfg.pkt_wrap (2**14), and 2**16 = 0 mod 2**14
        pkt_ctr=_fit(0, cfg.pkt_wrap - 1),
        lru_clock=_fit(0, clk_max),
        l1_tag=_fit(-1, addr_max >> cfg.cache.l1_shift),
        l1_lru=_fit(0, clk_max),
        l1_owner=_fit(-1, n - 1),
        l2_tag=_fit(-1, addr_max >> cfg.cache.l2_shift),
        l2_lru=_fit(0, clk_max),
        l2_mig=_fit(0, 1),
        l2_last=_fit(-1, n - 1),
        l2_streak=np.dtype(np.int16),   # saturating narrow (see below)
        dir_loc=_fit(-1, n - 1),
        fwd_tag=_fit(-1, addr_max >> cfg.cache.l2_shift),
        fwd_dst=_fit(-1, n - 1),
        fwd_ptr=_fit(0, cfg.fwd_entries),
        inp=_fit(-1, msg_hi),
        q_desc=_fit(-1, msg_hi),
        q_head=_fit(0, cfg.send_queue),
        q_size=_fit(0, cfg.send_queue + 1),
        q_fid=_fit(0, flits_max),
        rob=_fit(-1, msg_hi),
        pc=_fit(-1, msg_hi),
        trace=_fit(-1, addr_max),
    )
    for k in _PINNED_I32:
        out[k] = i32
    return out


def widen_state(s: SimState) -> SimState:
    """Cast every narrow leaf up to the int32 compute domain.

    ``trace`` is exempt: it is read-only during simulation and its single
    consumer (``cache._next_addr``) casts after the gather, so the full
    (N, M) block is never re-materialized per cycle.  Under the wide
    policy every cast is a no-op and XLA elides it.
    """
    i32 = jnp.int32
    return SimState(**{
        k: (v if k == "trace" or v.dtype == i32 else v.astype(i32))
        for k, v in s._asdict().items()})


def narrow_state(s: SimState, dtypes: Dict[str, np.dtype]) -> SimState:
    """Cast leaves back down to their storage dtypes (``leaf_dtypes``).

    All casts are value-preserving by the bounds in :func:`leaf_dtypes`,
    with two deliberate exceptions: ``pkt_ctr`` may wrap (congruent mod
    ``cfg.pkt_wrap``, so packet ids are unchanged) and ``l2_streak``
    saturates at int16 max (comparisons against the validated
    ``migrate_threshold <= 32766`` are unaffected).
    """
    def down(k, v):
        dt = dtypes[k]
        if v.dtype == dt:
            return v
        if k == "l2_streak":
            v = jnp.minimum(v, np.iinfo(np.int16).max)
        return v.astype(dt)
    return SimState(**{k: down(k, v) for k, v in s._asdict().items()})


def _slot_hit(arr: jnp.ndarray, idx: Tuple[jnp.ndarray, ...]) -> jnp.ndarray:
    """One-hot of ``idx`` over the slot axes of ``arr`` (node axis first),
    shaped to broadcast against ``arr``."""
    k = len(idx)
    slots = arr.shape[1:1 + k]
    hit = True
    for a, i in enumerate(idx):
        iota = jax.lax.broadcasted_iota(i.dtype, (1,) + slots, a + 1)
        hit = hit & (iota == i.reshape(i.shape + (1,) * k))
    return hit.reshape(hit.shape + (1,) * (arr.ndim - 1 - k))


def node_get(arr: jnp.ndarray, idx) -> jnp.ndarray:
    """Per-node read ``arr[n, *idx[n]]``, the read that goes with
    :func:`node_set`: the one-hot select of ``idx`` summed over the slot
    axes.  Shapes as in :func:`node_set`; the result is ``(N, *fields)``
    for a full index and ``(N, *rest)`` for a partial one (a whole set
    of ways)."""
    idx = idx if isinstance(idx, tuple) else (idx,)
    picked = jnp.where(_slot_hit(arr, idx), arr, 0)
    return jnp.sum(picked, axis=tuple(range(1, 1 + len(idx))),
                   dtype=arr.dtype)


def node_set(arr: jnp.ndarray, idx, mask: jnp.ndarray, val) -> jnp.ndarray:
    """Per-node masked write: ``arr[n, *idx[n]] = val[n]`` where ``mask[n]``.

    ``arr`` is ``(N, *slots, *fields)``; ``idx`` is one ``(N,)`` index
    array, or a tuple of them, into the slot axes after the node axis;
    ``val`` is a scalar, ``(N,)``, or ``(N, *fields)`` for a write of a
    whole trailing field row.  Indices must be in range.

    The same write as ``arr.at[node, *idx].set(where(mask, val,
    arr[node, *idx]))``, done as a dense one-hot select over the slot
    axes instead of an XLA scatter (docs/architecture.md "State layout
    and memory budget").
    """
    idx = idx if isinstance(idx, tuple) else (idx,)
    k = len(idx)
    hit = _slot_hit(arr, idx) & mask.reshape(
        mask.shape + (1,) * (arr.ndim - 1))
    val = jnp.asarray(val, arr.dtype)
    if val.ndim:
        val = val.reshape(val.shape[:1] + (1,) * k + val.shape[1:])
    return jnp.where(hit, val, arr)


def state_bytes(cfg: SimConfig, trace_len: int = 200,
                policy: Optional[str] = None) -> int:
    """Exact SimState bytes for ONE scenario of ``cfg`` (trace included).

    ``policy`` overrides ``cfg.state_dtype_policy`` (so planners can
    quote both layouts without rebuilding configs).  Pure shape/dtype
    arithmetic — no device allocation.
    """
    if policy is not None:
        import dataclasses
        cfg = dataclasses.replace(cfg, state_dtype_policy=policy)
    cfg.validate()
    n, ca = cfg.num_nodes, cfg.cache
    shapes = dict(
        st=(n,), ctr=(n,), tr_ptr=(n,), pend_addr=(n,), install_mode=(n,),
        pkt_ctr=(n,), lru_clock=(n,),
        l1_tag=(n, ca.l1_sets, ca.l1_ways), l1_lru=(n, ca.l1_sets, ca.l1_ways),
        l1_owner=(n, ca.l1_sets, ca.l1_ways),
        l2_tag=(n, ca.l2_sets, ca.l2_ways), l2_lru=(n, ca.l2_sets, ca.l2_ways),
        l2_mig=(n, ca.l2_sets, ca.l2_ways), l2_last=(n, ca.l2_sets, ca.l2_ways),
        l2_streak=(n, ca.l2_sets, ca.l2_ways),
        dir_loc=dir_shape(cfg),
        fwd_tag=(n, cfg.fwd_entries), fwd_dst=(n, cfg.fwd_entries),
        fwd_ptr=(n,),
        inp=(n, NUM_PORTS, NUM_F),
        q_desc=(n, cfg.send_queue + 1, NUM_Q),
        q_head=(n,), q_size=(n,), q_fid=(n,),
        rob=(n, cfg.rob_slots, NUM_R), pc=(n, cfg.pc_depth, NUM_P),
        stats=(NUM_STATS,), stats_hi=(NUM_STATS,), cycle=(),
        trace=(n, trace_len),
        knob_mig=(), knob_mig_thr=(), knob_central=(), knob_ej_age=(),
    )
    dt = leaf_dtypes(cfg, trace_len)
    return sum(int(np.prod(shp, dtype=np.int64)) * dt[k].itemsize
               for k, shp in shapes.items())


# ---------------------------------------------------------------------------
# 64-bit statistics accumulator (base-2**30 hi/lo int32 pair)
# ---------------------------------------------------------------------------

#: fold base.  Per-cycle increments stay far below 2**31 - 2**30, so the
#: low word never overflows between folds even at 43k nodes.
STATS_FOLD = 1 << 30


def fold_stats(hi: jnp.ndarray, lo: jnp.ndarray
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Carry the low stats word into the high word: returns the canonical
    pair with ``lo = total mod 2**30`` (floor semantics, so a negative
    transient ``lo`` — possible after summing sharded per-tile deltas —
    normalizes correctly)."""
    carry = jnp.floor_divide(lo, STATS_FOLD)
    return hi + carry, lo - carry * STATS_FOLD


def stats_totals(hi, lo) -> np.ndarray:
    """Exact int64 counter totals from a (hi, lo) stats pair (host side)."""
    return (np.asarray(hi, np.int64) * STATS_FOLD
            + np.asarray(lo, np.int64))


def bump(stats: jnp.ndarray, name: str, amount) -> jnp.ndarray:
    """Add ``amount`` (scalar or array to be summed) to a named statistic.

    ``stats`` is the LOW word of the base-2**30 accumulator pair; the
    per-cycle fold in ``sim.cycle_step`` carries overflow into
    ``stats_hi``, so totals are exact int64 end to end (host view:
    :func:`stats_totals`)."""
    amt = jnp.sum(amount.astype(jnp.int32)) if hasattr(amount, "astype") else amount
    return stats.at[STAT_INDEX[name]].add(jnp.asarray(amt, jnp.int32))
