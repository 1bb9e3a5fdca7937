"""Vectorized simulator driver: the paper's main loop (§7.1/§7.2) in JAX.

The serial version's

    while not finished: Phase1(all); Phase2(all); Phase3(all)

and the GPU version's three-kernel loop both become a single jitted
``cycle_step`` (phases fused by XLA) inside ``lax.while_loop``.

There is ONE driver, :func:`_run_jit`, and it is batched: a solo ``run``
is the batch-of-1 special case of the sweep, so solo runs, batched sweeps
(:mod:`repro.core.sweep`) and the execution-plan layer
(:mod:`repro.core.engine`) all share the same loop, termination predicate,
progress monitors and statistics collection.

Progress monitors (carried inside the compiled loop, per scenario):

* **Livelock** — no *progress* statistic (anything but the pure-motion
  counters ``hops``/``deflections``) changes for
  ``cfg.livelock_window_effective`` consecutive cycles while the scenario
  is unfinished.  This catches the S14 backpressure/ejection-bar cycles
  the paper-faithful ``pc_depth=1`` register admits (flits keep
  circulating — hops keep rising — but nothing retires) without burning
  ``max_cycles``; at the default ``pc_depth`` the pending-completion
  queue's ejection guarantee resolves those cycles and the monitor
  watches them run to completion (docs/architecture.md).
* **Directory saturation** — on centralized-directory scenarios at >= 256
  nodes, evaluated every ``cfg.sat_window`` cycles: at least half the
  nodes sit in WAIT_DIR/WAIT_DATA while fewer than ``num_nodes/2``
  references retired over the window (the paper's node-0 hotspot).

A monitor never changes the cycle-by-cycle semantics of a healthy run —
it only stops early, snapshotting the statistics and a diagnostic
(circulating flits, wait-state counts, node-0 pressure) at the abort
cycle, so aborted results are independent of when the loop actually
exits.
"""
from __future__ import annotations

import functools
from typing import Dict, List, NamedTuple, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np

from .config import ST_DONE, ST_WAIT_DATA, ST_WAIT_DIR, SimConfig
from .cache import phase1a, phase1b
from .noc import phase2, phase3
from .ref_serial import STAT_NAMES
from .state import (F_DST, F_VALID, P_VALID, R_NFL, Geometry, NodeCtx,
                    SimState, fold_stats, init_state, leaf_dtypes,
                    make_geometry, make_node_ctx, narrow_state, stats_totals,
                    widen_state)
from .tracing import span

__all__ = ["cycle_step", "finished", "run", "stats_list", "ExecAux",
           "VectorSim", "ABORT_LABELS", "diag_counts", "check_cycle_cap",
           "aggregate_stats", "network_health"]

I32 = jnp.int32

#: statistics that witness forward progress.  hops/deflections are excluded:
#: they keep rising while flits merely circulate, which is exactly the
#: livelock signature the monitor must see *through*.
_PROG_IDX = np.asarray([i for i, k in enumerate(STAT_NAMES)
                        if k not in ("hops", "deflections")])

ABORT_NONE, ABORT_LIVELOCK, ABORT_SATURATION = 0, 1, 2
ABORT_LABELS = {ABORT_LIVELOCK: "livelock", ABORT_SATURATION: "dir_saturation"}
_SAT_MIN_NODES = 256


class ExecAux(NamedTuple):
    """Per-scenario abort record returned by the driver next to the state.

    All leaves are ``(B,)`` (or ``()`` for a solo run) except
    ``abort_stats`` which is ``(B, NUM_STATS)``.  ``abort == 0`` means the
    scenario ran to completion or to ``max_cycles`` untouched; the
    remaining fields are then zero and ignored."""

    abort: jnp.ndarray        # 0 none | 1 livelock | 2 dir saturation
    abort_cycle: jnp.ndarray
    abort_stats: jnp.ndarray     # stats LOW-word snapshot at the abort cycle
    abort_stats_hi: jnp.ndarray  # stats HIGH-word snapshot (base-2**30 pair)
    circ: jnp.ndarray         # circulating (in-flight) flits at abort
    wait_dir: jnp.ndarray     # nodes in WAIT_DIR at abort
    wait_data: jnp.ndarray    # nodes in WAIT_DATA at abort
    stalled: jnp.ndarray      # nodes with a backlogged send queue at abort
    dst0: jnp.ndarray         # in-flight flits destined to node 0 at abort


def diag_counts(st: np.ndarray, inp: np.ndarray,
                q_size: np.ndarray) -> Dict[str, np.int32]:
    """Abort-diagnostic counters from host-side state arrays, keyed like
    the corresponding :class:`ExecAux` fields (``circ``, ``wait_dir``,
    ``wait_data``, ``stalled``, ``dst0``).

    Any per-scenario slice shape works — node-flat or grid-shaped — as
    long as ``inp``'s last axis is the flit-field axis; the sharded and
    composed host drivers use this to snapshot one scenario at its abort
    chunk edge, mirroring the in-graph monitor's snapshot."""
    valid = inp[..., F_VALID] > 0
    return dict(
        circ=np.int32(valid.sum()),
        wait_dir=np.int32((st == ST_WAIT_DIR).sum()),
        wait_data=np.int32((st == ST_WAIT_DATA).sum()),
        stalled=np.int32((q_size > 0).sum()),
        dst0=np.int32((valid & (inp[..., F_DST] == 0)).sum()),
    )


class _Mon(NamedTuple):
    prev_prog: jnp.ndarray    # (B, P) progress stats last cycle
    frz: jnp.ndarray          # (B,) consecutive frozen cycles
    refs_anchor: jnp.ndarray  # (B,) sum(tr_ptr) at the last window edge
    aux: ExecAux


def cycle_step(s: SimState, cfg: SimConfig, geo: Geometry,
               ctx: NodeCtx) -> SimState:
    """One simulated cycle = phases 1a, 1b, 2, 3 (S1).

    The phases always compute in int32: under a packed storage layout
    (``cfg.state_dtype_policy``) the state is widened on entry and
    narrowed back on exit, so the loop carry — the persistent footprint —
    stays narrow while phase semantics are untouched.  The cycle boundary
    also folds the low stats word into ``stats_hi`` (base-2**30 pair), so
    counters cannot wrap at 43k nodes x long runs."""
    dtypes = leaf_dtypes(cfg, s.trace.shape[-1])
    s = widen_state(s)
    with jax.named_scope("phase1a"):
        s = phase1a(s, cfg, ctx)
    with jax.named_scope("phase1b"):
        s = phase1b(s, cfg, ctx)
    with jax.named_scope("phase2"):
        s, arb = phase2(s, cfg, ctx)
    with jax.named_scope("phase3"):
        s = phase3(s, cfg, geo, ctx, arb)
    hi, lo = fold_stats(s.stats_hi, s.stats)
    return narrow_state(
        s._replace(cycle=s.cycle + 1, stats=lo, stats_hi=hi), dtypes)


def finished(s: SimState) -> jnp.ndarray:
    """Termination predicate.  Scalar for a solo state; ``(B,)`` for a
    batched sweep state (reductions run over everything but the leading
    scenario axis)."""
    b = s.cycle.ndim                       # 0 solo, 1 batched
    tail = lambda x: tuple(range(b, x.ndim))
    done = jnp.all(s.st == ST_DONE, axis=tail(s.st))
    net_in = s.inp[..., F_VALID] > 0
    net_empty = ~jnp.any(net_in, axis=tail(net_in))
    q_empty = jnp.all(s.q_size == 0, axis=tail(s.q_size))
    rob_nfl = s.rob[..., R_NFL]
    rob_empty = jnp.all(rob_nfl == 0, axis=tail(rob_nfl))
    pc_v = s.pc[..., P_VALID]
    pc_empty = jnp.all(pc_v == 0, axis=tail(pc_v))
    return done & net_empty & q_empty & rob_empty & pc_empty


def _mon_init(s: SimState) -> _Mon:
    zb = jnp.zeros(s.cycle.shape, I32)
    aux = ExecAux(abort=zb, abort_cycle=zb,
                  abort_stats=jnp.zeros_like(s.stats),
                  abort_stats_hi=jnp.zeros_like(s.stats_hi),
                  circ=zb, wait_dir=zb, wait_data=zb, stalled=zb, dst0=zb)
    # tr_ptr may be stored narrow (packed layout): widen before the sum
    return _Mon(prev_prog=s.stats[..., _PROG_IDX], frz=zb,
                refs_anchor=jnp.sum(s.tr_ptr.astype(I32), axis=-1), aux=aux)


def _mon_update(mon: _Mon, st: SimState, active: jnp.ndarray,
                cfg: SimConfig) -> _Mon:
    """Advance the livelock/saturation monitors one cycle (batched).

    Per-cycle cost is kept to the (B, P) progress-stat compare: the O(N)
    saturation reductions run only at ``sat_window`` edges and the O(N)
    diagnostic snapshot only on the (at most one) cycle a monitor fires —
    both behind ``lax.cond`` (their outputs are scalars per scenario, so
    the carry-copy concern that rules out a per-step cond around the main
    loop body does not apply)."""
    n = cfg.num_nodes
    lw = cfg.livelock_window_effective
    sw = cfg.sat_window if n >= _SAT_MIN_NODES else 0

    prog = st.stats[:, _PROG_IDX]
    frz = jnp.where(jnp.all(prog == mon.prev_prog, axis=-1), mon.frz + 1, 0)
    fire_lv = (active & (frz >= lw)) if lw > 0 \
        else jnp.zeros_like(active)

    if sw > 0:
        at_edge = (st.cycle % sw) == 0       # one clock: all-or-none

        def sat_eval(_):
            refs = jnp.sum(st.tr_ptr.astype(I32), axis=-1)
            wd = jnp.sum((st.st == ST_WAIT_DIR).astype(I32), axis=-1)
            wdd = jnp.sum((st.st == ST_WAIT_DATA).astype(I32), axis=-1)
            fire = (active & at_edge & (st.knob_central > 0)
                    & ((wd + wdd) * 2 >= n)
                    & ((refs - mon.refs_anchor) * 2 < n))
            return fire, jnp.where(at_edge, refs, mon.refs_anchor)

        fire_sat, refs_anchor = jax.lax.cond(
            jnp.any(at_edge), sat_eval,
            lambda _: (jnp.zeros_like(active), mon.refs_anchor), None)
    else:
        fire_sat = jnp.zeros_like(active)
        refs_anchor = mon.refs_anchor
    fire_lv = fire_lv & ~fire_sat      # saturation is the sharper diagnosis
    fire = fire_lv | fire_sat

    def snapshot(aux):
        valid = st.inp[..., F_VALID] > 0
        circ = jnp.sum(valid.astype(I32), axis=(-2, -1))
        dst0 = jnp.sum((valid & (st.inp[..., F_DST] == 0)).astype(I32),
                       axis=(-2, -1))
        stalled = jnp.sum((st.q_size > 0).astype(I32), axis=-1)
        wd = jnp.sum((st.st == ST_WAIT_DIR).astype(I32), axis=-1)
        wdd = jnp.sum((st.st == ST_WAIT_DATA).astype(I32), axis=-1)
        snap = lambda new, old: jnp.where(fire, new, old)
        return ExecAux(
            abort=jnp.where(fire, jnp.where(fire_sat, ABORT_SATURATION,
                                            ABORT_LIVELOCK), aux.abort),
            abort_cycle=snap(st.cycle, aux.abort_cycle),
            abort_stats=jnp.where(fire[:, None], st.stats, aux.abort_stats),
            abort_stats_hi=jnp.where(fire[:, None], st.stats_hi,
                                     aux.abort_stats_hi),
            circ=snap(circ, aux.circ),
            wait_dir=snap(wd, aux.wait_dir),
            wait_data=snap(wdd, aux.wait_data),
            stalled=snap(stalled, aux.stalled),
            dst0=snap(dst0, aux.dst0),
        )

    aux = jax.lax.cond(jnp.any(fire), snapshot, lambda a: a, mon.aux)
    return _Mon(prog, frz, refs_anchor, aux)


@functools.partial(jax.jit, static_argnums=(1, 3), donate_argnums=(0,))
@jax.named_scope("driver")
def _run_jit(s: SimState, cfg: SimConfig, max_cycles: jnp.ndarray, chunk: int):
    """Drive a state to completion in one compiled loop; returns
    ``(state, ExecAux)``.

    The input state is DONATED: XLA aliases every input buffer to the
    matching output (the loop carry updates in place instead of
    double-buffering the full mesh), and the caller's arrays are dead
    after the call — every caller here rebinds the result.  Use
    :class:`VectorSim` (whose per-step jit does not donate) to keep a
    pre-step state alive.

    The driver is batched (leading scenario axis); a solo state is lifted
    to a batch of one and unlifted on return, so every caller shares one
    code path.  ``cycle_step`` is vmapped and every scenario terminates
    independently.  A finished scenario is NOT frozen with a full-state
    select — stepping a finished state is a semantic no-op on every leaf
    except the clock (all phase masks are false and every statistic bump
    is zero), and keeping the pre-step state alive for a freeze select
    would block XLA's in-place reuse of every large buffer in the loop
    carry.  Instead the loop records each scenario's finish cycle and
    rewrites the per-scenario ``cycle`` leaf at the end, so the returned
    state is bit-identical to B solo runs.  Aborted scenarios (livelock /
    saturation monitors) likewise keep stepping; their reported statistics
    come from the ``ExecAux`` snapshot taken at the abort cycle, so results
    are independent of when the loop exits.

    Everything outside the four phases of ``cycle_step`` runs under the
    device scope ``driver`` (:mod:`repro.core.tracing`).
    """
    solo = s.cycle.ndim == 0
    if solo:
        s = jax.tree.map(lambda x: x[None], s)

    geo = make_geometry(cfg.rows, cfg.cols)
    ctx = make_node_ctx(cfg)
    vstep = jax.vmap(lambda st: cycle_step(st, cfg, geo, ctx))

    def step(c):
        st, done, mon = c
        nxt = vstep(st)
        done = jnp.where((done < 0) & finished(nxt), nxt.cycle, done)
        active = (done < 0) & (mon.aux.abort == 0)
        return nxt, done, _mon_update(mon, nxt, active, cfg)

    def alive(done, mon):
        return jnp.any((done < 0) & (mon.aux.abort == 0))

    carry = (s, jnp.full(s.cycle.shape, -1, I32), _mon_init(s))
    if chunk > 1:
        # main loop: whole chunks with NO per-cycle branch (a per-step
        # lax.cond guard costs carry copies); the loop condition keeps
        # whole chunks from overstepping the cycle cap
        def chunk_cond(c):
            st, done, mon = c
            return alive(done, mon) & (st.cycle[0] + chunk <= max_cycles)

        def chunk_body(c):
            c, _ = jax.lax.scan(lambda cc, _: (step(cc), ()), c,
                                None, length=chunk)
            return c

        carry = jax.lax.while_loop(chunk_cond, chunk_body, carry)

    # tail: per-cycle, so an unfinished scenario stops at exactly
    # max_cycles just like the unchunked loop
    def tail_cond(c):
        st, done, mon = c
        return alive(done, mon) & (st.cycle[0] < max_cycles)

    fs, done, mon = jax.lax.while_loop(tail_cond, step, carry)
    aux = mon.aux
    # finished scenarios kept no-op stepping; restore their true clock.
    # aborted scenarios report the abort cycle.
    cyc = jnp.where(done >= 0, done,
                    jnp.where(aux.abort > 0, aux.abort_cycle, fs.cycle))
    fs = fs._replace(cycle=cyc)
    if solo:
        unlift = lambda x: x[0]
        fs = jax.tree.map(unlift, fs)
        aux = jax.tree.map(unlift, aux)
    return fs, aux


@span("repro.readback")
def stats_list(s: SimState, aux: ExecAux) -> List[Dict[str, int]]:
    """Per-scenario statistics dicts from a driven state + its ExecAux.

    Healthy scenarios get exactly the classic key set (STAT_NAMES +
    ``cycles`` + ``finished``) — bit-identical to what a solo run always
    produced.  Aborted scenarios report the snapshot taken at the abort
    cycle plus ``aborted`` (label) and the diagnostic counters."""
    stats = np.atleast_2d(stats_totals(s.stats_hi, s.stats))
    cyc = np.atleast_1d(np.asarray(s.cycle))
    fin = np.atleast_1d(np.asarray(finished(s)))
    a = {k: np.atleast_1d(np.asarray(v)) for k, v in aux._asdict().items()}
    a["abort_stats"] = np.atleast_2d(
        stats_totals(aux.abort_stats_hi, aux.abort_stats))
    out = []
    for b in range(cyc.shape[0]):
        code = int(a["abort"][b])
        if code:
            d = {k: int(v) for k, v in zip(STAT_NAMES, a["abort_stats"][b])}
            d["cycles"] = int(a["abort_cycle"][b])
            d["finished"] = 0
            d["aborted"] = ABORT_LABELS[code]
            d["circulating_flits"] = int(a["circ"][b])
            d["wait_dir_nodes"] = int(a["wait_dir"][b])
            d["wait_data_nodes"] = int(a["wait_data"][b])
            d["stalled_queues"] = int(a["stalled"][b])
            d["flits_to_node0"] = int(a["dst0"][b])
        else:
            d = {k: int(v) for k, v in zip(STAT_NAMES, stats[b])}
            d["cycles"] = int(cyc[b])
            d["finished"] = int(bool(fin[b]))
        out.append(d)
    return out


def aggregate_stats(stats: List[Dict[str, int]]) -> Dict[str, int]:
    """Sum the ``STAT_NAMES`` counters over per-scenario ``stats`` dicts
    (as produced by :func:`stats_list` / :func:`run`); ``cycles`` becomes
    the max and ``finished`` the min, so the aggregate reads like one
    worst-case scenario.  Non-counter diagnostic keys are dropped."""
    out = {k: sum(int(d.get(k, 0)) for d in stats) for k in STAT_NAMES}
    out["cycles"] = max((int(d.get("cycles", 0)) for d in stats), default=0)
    out["finished"] = min((int(d.get("finished", 0)) for d in stats),
                          default=0)
    return out


def network_health(stats: Dict[str, int]) -> Dict[str, float]:
    """Derived network-health ratios from one statistics dict ``stats``
    (a solo result or an :func:`aggregate_stats` roll-up) — the
    deflection-routing metrics the literature tracks alongside raw
    throughput (deflection rate, ejection-latency proxy, recovered
    drops):

    * ``deflection_rate`` — deflections per hop: the fraction of routing
      decisions that missed their productive port.
    * ``hops_per_flit`` — average hops each *delivered* flit took.  In a
      bufferless mesh every deflection is a detour, so this proxies
      in-network (ejection) latency without per-flit timestamps.
    * ``deflections_per_flit`` — detours per delivered flit (the same
      latency proxy normalized to the minimal-route floor).
    * ``drops_recovered`` — whole-packet response drops recovered by the
      retransmit path (``send_drop``); ``stray_responses`` — stale
      duplicates absorbed after a transaction restart.
    """
    hops = int(stats.get("hops", 0))
    defl = int(stats.get("deflections", 0))
    flits = int(stats.get("flits_delivered", 0))
    return {
        "deflection_rate": defl / hops if hops else 0.0,
        "hops_per_flit": hops / flits if flits else 0.0,
        "deflections_per_flit": defl / flits if flits else 0.0,
        "drops_recovered": int(stats.get("send_drop", 0)),
        "stray_responses": int(stats.get("stray", 0)),
    }


def check_cycle_cap(cfg: SimConfig, max_cycles: Optional[int]) -> None:
    """Reject a per-call cycle cap above ``cfg.max_cycles`` under the
    packed layout: the narrow dtype map (LRU clocks, flit ages) is sized
    from the config's own cap, so overrunning it could silently wrap
    narrow counters.  The wide layout has int32 headroom everywhere and
    accepts any cap."""
    if (cfg.state_dtype_policy == "packed" and max_cycles is not None
            and max_cycles > cfg.max_cycles):
        raise ValueError(
            f"max_cycles={max_cycles} exceeds cfg.max_cycles="
            f"{cfg.max_cycles}: the packed state layout sizes its narrow "
            "dtypes from the config cap — raise cfg.max_cycles instead")


def run(cfg: SimConfig, trace: np.ndarray, max_cycles: Optional[int] = None,
        chunk: int = 1) -> Union[Dict[str, int], List[Dict[str, int]]]:
    """Run the simulator to completion; returns statistics.

    Args:
        cfg: the simulation config (mesh shape, caches, policies).
        trace: ``(num_nodes, M)`` for a solo run (returns one dict) or
            ``(B, num_nodes, M)`` for a batched run (returns a list of
            dicts; the policy knobs are then shared — use
            :mod:`repro.core.sweep` or :mod:`repro.core.engine` to vary
            them per scenario).
        max_cycles: hard cycle cap (default ``cfg.max_cycles``).
        chunk: simulated cycles per device-loop termination check."""
    check_cycle_cap(cfg, max_cycles)
    s = init_state(cfg, trace)
    solo = s.cycle.ndim == 0
    s, aux = _run_jit(s, cfg, jnp.asarray(max_cycles or cfg.max_cycles,
                                          jnp.int32), chunk)
    out = stats_list(s, aux)
    return out[0] if solo else out


class VectorSim:
    """Step-at-a-time wrapper (used by the equivalence tests to compare
    against :class:`repro.core.ref_serial.SerialSim` cycle by cycle)."""

    def __init__(self, cfg: SimConfig, trace: np.ndarray):
        self.cfg = cfg
        self.geo = make_geometry(cfg.rows, cfg.cols)
        self.ctx = make_node_ctx(cfg)
        self.state = init_state(cfg, trace)
        self._step = jax.jit(
            lambda s: cycle_step(s, cfg, self.geo, self.ctx))

    def step(self) -> None:
        self.state = self._step(self.state)

    def stats(self) -> Dict[str, int]:
        st = stats_totals(self.state.stats_hi, self.state.stats)
        out = {k: int(v) for k, v in zip(STAT_NAMES, st)}
        out["cycles"] = int(self.state.cycle)
        out["finished"] = int(bool(finished(self.state)))
        return out

    def run(self, max_cycles: Optional[int] = None) -> Dict[str, int]:
        limit = max_cycles or self.cfg.max_cycles
        self.state, _ = _run_jit(self.state, self.cfg,
                                 jnp.asarray(limit, jnp.int32), 1)
        return self.stats()
