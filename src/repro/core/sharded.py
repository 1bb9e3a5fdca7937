"""Multi-device NoC simulation: spatial and composed ``shard_map`` backends.

Two decompositions live here, sharing one step builder:

* **Spatial (2-D)** — the simulated router grid ``(R, C)`` is
  block-partitioned over the device mesh: rows over ``row_axes`` (e.g.
  ``("pod", "data")``), columns over ``col_axes`` (e.g. ``("model",)``).
  Every phase is node-local except the phase-3 flit transfer, whose
  cross-tile edges become four ``ppermute`` halo slabs per cycle — the
  simulated 2-D mesh maps onto the physical 2-D ICI torus, so halo traffic
  is near-neighbour on the real interconnect.

* **Composed (3-D)** — a *batch* of B scenarios of the same mesh shape is
  laid out over a ``(scenario, rows, cols)`` device mesh: the scenario
  axis is sharded over ``batch_axes`` and, within each spatial tile, the
  local scenarios are vmapped through the very same per-tile cycle step.
  Halo exchange is unchanged per tile — the batched halo slabs ride the
  same four ``ppermute`` collectives (one per direction, all local
  scenarios batched into each), so the fixed collective cost is paid once
  per cycle, not once per scenario.  Termination is per scenario: a
  finished scenario freezes bit-identically to its solo run while its
  batch-mates keep stepping.  :func:`run_composed` is the driver.

The directory must be distributed (``dir_layout="home"``): entry(tag)
lives at node ``tag % N`` which is the only node that ever touches it, so
the location array shards exactly like the nodes and directory traffic
rides the simulated network itself (no extra collectives).
"""
from __future__ import annotations

import dataclasses
import functools
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .cache import phase1a, phase1b
from .config import SimConfig
from .noc import deliver, phase2
from .sim import (ABORT_LIVELOCK, ExecAux, _PROG_IDX, check_cycle_cap,
                  diag_counts, finished as _finished, stats_list)
from .state import (NUM_F, NodeCtx, SimState, fold_stats, init_state,
                    leaf_dtypes, make_geometry, narrow_state, widen_state)

__all__ = ["ShardedSim", "run_composed", "make_sharded_step", "to_grid",
           "state_specs", "make_geo_arrays"]

I32 = jnp.int32

#: leaves whose leading dims are (scenario?, node) — the node dim is
#: reshaped (N, …) -> (R, C, …) for sharding; everything else (stats,
#: cycle, knob_*) is per-scenario scalar state, replicated across the
#: spatial tiles and sharded only over the scenario axis (if any).
_NODE_LEAVES = {
    "st", "ctr", "tr_ptr", "pend_addr", "install_mode", "pkt_ctr",
    "lru_clock", "l1_tag", "l1_lru", "l1_owner", "l2_tag", "l2_lru",
    "l2_mig", "l2_last", "l2_streak", "dir_loc", "fwd_tag", "fwd_dst",
    "fwd_ptr", "inp", "q_desc", "q_head", "q_size", "q_fid", "rob", "pc",
    "trace",
}


def to_grid(s: SimState, cfg: SimConfig) -> SimState:
    """Reshape node-major leaves ``(N, …) -> (R, C, …)``.

    A batched state (leading scenario axis, detected from
    ``s.cycle.ndim``) keeps its batch dim: ``(B, N, …) -> (B, R, C, …)``.
    """
    lead = s.cycle.ndim                       # 0 solo, 1 batched
    def rs(name, x):
        if name in _NODE_LEAVES:
            return x.reshape(x.shape[:lead] + (cfg.rows, cfg.cols)
                             + x.shape[lead + 1:])
        return x
    return SimState(**{k: rs(k, v) for k, v in s._asdict().items()})


def state_specs(cfg: SimConfig, row_axes, col_axes,
                batch_axes: Tuple[str, ...] = ()) -> SimState:
    """Per-leaf :class:`PartitionSpec` pytree for a (possibly batched)
    grid-shaped state.

    Node leaves shard ``(B?, R, C, …)`` over ``(batch_axes?, row_axes,
    col_axes)``; per-scenario leaves (stats, cycle, knobs) shard only
    their leading scenario axis (or are replicated in the solo case)."""
    d = {}
    for k in SimState._fields:
        if k in _NODE_LEAVES:
            d[k] = (P(batch_axes, row_axes, col_axes) if batch_axes
                    else P(row_axes, col_axes))
        else:
            d[k] = P(batch_axes) if batch_axes else P()
    return SimState(**d)


def _halo_transfer(out4: jnp.ndarray, vp4: jnp.ndarray,
                   row_axes, col_axes) -> jnp.ndarray:
    """Phase-3 transfer for one ``(…, Rt, Ct, 4, F)`` tile with ppermute
    halos.  Leading batch dims (the composed backend's local scenario
    axis) ride along unchanged — each directional halo slab is ONE
    ``ppermute`` regardless of batch size."""
    nrow = jax.lax.axis_size(row_axes)
    ncol = jax.lax.axis_size(col_axes)
    perm_dn = [(i, (i + 1) % nrow) for i in range(nrow)]
    perm_up = [(i, (i - 1) % nrow) for i in range(nrow)]
    perm_rt = [(i, (i + 1) % ncol) for i in range(ncol)]
    perm_lt = [(i, (i - 1) % ncol) for i in range(ncol)]

    # input N (p=0) <- neighbour-above's output S (p=2)
    from_above = jax.lax.ppermute(out4[..., -1:, :, 2, :], row_axes, perm_dn)
    in_n = jnp.concatenate([from_above, out4[..., :-1, :, 2, :]], axis=-3)
    # input S (p=2) <- neighbour-below's output N (p=0)
    from_below = jax.lax.ppermute(out4[..., :1, :, 0, :], row_axes, perm_up)
    in_s = jnp.concatenate([out4[..., 1:, :, 0, :], from_below], axis=-3)
    # input W (p=3) <- left neighbour's output E (p=1)
    from_left = jax.lax.ppermute(out4[..., :, -1:, 1, :], col_axes, perm_rt)
    in_w = jnp.concatenate([from_left, out4[..., :, :-1, 1, :]], axis=-2)
    # input E (p=1) <- right neighbour's output W (p=3)
    from_right = jax.lax.ppermute(out4[..., :, :1, 3, :], col_axes, perm_lt)
    in_e = jnp.concatenate([out4[..., :, 1:, 3, :], from_right], axis=-2)

    inp = jnp.stack([in_n, in_e, in_s, in_w], axis=-2)   # (…, Rt, Ct, 4, F)
    # global mesh edges have no links: the valid-port mask kills wraparound
    return jnp.where(vp4[..., None], inp, 0)


def _flatten_nodes(x: jnp.ndarray) -> jnp.ndarray:
    return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])


#: step builders keyed on (cfg, mesh, axes): two drivers over the same
#: decomposition share compiled programs (repeated buckets, benchmarks).
#: Bounded LRU — each entry pins jitted executables and device handles,
#: so a long-lived process sweeping many mesh shapes must not grow it
#: monotonically.
_BUILD_CACHE: OrderedDict = OrderedDict()
_BUILD_CACHE_MAX = 16


def make_sharded_step(cfg: SimConfig, mesh,
                      row_axes: Tuple[str, ...] = ("data",),
                      col_axes: Tuple[str, ...] = ("model",),
                      batch_axes: Tuple[str, ...] = ()):
    """Returns ``build(n_cycles)`` -> jitted sharded step advancing the sim
    by ``n_cycles`` cycles.

    With empty ``batch_axes`` this is the classic 2-D spatial step (a
    no-op once globally finished).  With ``batch_axes`` the state carries
    a leading scenario axis sharded over those mesh axes; within each
    tile the local scenarios are vmapped through the same per-tile cycle,
    and termination/freezing is *per scenario* (psum of the tile-local
    finished flags over the spatial axes only).

    Builders (and therefore compiled programs) are cached on
    ``(cfg, mesh, row_axes, col_axes, batch_axes)``, so drivers over the
    same decomposition never re-trace."""
    ckey = (cfg, mesh, tuple(row_axes), tuple(col_axes), tuple(batch_axes))
    if ckey in _BUILD_CACHE:
        _BUILD_CACHE.move_to_end(ckey)
        return _BUILD_CACHE[ckey]
    assert not cfg.centralized_directory and cfg.dir_layout == "home", \
        "sharded simulation requires the distributed, home-sharded directory"
    sspec = state_specs(cfg, row_axes, col_axes, batch_axes)
    gspec = (P(row_axes, col_axes), P(row_axes, col_axes),
             P(row_axes, col_axes), P(row_axes, col_axes))
    spatial_axes = tuple(row_axes) + tuple(col_axes)
    batched = bool(batch_axes)

    # sim.finished reduces over every axis when `cycle` is scalar, so it
    # serves unchanged as the tile-local termination predicate (vmapped
    # over the local scenario axis in the composed case)
    tile_finished = jax.vmap(_finished) if batched else _finished

    def one_cycle(flat: SimState, ctx: NodeCtx, rt: int, ct: int) -> SimState:
        # widen/narrow at the same per-cycle boundary as sim.cycle_step:
        # phases (and the halo slabs) compute in int32, the scan carry
        # stays in the storage layout.  Stats are folded per chunk in
        # step_tile (after the cross-tile psum), not here — the tile-
        # local low word has int32 headroom for any chunk length.
        dtypes = leaf_dtypes(cfg, flat.trace.shape[-1])
        flat = widen_state(flat)

        def p12(fs):
            s = phase1a(fs, cfg, ctx)
            s = phase1b(s, cfg, ctx)
            return phase2(s, cfg, ctx)

        vp4 = ctx.valid_port.reshape(rt, ct, 4)
        if batched:
            s, arb = jax.vmap(p12)(flat)
            bl = s.st.shape[0]
            out4 = arb.out.reshape(bl, rt, ct, 4, NUM_F)
            inp_next = _halo_transfer(out4, vp4, row_axes, col_axes)
            s = jax.vmap(lambda ss, ab, ip: deliver(ss, cfg, ctx, ab, ip))(
                s, arb, inp_next.reshape(bl, rt * ct, 4, NUM_F))
        else:
            s, arb = p12(flat)
            out4 = arb.out.reshape(rt, ct, 4, NUM_F)
            inp_next = _halo_transfer(out4, vp4, row_axes, col_axes)
            s = deliver(s, cfg, ctx, arb, inp_next.reshape(rt * ct, 4, NUM_F))
        return narrow_state(s._replace(cycle=s.cycle + 1), dtypes)

    def step_tile(n_cycles: int, sg: SimState, nid2, nr2, nc2, vp2):
        lead = 1 if batched else 0
        rt, ct = sg.st.shape[lead], sg.st.shape[lead + 1]
        ctx = NodeCtx(_flatten_nodes(nid2), _flatten_nodes(nr2),
                      _flatten_nodes(nc2), _flatten_nodes(vp2))

        def flat_of(s):  # (B?, Rt, Ct, …) -> (B?, Nl, …) for node leaves
            return SimState(**{
                k: (v.reshape(v.shape[:lead] + (rt * ct,) + v.shape[lead + 2:])
                    if k in _NODE_LEAVES else v)
                for k, v in s._asdict().items()})

        def grid_of(s):
            return SimState(**{
                k: (v.reshape(v.shape[:lead] + (rt, ct) + v.shape[lead + 1:])
                    if k in _NODE_LEAVES else v)
                for k, v in s._asdict().items()})

        flat = flat_of(sg)
        # stats start replicated (across spatial tiles) but accumulate
        # device-local sums inside the scan; the psum below re-replicates
        # the delta (the shard_map replication check is disabled for
        # exactly this carry).  Both words of the base-2**30 pair ride:
        # component deltas reconstruct the exact value sum, and one fold
        # after the psum restores the canonical (hi, lo) form — matching
        # the dense driver's per-cycle fold bit for bit at chunk edges.
        in_stats, in_hi = flat.stats, flat.stats_hi

        nspat = jax.lax.axis_size(spatial_axes)

        def body(carry, _):
            fin_local = tile_finished(carry)        # () solo | (Bl,) batched
            fin = jax.lax.psum(fin_local.astype(I32), spatial_axes) == nspat
            nxt = one_cycle(carry, ctx, rt, ct)
            if batched:
                frz = lambda a, b: jnp.where(
                    fin.reshape(fin.shape + (1,) * (a.ndim - 1)), a, b)
            else:
                frz = lambda a, b: jnp.where(fin, a, b)
            return jax.tree.map(frz, carry, nxt), ()

        flat, _ = jax.lax.scan(body, flat, None, length=n_cycles)
        # stats: replicate across spatial tiles via psum of the local
        # delta (never across the scenario axis — those are independent)
        hi, lo = fold_stats(
            in_hi + jax.lax.psum(flat.stats_hi - in_hi, spatial_axes),
            in_stats + jax.lax.psum(flat.stats - in_stats, spatial_axes))
        flat = flat._replace(stats=lo, stats_hi=hi)
        return grid_of(flat)

    cache = {}

    def build(n_cycles: int):
        if n_cycles not in cache:
            # stats leave the tile replicated but become device-varying
            # inside the scan (re-replicated via psum), so the
            # replication check must be off
            smapped = jax.shard_map(
                functools.partial(step_tile, n_cycles),
                mesh=mesh,
                in_specs=(sspec,) + gspec,
                out_specs=sspec,
                check_vma=False,
            )
            # donate the state (arg 0): in/out shardings and dtypes match
            # leaf for leaf, so XLA updates the mesh in place instead of
            # double-buffering it; the geometry args are reused each
            # chunk and are not donated
            cache[n_cycles] = jax.jit(smapped, donate_argnums=(0,))
        return cache[n_cycles]

    _BUILD_CACHE[ckey] = build
    while len(_BUILD_CACHE) > _BUILD_CACHE_MAX:
        _BUILD_CACHE.popitem(last=False)
    return build


def make_geo_arrays(cfg: SimConfig, mesh, row_axes=("data",),
                    col_axes=("model",)):
    """Global geometry arrays, laid out (R, C, …) and device_put sharded.

    Geometry has no scenario axis: on a 3-D composed mesh the arrays are
    replicated over the batch axes (every scenario shares one grid)."""
    geo = make_geometry(cfg.rows, cfg.cols)
    n, c = cfg.num_nodes, cfg.cols
    nid = np.arange(n, dtype=np.int32).reshape(cfg.rows, cfg.cols)
    nr = np.asarray(geo.node_r).reshape(cfg.rows, cfg.cols)
    nc = np.asarray(geo.node_c).reshape(cfg.rows, cfg.cols)
    vp = np.asarray(geo.valid_port).reshape(cfg.rows, cfg.cols, 4)
    sh = NamedSharding(mesh, P(row_axes, col_axes))
    return (jax.device_put(nid, sh), jax.device_put(nr, sh),
            jax.device_put(nc, sh), jax.device_put(vp, sh))


class ShardedSim:
    """Driver: host-chunked sharded simulation with global termination.

    Args:
        cfg: structural simulator config; must use the distributed
            home-sharded directory (``centralized_directory=False``,
            ``dir_layout="home"``), and ``rows``/``cols`` must be
            divisible by the spatial tile grid implied by the mesh.
        trace: ``(num_nodes, M)`` for a solo spatial run, or
            ``(B, num_nodes, M)`` for a composed batched run (then
            ``batch_axes`` must name the mesh axes the scenario dim is
            sharded over, and B must divide by their total size).
        mesh: a :class:`jax.sharding.Mesh` whose axes cover
            ``batch_axes + row_axes + col_axes``.
        row_axes / col_axes: mesh axes the simulated rows/columns are
            block-partitioned over.
        batch_axes: mesh axes for the scenario dim (composed backend);
            empty for the classic 2-D spatial decomposition.
        knobs: optional ``(migration, threshold, centralized, eject_age)``
            int32 vectors of length B — per-scenario traced policy knobs,
            as produced by :meth:`repro.core.sweep.SweepSpec.knob_arrays`.

    :meth:`run` returns one stats dict (solo) or a list of B dicts
    (batched), each bit-identical to the corresponding solo
    :func:`repro.core.sim.run`."""

    def __init__(self, cfg: SimConfig, trace: np.ndarray, mesh,
                 row_axes: Tuple[str, ...] = ("data",),
                 col_axes: Tuple[str, ...] = ("model",),
                 batch_axes: Tuple[str, ...] = (),
                 knobs: Optional[Tuple[np.ndarray, np.ndarray,
                                       np.ndarray, np.ndarray]] = None):
        nrow = int(np.prod([mesh.shape[a] for a in row_axes]))
        ncol = int(np.prod([mesh.shape[a] for a in col_axes]))
        assert cfg.rows % nrow == 0 and cfg.cols % ncol == 0, \
            f"mesh {cfg.rows}x{cfg.cols} not divisible by tiles {nrow}x{ncol}"
        trace = np.asarray(trace)
        if batch_axes:
            nb = int(np.prod([mesh.shape[a] for a in batch_axes]))
            assert trace.ndim == 3, "batch_axes requires a (B, N, M) trace"
            assert trace.shape[0] % nb == 0, \
                f"batch {trace.shape[0]} not divisible by {nb} scenario " \
                f"shard(s); pad like run_composed does"
        else:
            assert trace.ndim == 2, "a (B, N, M) trace requires batch_axes"
        self.cfg = cfg
        self.mesh = mesh
        self.batch = trace.shape[0] if batch_axes else None
        s = init_state(cfg, trace)
        if knobs is not None:
            mig, thr, cen, eja = knobs
            s = s._replace(knob_mig=jnp.asarray(mig, I32),
                           knob_mig_thr=jnp.asarray(thr, I32),
                           knob_central=jnp.asarray(cen, I32),
                           knob_ej_age=jnp.asarray(eja, I32))
        s = to_grid(s, cfg)
        specs = state_specs(cfg, row_axes, col_axes, batch_axes)
        self.state = jax.device_put(
            s, jax.tree.map(lambda p: NamedSharding(mesh, p), specs,
                            is_leaf=lambda x: isinstance(x, P)))
        self.geo = make_geo_arrays(cfg, mesh, row_axes, col_axes)
        self.build_step = make_sharded_step(cfg, mesh, row_axes, col_axes,
                                            batch_axes)
        self._finished = jax.jit(self._finished_fn)

    @staticmethod
    def _finished_fn(s: SimState) -> jnp.ndarray:
        return _finished(s)

    def run(self, max_cycles: Optional[int] = None, chunk: int = 256
            ) -> Union[Dict[str, int], List[Dict[str, int]]]:
        """Host-chunked driver.  Shares the driver-level termination and
        statistics machinery with :mod:`repro.core.sim` — including the
        livelock monitor, evaluated between chunks at host level (chunk
        granularity: progress must be absent across whole chunks, a
        strictly conservative version of the per-cycle in-graph monitor).

        Args:
            max_cycles: cycle cap (default ``cfg.max_cycles``); the tail
                chunk is clamped so an unfinished run stops at exactly
                this cycle, matching the dense backend bit-for-bit.
            chunk: simulated cycles per device dispatch (and per host
                termination/livelock check).

        Returns: one stats dict for a solo spatial sim, or a list of B
        dicts in scenario order for a composed batched sim."""
        check_cycle_cap(self.cfg, max_cycles)
        if self.batch is not None:
            return self._run_batched(max_cycles, chunk)
        return self._run_solo(max_cycles, chunk)

    def _run_solo(self, max_cycles, chunk):
        limit = max_cycles or self.cfg.max_cycles
        lw = self.cfg.livelock_window_effective
        prev_prog, frozen, abort = None, 0, 0
        while True:
            cyc = int(self.state.cycle)
            if cyc >= limit:
                break
            # clamp the last chunk so an unfinished run stops at exactly
            # max_cycles, matching the dense backend bit-for-bit (the
            # shorter tail program compiles once and is cached)
            n_step = min(chunk, limit - cyc)
            self.state = self.build_step(n_step)(self.state, *self.geo)
            if bool(self._finished(self.state)):
                break
            prog = tuple(np.asarray(self.state.stats)[_PROG_IDX].tolist())
            if prog == prev_prog:
                frozen += n_step
            else:
                prev_prog, frozen = prog, 0
            if lw and frozen >= lw:
                abort = ABORT_LIVELOCK
                break
        s = self.state
        z = np.int32(0)
        if abort:
            d = diag_counts(np.asarray(s.st), np.asarray(s.inp),
                             np.asarray(s.q_size))
            aux = ExecAux(
                abort=np.int32(abort),
                abort_cycle=np.asarray(s.cycle, np.int32),
                abort_stats=np.asarray(s.stats),
                abort_stats_hi=np.asarray(s.stats_hi), **d)
        else:
            zs = np.zeros_like(np.asarray(s.stats))
            aux = ExecAux(z, z, zs, zs, z, z, z, z, z)
        return stats_list(s, aux)[0]

    def _run_batched(self, max_cycles, chunk):
        """Composed-backend host loop: per-scenario termination and
        livelock accounting.  All *active* (unfinished, unaborted)
        scenarios share one clock — they step together each chunk; a
        finished scenario is frozen in-graph at its exact finish cycle,
        and an aborted one keeps stepping (like the dense driver) with
        its reported statistics snapshotted at the abort chunk edge."""
        limit = max_cycles or self.cfg.max_cycles
        lw = self.cfg.livelock_window_effective
        nb = self.batch
        nstats = int(self.state.stats.shape[-1])
        prev_prog: List = [None] * nb
        frozen = np.zeros(nb, np.int64)
        abort = np.zeros(nb, np.int32)
        ab_cycle = np.zeros(nb, np.int32)
        ab_stats = np.zeros((nb, nstats), np.int32)
        ab_hi = np.zeros((nb, nstats), np.int32)
        diag = {k: np.zeros(nb, np.int32)
                for k in ("circ", "wait_dir", "wait_data", "stalled", "dst0")}
        fin = np.asarray(self._finished(self.state))
        while True:
            active = ~fin & (abort == 0)
            if not active.any():
                break
            cyc = int(np.asarray(self.state.cycle)[active].max())
            if cyc >= limit:
                break
            n_step = min(chunk, limit - cyc)
            self.state = self.build_step(n_step)(self.state, *self.geo)
            # one predicate evaluation per chunk: this post-step vector
            # is both the monitor's not-finished guard and the next
            # iteration's activity mask
            fin = np.asarray(self._finished(self.state))
            if not lw:
                continue
            stats = np.asarray(self.state.stats)
            stats_hi = np.asarray(self.state.stats_hi)
            cyc_now = np.asarray(self.state.cycle)
            st = inp = qs = None
            for b in np.nonzero(active)[0]:
                prog = stats[b, _PROG_IDX].tobytes()
                if prog == prev_prog[b]:
                    frozen[b] += n_step
                else:
                    prev_prog[b], frozen[b] = prog, 0
                if frozen[b] >= lw and not fin[b]:
                    abort[b] = ABORT_LIVELOCK
                    ab_cycle[b] = int(cyc_now[b])
                    ab_stats[b] = stats[b]
                    ab_hi[b] = stats_hi[b]
                    if st is None:   # pull the big arrays at most once
                        st = np.asarray(self.state.st)
                        inp = np.asarray(self.state.inp)
                        qs = np.asarray(self.state.q_size)
                    for k, v in diag_counts(st[b], inp[b], qs[b]).items():
                        diag[k][b] = v
        aux = ExecAux(abort=abort, abort_cycle=ab_cycle, abort_stats=ab_stats,
                      abort_stats_hi=ab_hi,
                      circ=diag["circ"], wait_dir=diag["wait_dir"],
                      wait_data=diag["wait_data"], stalled=diag["stalled"],
                      dst0=diag["dst0"])
        return stats_list(self.state, aux)


def run_composed(spec, grid: Tuple[int, int, int],
                 max_cycles: Optional[int] = None, chunk: int = 256,
                 devices: Optional[Sequence] = None
                 ) -> List[Dict[str, int]]:
    """Composed backend: B scenarios × spatial tiles on one 3-D device mesh.

    Args:
        spec: a :class:`repro.core.sweep.SweepSpec` — the scenarios'
            workloads and traced policy knobs over one structural config
            (``dir_layout`` is forced to ``"home"`` here; a centralized-
            directory scenario is therefore rejected by validation).
        grid: ``(batch_shards, row_tiles, col_tiles)`` device grid; its
            product is the number of devices used.  ``(1, rt, ct)``
            degenerates to the spatial backend; ``(1, 1, 1)`` to a solo
            run — both bit-identically.
        max_cycles: cycle cap (default ``cfg.max_cycles``).
        chunk: simulated cycles per device dispatch.
        devices: device list to build the mesh from (default
            ``jax.devices()``); must hold at least ``prod(grid)``.

    The scenario batch is padded up to a multiple of ``batch_shards``
    with copies of the last scenario exactly like
    :func:`repro.core.sweep.run_sweep` (copies finish the same cycle as
    their original, so padding costs no wall-clock and is dropped from
    the results).

    Returns: one stats dict per scenario, in scenario order,
    bit-identical to solo :func:`repro.core.sim.run` calls."""
    from .sweep import SweepSpec   # deferred: avoid an import cycle
    bs, rt, ct = grid
    cfg = dataclasses.replace(spec.cfg, dir_layout="home")
    spec = SweepSpec(cfg, spec.scenarios)
    spec.validate()
    traces = spec.traces()
    mig, thr, cen, eja = spec.knob_arrays()
    pad = (-spec.size) % bs
    if pad:
        traces = np.concatenate([traces, np.repeat(traces[-1:], pad, 0)])
        mig, thr, cen, eja = (np.concatenate([a, np.repeat(a[-1:], pad, 0)])
                              for a in (mig, thr, cen, eja))
    devs = list(devices if devices is not None else jax.devices())
    need = bs * rt * ct
    if len(devs) < need:
        raise ValueError(f"composed grid {grid} needs {need} device(s), "
                         f"have {len(devs)}")
    mesh = Mesh(np.asarray(devs[:need]).reshape(bs, rt, ct),
                ("scenario", "data", "model"))
    sim = ShardedSim(cfg, traces, mesh, row_axes=("data",),
                     col_axes=("model",), batch_axes=("scenario",),
                     knobs=(mig, thr, cen, eja))
    return sim.run(max_cycles, chunk=chunk)[:spec.size]
