"""Trace synthesis for the reference: the paper's application models and
the synthetic NoC patterns, as address streams.

A copy of the simulator's generators, kept with the benchmark so that the
reference derives its inputs from the seed by itself: the program makes
its own traces from the same source spec and seed, and the two have to
agree bit for bit for the statistics to.  A source spec is ``name`` or
``name:key=val,...`` (``rate``, and ``frac``/``hot`` for ``hotspot``).
"""
from __future__ import annotations

import os
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .machine import Machine

#: node-slab size for vectorized synthesis; fixed so the generated trace is
#: a pure function of (cfg, app, refs, seed), never of how slabs divide n.
_SLAB = 8192

#: registered synthetic-pattern names
PATTERN_NAMES = ("transpose", "bitcomp", "hotspot", "tornado", "neighbor")

#: per-node local hot-set size for filler (sub-``rate``) references.
_FILLER_HOT = 4


TRACE_APPS = {
    "matmul": dict(stride=8, p_shared=0.45, p_local=0.35, hot_blocks=8, p_neighbour=0.05),
    "apsi": dict(stride=16, p_shared=0.20, p_local=0.50, hot_blocks=16, p_neighbour=0.10),
    "mgrid": dict(stride=8, p_shared=0.10, p_local=0.45, hot_blocks=12, p_neighbour=0.30),
    "wupwise": dict(stride=64, p_shared=0.25, p_local=0.40, hot_blocks=8, p_neighbour=0.10),
    "equake": dict(stride=4, p_shared=0.30, p_local=0.25, hot_blocks=24, p_neighbour=0.10),
}


def _app_seed(app: str, seed: int) -> int:
    stable = sum(ord(ch) * (i + 1) for i, ch in enumerate(app)) % 65536
    return seed * 1_000_003 + stable


def _region_layout(cfg: Machine):
    addr_space = 1 << cfg.addr_bits
    blk = cfg.cache.l2_block
    shared_hi = addr_space // 4
    priv_size = max(blk * 4, (addr_space - shared_hi) // cfg.num_nodes)
    return addr_space, blk, shared_hi, priv_size


def _neighbour_table(cfg: Machine, nodes: np.ndarray):
    """(len(nodes), 4) neighbour node ids (repeat-padded) + counts."""
    r, c = nodes // cfg.cols, nodes % cfg.cols
    cand = np.stack([
        np.where(r > 0, nodes - cfg.cols, -1),
        np.where(r < cfg.rows - 1, nodes + cfg.cols, -1),
        np.where(c > 0, nodes - 1, -1),
        np.where(c < cfg.cols - 1, nodes + 1, -1),
    ], axis=1)
    # compact valid neighbours to the front (stable order: up, down, left,
    # right — the same enumeration order as the loop reference)
    order = np.argsort(cand < 0, axis=1, kind="stable")
    cand = np.take_along_axis(cand, order, axis=1)
    count = (cand >= 0).sum(axis=1)
    # pad with the first neighbour so any index is safe (never selected:
    # picks are drawn modulo count)
    cand = np.where(cand < 0, cand[:, :1], cand)
    return cand, count


def app_trace(cfg: Machine, app: str, refs_per_core: int = 200, seed: int = 0) -> np.ndarray:
    """Representative trace for one of the paper's five applications.

    Vectorized synthesis: all randomness is drawn as ``(slab, M)`` blocks
    (one slab = up to ``_SLAB`` nodes), so generation is O(numpy ops), not
    O(n*M) Python iterations.  Draw order differs from the historical
    per-node loop (:func:`app_trace_loop`), so addresses differ draw-by-draw
    while the access-pattern *distribution* (region mix, hot-set reuse,
    stride behaviour) is identical — see ``tests/test_trace_vec.py``.
    """
    if app not in TRACE_APPS:
        raise ValueError(f"unknown app {app!r}; choose from {sorted(TRACE_APPS)}")
    p = TRACE_APPS[app]
    n, m = cfg.num_nodes, refs_per_core
    addr_space, blk, shared_hi, priv_size = _region_layout(cfg)
    priv_blocks = max(1, priv_size // blk)
    n_shared_blocks = max(1, shared_hi // blk)

    # bounded zipf(1.6) over the shared blocks by inverse CDF: one uniform
    # draw + searchsorted instead of numpy's rejection sampler.  The loop
    # reference draws unbounded zipf then wraps modulo n_shared_blocks; the
    # wrap moves < 1% of the mass at realistic block counts, so the two are
    # distribution-equivalent (asserted by tests/test_trace_vec.py).
    zcdf = np.cumsum(np.arange(1, n_shared_blocks + 1, dtype=np.float64)
                     ** -1.6)
    zcdf /= zcdf[-1]

    # int32 arithmetic end-to-end (addresses are bounded by
    # shared_hi + n*priv_size + priv_size): at 13M samples per 256x256
    # trace the generator is memory-bandwidth bound, so halving the
    # element width matters.  Fall back to int64 for astronomically
    # large meshes.
    top = shared_hi + (n + 1) * priv_size
    idt = np.int32 if top < 2**31 else np.int64
    t_local = p["p_shared"] + p["p_local"]
    t_nb = t_local + p["p_neighbour"]

    out = np.empty((n, m), dtype=np.int32)

    def fill_slab(slab_index: int) -> None:
        # per-slab generator derived from (app, seed, slab): slabs are
        # independent streams, so synthesis parallelizes over host threads
        # (numpy releases the GIL in the fill/searchsorted/cumsum kernels)
        # while staying a pure function of (cfg, app, refs, seed).
        g = np.random.default_rng(np.random.PCG64(
            np.random.SeedSequence([_app_seed(app, seed), slab_index])))
        lo = slab_index * _SLAB
        nodes = np.arange(lo, min(lo + _SLAB, n), dtype=idt)
        ns = len(nodes)
        base = (shared_hi + nodes * priv_size).astype(idt)

        hot = base[:, None] + g.integers(
            0, priv_blocks, (ns, p["hot_blocks"]), dtype=idt) * blk
        kinds = g.random((ns, m), dtype=np.float32)
        hot_idx = g.integers(0, p["hot_blocks"], (ns, m), dtype=np.int32)
        # uniform over each node's own neighbour count (2..4): scale one
        # uniform draw by the count — a modulo of a fixed-range draw would
        # bias the first neighbour on 3-neighbour border nodes
        nb_u = g.random((ns, m), dtype=np.float32)
        nb_block = g.integers(0, priv_blocks, (ns, m), dtype=idt)

        # default: the strided-cursor branch (cursor advances only on
        # strided references: a cumulative count, not a sequential loop)
        is_else = kinds >= t_nb
        strided = np.cumsum(is_else, axis=1, dtype=idt) * p["stride"]
        a = base[:, None] + strided % priv_size

        shared_m = kinds < p["p_shared"]
        local_m = (kinds >= p["p_shared"]) & (kinds < t_local)
        nb_m = (kinds >= t_local) & ~is_else & ~local_m

        # shared branch: draw exactly the uniforms it needs (the count is
        # a pure function of `kinds`, so generation stays deterministic)
        zu = g.random(int(shared_m.sum()), dtype=np.float32)
        zb = (np.searchsorted(zcdf, zu).astype(idt) + 1) % n_shared_blocks
        a[shared_m] = zb * blk

        a_local = np.take_along_axis(hot, hot_idx.astype(idt), axis=1)
        a[local_m] = a_local[local_m]

        nb_table, nb_count = _neighbour_table(cfg, nodes)
        nb_pick = (nb_u * nb_count[:, None]).astype(idt)
        nb = np.take_along_axis(nb_table.astype(idt), nb_pick, axis=1)
        a_nb = shared_hi + nb * priv_size + nb_block * blk
        a[nb_m] = a_nb[nb_m]

        out[lo:lo + ns] = a % addr_space

    n_slabs = -(-n // _SLAB)
    if n_slabs == 1:
        fill_slab(0)
    else:
        workers = min(n_slabs, os.cpu_count() or 1)
        with ThreadPoolExecutor(workers) as ex:
            list(ex.map(fill_slab, range(n_slabs)))
    return out


def _pat_seed(name: str, seed: int):
    # same stable-hash construction as apps._app_seed, offset so a pattern
    # and an app with the same seed never share a stream
    stable = sum(ord(ch) * (i + 1) for i, ch in enumerate(name)) % 65536
    return np.random.SeedSequence([0x5E7A, stable, seed])


def _rc(cfg: Machine):
    i = np.arange(cfg.num_nodes, dtype=np.int64)
    return i // cfg.cols, i % cfg.cols


def dst_map(cfg: Machine, name: str) -> np.ndarray:
    """The ``(N,)`` destination-node map of a deterministic pattern
    (``transpose`` / ``bitcomp`` / ``tornado`` / ``neighbor``) for
    ``cfg``'s mesh — the ground truth the property tests assert against.
    ``hotspot`` is stochastic and has no fixed map (``ValueError``)."""
    r, c = _rc(cfg)
    if name == "transpose":
        return (c * cfg.rows + r).astype(np.int64)
    if name == "bitcomp":
        return cfg.num_nodes - 1 - np.arange(cfg.num_nodes, dtype=np.int64)
    if name == "tornado":
        return (((r + cfg.rows // 2) % cfg.rows) * cfg.cols
                + (c + cfg.cols // 2) % cfg.cols)
    if name == "neighbor":
        return (r * cfg.cols + (c + 1) % cfg.cols).astype(np.int64)
    raise ValueError(f"pattern {name!r} has no deterministic destination "
                     f"map; deterministic patterns: "
                     f"{[n for n in PATTERN_NAMES if n != 'hotspot']}")


def pattern_trace(cfg: Machine, refs_per_core: int, seed: int,
                  dst, rate: float, name: str) -> np.ndarray:
    """Synthesize the address stream realizing a destination pattern.

    Args:
        cfg: simulated machine (mesh + address-space geometry).
        refs_per_core: references per node (the trace's ``M``).
        seed: RNG seed; the stream is a pure function of
            ``(cfg, name, seed, params)``.
        dst: destination node per reference — ``(N,)`` (broadcast over
            references) or ``(N, M)``.
        rate: injection rate in ``[0, 1]`` — probability a reference
            carries pattern traffic; the rest re-touch a node-local
            hot set (home == self, so no network traffic after the
            first-touch memory fill).
        name: pattern name (seeds the per-pattern RNG stream).

    Returns: ``(N, M) int32`` addresses.  A pattern reference uses tag
    ``dst + k*N`` with ``k`` uniform over the tag space, so its
    directory home is exactly ``dst`` and repeated tags (which would be
    cache-hot and silent) are rare.

    Raises ``ValueError`` when the directory has fewer entries than the
    mesh has nodes: the home map ``tag % N`` then cannot reach every
    destination and the ``% entries`` wrap would silently scramble both
    the pattern and the rate throttle — grow ``cfg.addr_bits`` (or
    shrink ``cfg.cache.l2_block``) instead."""
    n, m = cfg.num_nodes, refs_per_core
    if cfg.dir_entries < n:
        raise ValueError(
            f"pattern {name!r} needs at least one directory entry per "
            f"node to realize destination homes, but dir_entries="
            f"{cfg.dir_entries} < num_nodes={n} "
            f"(addr_bits={cfg.addr_bits}, l2_block={cfg.cache.l2_block}); "
            "increase addr_bits")
    g = np.random.default_rng(np.random.PCG64(_pat_seed(name, seed)))
    entries = cfg.dir_entries
    k_span = max(1, entries // n)
    dst = np.asarray(dst, np.int64)
    if dst.ndim == 1:
        dst = dst[:, None]

    nodes = np.arange(n, dtype=np.int64)[:, None]
    kdraw = g.integers(0, k_span, (n, m))
    is_pat = g.random((n, m)) < rate
    # filler hot set: tags congruent to the own node id → inline directory,
    # cache-hot after first touch
    hot = nodes + g.integers(0, k_span, (n, _FILLER_HOT)) * n
    filler = np.take_along_axis(hot, g.integers(0, _FILLER_HOT, (n, m)),
                                axis=1)
    tag = np.where(is_pat, dst + kdraw * n, filler) % entries
    return (tag << cfg.cache.l2_shift).astype(np.int32)


def _hotspot_dst(cfg: Machine, g: np.random.Generator, m: int,
                 frac: float, hot: int) -> np.ndarray:
    n = cfg.num_nodes
    hot = min(hot, n)
    hot_ids = (np.arange(hot, dtype=np.int64) * n) // hot   # evenly spaced
    pick = g.integers(0, hot, (n, m))
    uni = g.integers(0, n, (n, m))
    return np.where(g.random((n, m)) < frac, hot_ids[pick], uni)


def _hotspot_trace(cfg: Machine, refs: int, seed: int, rate: float = 1.0,
                   frac: float = 0.5, hot: int = 1) -> np.ndarray:
    g = np.random.default_rng(np.random.PCG64(_pat_seed("hotspot@", seed)))
    dst = _hotspot_dst(cfg, g, refs, frac, hot)
    return pattern_trace(cfg, refs, seed, dst, rate, "hotspot")


def trace(cfg: Machine, spec: str, refs_per_core: int, seed: int
          ) -> np.ndarray:
    """The ``(num_nodes, refs_per_core)`` int32 trace of source ``spec``."""
    name, _, argstr = spec.partition(":")
    params = {}
    for tok in filter(None, (t.strip() for t in argstr.split(","))):
        key, _, raw = tok.partition("=")
        params[key.strip()] = int(raw) if key.strip() == "hot" else float(raw)
    if name in TRACE_APPS:
        if params:
            raise ValueError(f"application {name!r} takes no parameters")
        return app_trace(cfg, name, refs_per_core, seed)
    if name == "hotspot":
        return _hotspot_trace(cfg, refs_per_core, seed, **params)
    if name in PATTERN_NAMES:
        return pattern_trace(cfg, refs_per_core, seed, dst_map(cfg, name),
                             params.get("rate", 1.0), name)
    raise ValueError(f"unknown trace source {spec!r}")
