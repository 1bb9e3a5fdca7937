"""Vectorized trace synthesis vs the seed per-node-loop generators.

Two levels of parity:

* :func:`repro.core.workloads.from_model_schedule` is **bit-identical** to the
  original loop (the only random draws are the activation block indices,
  and numpy's bounded-integer sampling consumes the PCG64 stream the same
  way scalar-by-scalar and in blocks).
* :func:`repro.core.workloads.app_trace` draws its streams in a different
  (blocked, per-slab) order than the loop reference
  :func:`repro.core.workloads.app_trace_loop`, so arrays differ element-wise;
  equivalence is asserted at the distribution level — region mix, zipf
  concentration of the shared region, hot-set reuse — which is what the
  simulator's traffic actually depends on.
"""
import numpy as np

from repro.core.config import SimConfig
from repro.core.workloads import (app_trace, app_trace_loop,
                                  from_model_schedule, random_trace,
                                  stacked_traces, TRACE_APPS)


def _fms_loop_reference(cfg, layer_params_bytes, d_model, n_layers,
                        refs_per_core=200, seed=0):
    """Verbatim copy of the seed per-node-loop from_model_schedule."""
    g = np.random.default_rng(np.random.PCG64(seed))
    n = cfg.num_nodes
    addr_space = 1 << cfg.addr_bits
    blk = cfg.cache.l2_block
    w_region = addr_space // 2
    act_region = addr_space - w_region
    shard = max(blk * 8, min(layer_params_bytes // max(1, n // n_layers),
                             w_region // n))
    out = np.full((n, refs_per_core), -1, dtype=np.int64)
    act_blocks = max(1, (d_model * 2) // blk)
    for node in range(n):
        layer = node % n_layers
        wbase = (node * shard) % max(blk, w_region - shard)
        abase = w_region + (layer * act_blocks * blk) % max(
            blk, act_region - act_blocks * blk)
        i = 0
        while i < refs_per_core:
            for _ in range(min(6, refs_per_core - i)):
                out[node, i] = wbase + ((i * blk) % shard)
                i += 1
            if i < refs_per_core:
                out[node, i] = abase + int(g.integers(0, act_blocks)) * blk
                i += 1
    return (out % addr_space).astype(np.int32)


def test_from_model_schedule_bit_identical_to_loop():
    cfg = SimConfig(rows=8, cols=8, addr_bits=16)
    for refs in (13, 14, 20, 21, 200):
        vec = from_model_schedule(cfg, 1 << 20, 512, 4, refs, seed=3)
        ref = _fms_loop_reference(cfg, 1 << 20, 512, 4, refs, seed=3)
        assert np.array_equal(vec, ref), refs


def test_app_trace_shape_dtype_range_determinism():
    cfg = SimConfig(rows=8, cols=8, addr_bits=16)
    for app in TRACE_APPS:
        t = app_trace(cfg, app, 37, seed=9)
        assert t.shape == (64, 37) and t.dtype == np.int32
        assert t.min() >= 0 and t.max() < (1 << cfg.addr_bits)
        assert np.array_equal(t, app_trace(cfg, app, 37, seed=9))
    assert not np.array_equal(app_trace(cfg, "matmul", 37, 1),
                              app_trace(cfg, "matmul", 37, 2))


def test_app_trace_multi_slab_deterministic():
    """A mesh spanning several synthesis slabs (8192 nodes each) is still a
    pure function of (cfg, app, refs, seed) under the thread pool."""
    cfg = SimConfig(rows=96, cols=96)        # 9216 nodes = 2 slabs
    a = app_trace(cfg, "equake", 10, seed=4)
    b = app_trace(cfg, "equake", 10, seed=4)
    assert a.shape == (9216, 10)
    assert np.array_equal(a, b)


def test_app_trace_distribution_matches_loop_reference():
    """Region mix and shared-region zipf concentration of the vectorized
    generator match the seed loop generator (same model parameters, a
    different PCG64 draw order)."""
    cfg = SimConfig(rows=8, cols=8, addr_bits=16)
    shared_hi = (1 << cfg.addr_bits) // 4
    refs = 400
    for app, params in TRACE_APPS.items():
        vec = app_trace(cfg, app, refs, seed=5)
        ref = app_trace_loop(cfg, app, refs, seed=5)
        # fraction of references landing in the shared region
        fv = float((vec < shared_hi).mean())
        fl = float((ref < shared_hi).mean())
        assert abs(fv - fl) < 0.05, (app, fv, fl)
        # the shared region is zipf-concentrated the same way: the single
        # hottest L2 block takes the same share of shared traffic
        blk = cfg.cache.l2_block
        sv, sl = vec[vec < shared_hi], ref[ref < shared_hi]
        top_v = np.bincount(sv // blk).max() / len(sv)
        top_l = np.bincount(sl // blk).max() / len(sl)
        assert abs(top_v - top_l) < 0.08, (app, top_v, top_l)
        # private-region traffic reuses a small hot set plus a stride
        # cursor: per-node unique-address count far below refs
        pv = vec[0][vec[0] >= shared_hi]
        assert len(np.unique(pv)) < len(pv), app


def test_app_trace_edge_node_neighbour_uniformity():
    """A 3-neighbour border node picks each neighbour uniformly (a modulo
    of a fixed-range draw would bias the first one to 1/2)."""
    cfg = SimConfig(rows=8, cols=8, addr_bits=16)
    shared_hi = (1 << cfg.addr_bits) // 4
    priv = max(cfg.cache.l2_block * 4,
               ((1 << cfg.addr_bits) - shared_hi) // cfg.num_nodes)
    node = 1                                  # top edge: neighbours 9, 0, 2
    tr = app_trace(cfg, "mgrid", 20_000, seed=3)[node]
    owners = (tr[tr >= shared_hi] - shared_hi) // priv
    counts = np.bincount(owners[np.isin(owners, (0, 2, 9))],
                         minlength=10)[[0, 2, 9]]
    assert counts.min() > 0
    assert counts.max() / counts.min() < 1.25, counts


def test_stacked_traces_uses_vectorized_generator():
    cfg = SimConfig(rows=4, cols=4, addr_bits=14)
    trs = stacked_traces(cfg, [("matmul", 0, 10), ("random", 1, 30)])
    assert trs.shape == (2, cfg.num_nodes, 30)
    assert np.all(trs[0, :, 10:] == -1)
    assert np.array_equal(trs[0, :, :10], app_trace(cfg, "matmul", 10, 0))
    assert np.array_equal(trs[1], random_trace(cfg, 30, 1))
