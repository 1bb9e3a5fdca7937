"""Serial golden model vs vectorized JAX simulator (paper §7.3 methodology).

The GPU paper validates its parallel simulator against the serial C++ one;
we assert bit-identical statistics AND identical cycle counts.
"""
import dataclasses

import numpy as np
import pytest
from hypothesis import HealthCheck, given, settings, strategies as st

from repro.core.config import SimConfig
from repro.core.ref_serial import SerialSim
from repro.core.sim import VectorSim, run
from repro.core.workloads import (PATTERN_NAMES, TRACE_APPS, app_trace,
                                  random_trace, resolve_trace)


def final_stats_equal(cfg: SimConfig, trace) -> None:
    ref = SerialSim(cfg, trace).run()
    got = run(cfg, trace)
    assert ref == got, {k: (ref[k], got.get(k)) for k in ref
                        if ref[k] != got.get(k)}


@pytest.mark.parametrize("app,seed,dist", [
    ("matmul", 1, False),
    ("equake", 7, False),
    ("mgrid", 2, True),
    ("random", 3, True),
])
def test_end_to_end_identical(app, seed, dist):
    cfg = SimConfig(rows=4, cols=4, addr_bits=14, migrate_threshold=2,
                    centralized_directory=not dist)
    tr = (random_trace(cfg, 30, seed) if app == "random"
          else app_trace(cfg, app, 30, seed))
    final_stats_equal(cfg, tr)


#: every traffic source: the paper's apps, ``random`` and the NoC patterns.
SOURCES = (*TRACE_APPS, "random", *PATTERN_NAMES)

#: the directory arrangements the simulator supports.
DIRECTORIES = {
    "central_flat": dict(centralized_directory=True, dir_layout="flat"),
    "dist_flat": dict(centralized_directory=False, dir_layout="flat"),
    "dist_home": dict(centralized_directory=False, dir_layout="home"),
}


@pytest.mark.parametrize("layout", ["wide", "packed"])
@pytest.mark.parametrize("directory", list(DIRECTORIES))
@pytest.mark.parametrize("source", SOURCES)
def test_dense_matches_serial(source, directory, layout):
    """Every source, directory arrangement and state layout agrees with
    the serial golden model."""
    cfg = SimConfig(rows=4, cols=4, addr_bits=14, migrate_threshold=2,
                    state_dtype_policy=layout, **DIRECTORIES[directory])
    final_stats_equal(cfg, resolve_trace(cfg, source, 20, 5))


def test_nonsquare_mesh():
    cfg = SimConfig(rows=3, cols=5, addr_bits=14)
    final_stats_equal(cfg, app_trace(cfg, "apsi", 25, 11))


def test_flat_vs_home_directory_layout():
    cfg = SimConfig(rows=4, cols=4, addr_bits=14,
                    centralized_directory=False)
    tr = app_trace(cfg, "wupwise", 30, 5)
    a = run(cfg, tr)
    b = run(dataclasses.replace(cfg, dir_layout="home"), tr)
    assert a == b


@settings(max_examples=3, deadline=None,
          suppress_health_check=list(HealthCheck))
@given(
    rows=st.integers(2, 4),
    cols=st.integers(2, 4),
    refs=st.integers(10, 25),
    seed=st.integers(0, 100),
    thr=st.integers(1, 4),
    dist=st.booleans(),
)
def test_property_equivalence(rows, cols, refs, seed, thr, dist):
    """Any small config: serial and vectorized agree exactly."""
    cfg = SimConfig(rows=rows, cols=cols, addr_bits=13,
                    migrate_threshold=thr, centralized_directory=not dist)
    tr = random_trace(cfg, refs, seed)
    final_stats_equal(cfg, tr)


def test_lockstep_state():
    """Cycle-by-cycle: the first 300 cycles match on every FSM/stat field."""
    cfg = SimConfig(rows=3, cols=3, addr_bits=13, migrate_threshold=2)
    tr = app_trace(cfg, "matmul", 20, 4)
    ss = SerialSim(cfg, tr)
    vs = VectorSim(cfg, tr)
    for cyc in range(300):
        ss.step()
        vs.step()
        s = vs.state
        assert np.array_equal(ss.st, np.asarray(s.st)), cyc
        assert np.array_equal(ss.tr_ptr, np.asarray(s.tr_ptr)), cyc
        assert np.array_equal(
            np.array([len(q) for q in ss.sendq]), np.asarray(s.q_size)), cyc
        if ss.finished():
            break
    assert ss.finished() == bool(np.asarray(vs.stats()["finished"]))
