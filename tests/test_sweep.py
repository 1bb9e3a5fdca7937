"""Batched sweep engine vs per-scenario solo runs (bit-exact).

``run_sweep`` executes B scenarios in one vmapped compiled loop; every
test here asserts its per-scenario stats are *identical* — every counter,
the cycle count, and the finished flag — to what a solo
:func:`repro.core.sim.run` produces for the same scenario.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

import numpy as np
import pytest

from repro.core.config import SimConfig
from repro.core.sim import run
from repro.core.sweep import ScenarioSpec, SweepSpec, run_sweep
from repro.core.workloads import app_trace, random_trace, stacked_traces


def solo(cfg: SimConfig, sc: ScenarioSpec):
    rc = sc.resolve_cfg(cfg)
    tr = (random_trace(rc, sc.refs_per_core, sc.seed) if sc.app == "random"
          else app_trace(rc, sc.app, sc.refs_per_core, sc.seed))
    return run(rc, tr)


def assert_matches_solo(cfg: SimConfig, spec: SweepSpec, got) -> None:
    assert len(got) == spec.size
    for sc, g in zip(spec.scenarios, got):
        ref = solo(cfg, sc)
        assert ref == g, (sc, {k: (ref[k], g.get(k)) for k in ref
                               if ref[k] != g.get(k)})


def test_sweep_apps_by_seeds_bit_exact():
    """8 scenarios (4 apps x 2 seeds) in one jitted batch == 8 solo runs."""
    cfg = SimConfig(rows=4, cols=4, addr_bits=14, migrate_threshold=2,
                    centralized_directory=False)
    spec = SweepSpec.cross(cfg, ["matmul", "equake", "mgrid", "random"],
                           [1, 7], refs_per_core=25)
    assert spec.size == 8
    assert_matches_solo(cfg, spec, run_sweep(spec))


def test_sweep_mixed_termination():
    """Scenarios of different lengths coexist: early finishers freeze
    bit-exactly while stragglers keep stepping (chunked loop included)."""
    cfg = SimConfig(rows=4, cols=4, addr_bits=14, centralized_directory=False)
    spec = SweepSpec(cfg, (
        ScenarioSpec("wupwise", 5, refs_per_core=8),
        ScenarioSpec("wupwise", 5, refs_per_core=40),
        ScenarioSpec("apsi", 2, refs_per_core=15),
    ))
    got = run_sweep(spec, chunk=4)
    assert got[0]["cycles"] < got[1]["cycles"]
    assert all(g["finished"] for g in got)
    assert_matches_solo(cfg, spec, got)


def test_sweep_policy_knobs():
    """Per-scenario traced knobs (migration on/off, threshold, directory
    placement) match solo runs whose *static* config carries the knob."""
    cfg = SimConfig(rows=4, cols=4, addr_bits=14, centralized_directory=False)
    spec = SweepSpec(cfg, (
        ScenarioSpec("matmul", 3, 25, migration_enabled=False),
        ScenarioSpec("matmul", 3, 25, migrate_threshold=1),
        ScenarioSpec("matmul", 3, 25, centralized_directory=True),
        ScenarioSpec("matmul", 3, 25),
    ))
    got = run_sweep(spec)
    # the knobs must actually change behaviour, not just be carried along
    assert len({tuple(sorted(g.items())) for g in got}) > 1
    assert_matches_solo(cfg, spec, got)


def test_sweep_chunked_equals_unchunked():
    cfg = SimConfig(rows=4, cols=4, addr_bits=14, centralized_directory=False)
    spec = SweepSpec.cross(cfg, ["mgrid"], [0, 3], refs_per_core=20)
    assert run_sweep(spec, chunk=1) == run_sweep(spec, chunk=8)


def test_stacked_traces_padding():
    cfg = SimConfig(rows=4, cols=4, addr_bits=14)
    trs = stacked_traces(cfg, [("matmul", 0, 10), ("matmul", 0, 30)])
    assert trs.shape == (2, cfg.num_nodes, 30)
    assert np.all(trs[0, :, 10:] == -1)
    assert np.array_equal(trs[0, :, :10], app_trace(cfg, "matmul", 10, 0))


def test_sweep_rejects_centralized_with_home_layout():
    cfg = SimConfig(rows=4, cols=4, addr_bits=14,
                    centralized_directory=False, dir_layout="home")
    spec = SweepSpec(cfg, (ScenarioSpec("matmul", 0, 10,
                                        centralized_directory=True),))
    with pytest.raises(ValueError):
        run_sweep(spec)


def test_sweep_sharded_over_host_devices():
    """run_sweep shards the scenario axis over jax devices; results must
    stay bit-identical to solo runs (subprocess so the main pytest
    process keeps its single CPU device)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import sys, json
        sys.path.insert(0, "src")
        from repro.core.config import SimConfig
        from repro.core.sim import run
        from repro.core.sweep import SweepSpec, run_sweep
        from repro.core.workloads import app_trace

        cfg = SimConfig(rows=4, cols=4, addr_bits=14, migrate_threshold=2,
                        centralized_directory=False)
        spec = SweepSpec.cross(cfg, ["matmul", "equake"], [1, 7], 20)
        got = run_sweep(spec, chunk=4)
        ref = [run(cfg, app_trace(cfg, sc.app, 20, sc.seed))
               for sc in spec.scenarios]
        print("RESULT " + json.dumps({"match": got == ref}))
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                         capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            assert json.loads(line[len("RESULT "):])["match"]
            return
    raise AssertionError(
        f"no result\nstdout={out.stdout}\nstderr={out.stderr[-2000:]}")


def test_sweep_indivisible_batch_pads_across_devices():
    """3 scenarios on 2 devices: run_sweep pads the batch to 4 internally
    so both devices are used; results stay bit-identical to solo runs
    (subprocess so the main pytest process keeps its single device)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
        import sys, json
        sys.path.insert(0, "src")
        from repro.core.config import SimConfig
        from repro.core.sim import run
        from repro.core.sweep import SweepSpec, run_sweep
        from repro.core.workloads import app_trace

        cfg = SimConfig(rows=4, cols=4, addr_bits=14, migrate_threshold=2,
                        centralized_directory=False)
        spec = SweepSpec.cross(cfg, ["matmul", "equake", "mgrid"], [3], 15)
        got = run_sweep(spec, chunk=4)
        ref = [run(cfg, app_trace(cfg, sc.app, 15, sc.seed))
               for sc in spec.scenarios]
        print("RESULT " + json.dumps({"n": len(got), "match": got == ref}))
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                         capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            res = json.loads(line[len("RESULT "):])
            assert res["n"] == 3 and res["match"], res
            return
    raise AssertionError(
        f"no result\nstdout={out.stdout}\nstderr={out.stderr[-2000:]}")


def test_solo_run_unchanged_by_batch_support():
    """A 2-D trace still drives the classic solo path (regression guard
    for the batch-axis threading through init_state/_run_jit)."""
    cfg = SimConfig(rows=4, cols=4, addr_bits=14, migrate_threshold=1,
                    centralized_directory=False)
    tr = app_trace(cfg, "matmul", 25, 3)
    a = run(cfg, tr)
    b = run(dataclasses.replace(cfg, migration_enabled=False), tr)
    assert a["finished"] and b["finished"]
    assert a["migrations"] > 0 and b["migrations"] == 0
    assert a != b  # knob still has effect on the solo path
