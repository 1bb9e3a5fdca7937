"""Composed scenario x row x col backend (repro.core.sharded.run_composed).

The contract under test: every degenerate device grid of the composed
backend is *exact* — a ``(1, 1, 1)`` grid on one device is a solo run, a
``(1, rt, ct)`` grid is the spatial backend, an indivisible scenario
axis pads with copies like ``run_sweep`` — all bit-identical to
sequential solo :func:`repro.core.sim.run` calls.  Plus the planner's
composed grid factoring and the calibration-file round trip.
"""
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.core import engine
from repro.core.config import SimConfig

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# degeneracies that run on the lone in-process CPU device
# ---------------------------------------------------------------------------

def test_composed_single_device_degenerates_to_solo():
    """grid (1,1,1): the full composed machinery (3-axis mesh, batched
    shard_map, identity ppermutes) on ONE device must reproduce solo
    runs bit-identically — including a per-scenario policy knob."""
    from repro.core.sharded import run_composed
    from repro.core.sim import run
    from repro.core.sweep import ScenarioSpec, SweepSpec
    from repro.core.trace import app_trace

    cfg = SimConfig(rows=4, cols=4, addr_bits=14,
                    centralized_directory=False)
    spec = SweepSpec(cfg, (
        ScenarioSpec("matmul", seed=0, refs_per_core=10),
        ScenarioSpec("mgrid", seed=1, refs_per_core=12,
                     migration_enabled=False),
    ))
    got = run_composed(spec, (1, 1, 1), chunk=16)
    ref = []
    for sc in spec.scenarios:
        c = sc.resolve_cfg(dataclasses.replace(cfg, dir_layout="home"))
        ref.append(run(c, app_trace(c, sc.app, sc.refs_per_core, sc.seed)))
    assert got == ref, [
        {k: (a.get(k), b.get(k)) for k in b if a.get(k) != b.get(k)}
        for a, b in zip(got, ref)]


def test_composed_clamps_max_cycles():
    """An unfinished capped composed run stops at exactly max_cycles for
    every scenario (tail-chunk clamp), matching the dense backend."""
    from repro.core.sharded import run_composed
    from repro.core.sim import run
    from repro.core.sweep import ScenarioSpec, SweepSpec
    from repro.core.trace import app_trace

    cfg = SimConfig(rows=4, cols=4, addr_bits=14,
                    centralized_directory=False)
    spec = SweepSpec(cfg, (ScenarioSpec("mgrid", seed=1, refs_per_core=25),
                           ScenarioSpec("matmul", seed=0, refs_per_core=25)))
    got = run_composed(spec, (1, 1, 1), max_cycles=100, chunk=64)
    hc = dataclasses.replace(cfg, dir_layout="home")
    for sc, g in zip(spec.scenarios, got):
        c = sc.resolve_cfg(hc)
        ref = run(c, app_trace(c, sc.app, sc.refs_per_core, sc.seed),
                  max_cycles=100)
        assert g["cycles"] == 100 and g["finished"] == 0
        assert g == ref


def test_composed_rejects_centralized_and_short_device_list():
    from repro.core.sharded import run_composed
    from repro.core.sweep import ScenarioSpec, SweepSpec

    cfg = SimConfig(rows=4, cols=4, addr_bits=14,
                    centralized_directory=False)
    with pytest.raises(ValueError, match="centralized"):
        run_composed(SweepSpec(cfg, (
            ScenarioSpec("matmul", centralized_directory=True),)),
            (1, 1, 1))
    # ask for one more device than the host exposes, whatever that is
    # (this suite also runs under XLA_FLAGS-faked multi-device hosts)
    import jax
    bs_over = len(jax.devices()) // 4 + 1
    with pytest.raises(ValueError, match="device"):
        run_composed(SweepSpec(cfg, (ScenarioSpec("matmul"),)),
                     (bs_over, 2, 2))


def test_composed_batched_livelock_abort_with_healthy_batchmate():
    """Per-scenario host monitor: the ROADMAP livelock wedge (16x16 /
    matmul / seed 0 / refs 20, loop-trace) aborts with its diagnostic
    while the healthy scenario sharing the batch finishes bit-identically
    to its solo run.  The wedge needs the paper-faithful ``pc_depth=1``
    escape hatch — the default pending-completion queue resolves it
    (tests/test_pc_queue.py)."""
    import jax
    import numpy as np
    from repro.core.sharded import ShardedSim
    from repro.core.sim import run
    from repro.core.trace import app_trace, app_trace_loop

    cfg = SimConfig(rows=16, cols=16, centralized_directory=False,
                    dir_layout="home", max_cycles=30_000, pc_depth=1)
    wedge = app_trace_loop(cfg, "matmul", 20, 0)   # the exact ROADMAP combo
    healthy = app_trace(cfg, "equake", 10, 1)
    m = max(wedge.shape[1], healthy.shape[1])
    tr = np.full((2, cfg.num_nodes, m), -1, np.int32)   # -1 = exhaustion pad
    tr[0, :, :wedge.shape[1]] = wedge
    tr[1, :, :healthy.shape[1]] = healthy
    mesh = jax.make_mesh((1, 1, 1), ("scenario", "data", "model"))
    got = ShardedSim(cfg, tr, mesh, batch_axes=("scenario",)).run(chunk=128)

    assert got[0]["aborted"] == "livelock"
    assert got[0]["finished"] == 0
    assert got[0]["cycles"] < 30_000      # aborted, not budget-burned
    assert got[0]["circulating_flits"] > 50
    assert got[0]["wait_dir_nodes"] + got[0]["wait_data_nodes"] > 128
    assert got[1] == run(cfg, healthy)


# ---------------------------------------------------------------------------
# real scenario-axis + spatial sharding (subprocess: 8 host devices)
# ---------------------------------------------------------------------------

def test_composed_grid_batch_padding_and_spatial_degeneracy():
    """On a 2x2x2 device grid: an indivisible batch of 3 pads to 4 and
    stays bit-identical to solo runs; a batch of 1 on a (1,2,2) grid
    matches the spatial ShardedSim and the solo run."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys, json, dataclasses
        sys.path.insert(0, "src")
        import jax
        from repro.core.config import SimConfig
        from repro.core.sharded import ShardedSim, run_composed
        from repro.core.sim import run
        from repro.core.sweep import ScenarioSpec, SweepSpec
        from repro.core.trace import app_trace

        cfg = SimConfig(rows=8, cols=8, addr_bits=16,
                        centralized_directory=False, migrate_threshold=2)
        spec = SweepSpec(cfg, (
            ScenarioSpec("mgrid", seed=2, refs_per_core=30),
            ScenarioSpec("matmul", seed=0, refs_per_core=25,
                         migration_enabled=False),
            ScenarioSpec("equake", seed=1, refs_per_core=20,
                         migrate_threshold=1),
        ))
        got = run_composed(spec, (2, 2, 2), chunk=64)
        hc = dataclasses.replace(cfg, dir_layout="home")
        ref = []
        for sc in spec.scenarios:
            c = sc.resolve_cfg(hc)
            ref.append(run(c, app_trace(c, sc.app, sc.refs_per_core,
                                        sc.seed)))

        one = SweepSpec(cfg, (spec.scenarios[0],))
        got1 = run_composed(one, (1, 2, 2), chunk=64)[0]
        c0 = spec.scenarios[0].resolve_cfg(hc)
        tr0 = app_trace(c0, "mgrid", 30, 2)
        mesh = jax.make_mesh((2, 2), ("data", "model"))
        spatial = ShardedSim(c0, tr0, mesh).run(chunk=64)
        print("RESULT " + json.dumps({
            "batch3_match": got == ref,
            "batch1_match": got1 == spatial == ref[0]}))
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                         capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            res = json.loads(line[len("RESULT "):])
            assert res["batch3_match"], res
            assert res["batch1_match"], res
            return
    raise AssertionError(
        f"no result\nstdout={out.stdout}\nstderr={out.stderr[-2000:]}")


# ---------------------------------------------------------------------------
# planner: grid factoring, backend choice, fallbacks
# ---------------------------------------------------------------------------

def test_choose_grid_factoring():
    # 8 devices, batch 2, 256x256: scenario axis takes 2, space takes 4
    grid, cost = engine.choose_grid(2, 256, 256, 8)
    assert grid[0] == 2 and grid[1] * grid[2] == 4
    assert cost < engine.backend_cost("sweep", 2, 256 * 256, 8)
    # one device: no composed grid exists
    assert engine.choose_grid(4, 16, 16, 1) == ((1, 1, 1), float("inf"))
    # batch 1 degenerates to the pure spatial factoring
    g1, c1 = engine.choose_grid(1, 256, 256, 8)
    assert g1[0] == 1 and g1[1] * g1[2] == 8
    assert c1 == engine.backend_cost("sharded", 1, 256 * 256, 8, g1[1:])


def test_backend_choice_composed():
    base = SimConfig(centralized_directory=False)
    big = dataclasses.replace(base, rows=256, cols=256)
    small = dataclasses.replace(base, rows=16, cols=16)
    # numerous AND large with devices to spare on both axes -> composed
    b, grid, note = engine.choose_backend(big, batch=2, ndev=8)
    assert b == "composed" and grid[0] == 2 and grid[1] * grid[2] == 4, \
        (b, grid, note)
    # batch >= devices: sweep already keeps every device busy
    assert engine.choose_backend(big, batch=8, ndev=4)[0] == "sweep"
    # batch == 1 belongs to the spatial backend, not composed
    assert engine.choose_backend(big, batch=1, ndev=8)[0] == "sharded"
    # small meshes never pay the collective cost
    assert engine.choose_backend(small, batch=2, ndev=8)[0] == "sweep"
    # centralized directory bars both spatial backends
    cen = dataclasses.replace(big, centralized_directory=True)
    assert engine.choose_backend(cen, batch=2, ndev=8)[0] == "sweep"


def test_forced_composed_falls_back_on_one_device():
    base = SimConfig(rows=4, cols=4, addr_bits=14,
                     centralized_directory=False)
    scs = [engine.make_scenario(base, app="matmul", seed=s,
                                refs_per_core=10) for s in range(2)]
    plan = engine.compile_plan(scs, ndev=1, force_backend="composed")
    b = plan.buckets[0]
    assert b.backend == "sweep" and "fell back" in b.note
    # with devices it sticks, and describe() reports the grid
    plan2 = engine.compile_plan(scs, ndev=8, force_backend="composed")
    b2 = plan2.buckets[0]
    assert b2.backend == "composed" and b2.devices_needed <= 8
    assert plan2.describe()["buckets"][0]["grid"] == list(b2.grid)


def test_composed_plan_on_short_device_list_degrades():
    """A composed plan compiled for more devices than this process has
    refuses to run rather than silently swapping in the sweep backend."""
    import jax
    base = SimConfig(rows=4, cols=4, addr_bits=14,
                     centralized_directory=False)
    scs = [engine.make_scenario(base, app="matmul", seed=s,
                                refs_per_core=10) for s in range(2)]
    plan = engine.compile_plan(scs, ndev=2 * len(jax.devices()),
                               force_backend="composed")
    b = plan.buckets[0]
    assert b.backend == "composed" and b.devices_needed > len(jax.devices())
    with pytest.raises(RuntimeError, match="planned for"):
        engine.execute_plan(plan, chunk=4)


# ---------------------------------------------------------------------------
# cost-model constants: calibration-file round trip
# ---------------------------------------------------------------------------

def test_cost_constants_roundtrip(tmp_path):
    defaults = engine.cost_constants()
    try:
        c = engine.CostConstants(halo_overhead=2.5, shard_fixed=512.0,
                                 batch_fixed=96.0)
        path = str(tmp_path / "cost_model.json")
        engine.save_cost_constants(path, c, meta={"devices": 8,
                                                  "note": "test"})
        loaded = engine.load_cost_constants(path)
        assert loaded == c == engine.cost_constants()
        # meta survives on disk but never leaks into the constants
        with open(path) as f:
            obj = json.load(f)
        assert obj["meta"]["devices"] == 8
        # the planner actually uses the loaded values
        assert engine.backend_cost("sharded", 1, 4096, 4, (2, 2)) \
            == 4096 / 4 * 2.5 + 512.0
        assert engine.backend_cost("composed", 4, 4096, 4, (2, 1, 2)) \
            == 2 * 4096 / 2 * 2.5 + 512.0 + 96.0
    finally:
        engine.set_cost_constants(defaults)
    assert engine.cost_constants() == defaults
