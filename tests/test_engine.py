"""Execution-plan layer (repro.core.engine).

The planner's contract: a heterogeneous scenario list — mixed mesh shapes,
apps, seeds, policy knobs — compiles into exactly one device program per
structural bucket, and the per-scenario statistics are *bit-identical* to
sequential solo :func:`repro.core.sim.run` calls in the original order.
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
from repro.core.sim import run, _run_jit
from repro.core.workloads import app_trace, random_trace

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def solo_reference(sc: engine.Scenario):
    tr = (random_trace(sc.cfg, sc.refs_per_core, sc.seed)
          if sc.app == "random"
          else app_trace(sc.cfg, sc.app, sc.refs_per_core, sc.seed))
    return run(sc.cfg, tr, chunk=4)


def test_mixed_shape_plan_bit_exact_one_compile_per_bucket():
    """Interleaved 4x4/6x6 scenarios with knob variety: two buckets, two
    compiled programs, results bit-identical to solo runs, order kept."""
    # addr_bits=15 + refs 23/24 make the state shapes unique to this test,
    # so the jit-cache delta below counts exactly this plan's compiles.
    base = SimConfig(addr_bits=15, centralized_directory=False)
    scs = [
        engine.make_scenario(base, 4, 4, "matmul", 0, 23),
        engine.make_scenario(base, 6, 6, "equake", 1, 24),
        engine.make_scenario(base, 4, 4, "mgrid", 2, 23,
                             migration_enabled=False),
        engine.make_scenario(base, 6, 6, "random", 3, 24,
                             migrate_threshold=1),
        engine.make_scenario(base, 4, 4, "matmul", 5, 23,
                             centralized_directory=True),
    ]
    plan = engine.compile_plan(scs, ndev=1)
    desc = plan.describe()
    assert desc["n_buckets"] == 2, desc
    assert [b["batch"] for b in desc["buckets"]] == [3, 2]

    before = _run_jit._cache_size()
    got = engine.execute_plan(plan, chunk=4)
    assert _run_jit._cache_size() - before == 2, \
        "expected exactly one compile per shape bucket"

    assert got == [solo_reference(sc) for sc in scs]


def test_knobs_do_not_split_buckets_but_shapes_do():
    base = SimConfig(addr_bits=14, centralized_directory=False)
    scs = [
        engine.make_scenario(base, 4, 4, "matmul", 0, 10),
        engine.make_scenario(base, 4, 4, "matmul", 0, 10,
                             migration_enabled=False, migrate_threshold=2),
        engine.make_scenario(base, 4, 4, "matmul", 0, 10,
                             centralized_directory=True),
        # structural changes DO split:
        engine.make_scenario(base, 4, 8, "matmul", 0, 10),
        engine.make_scenario(base, 4, 4, "matmul", 0, 10, addr_bits=13),
        engine.make_scenario(base, 4, 4, "matmul", 0, 10, mem_cycles=40),
    ]
    plan = engine.compile_plan(scs, ndev=1)
    assert len(plan.buckets) == 4
    assert plan.buckets[0].batch == 3


def test_choose_tiling():
    assert engine.choose_tiling(16, 16, 8) in ((2, 4), (4, 2))
    assert engine.choose_tiling(16, 16, 1) == (1, 1)
    assert engine.choose_tiling(16, 16, 3) == (1, 2)   # 3 doesn't divide; 2 does
    assert engine.choose_tiling(6, 6, 4) == (2, 2)
    assert engine.choose_tiling(5, 7, 8) in ((1, 7), (5, 1))
    rt, ct = engine.choose_tiling(256, 256, 8)
    assert rt * ct == 8 and 256 % rt == 0 and 256 % ct == 0


def test_cost_model_backend_choice():
    base = SimConfig(centralized_directory=False)
    big = dataclasses.replace(base, rows=256, cols=256)
    small = dataclasses.replace(base, rows=16, cols=16)
    # huge solo scenario on several devices -> spatial sharding wins
    assert engine.choose_backend(big, batch=1, ndev=4)[0] == "sharded"
    # batched work -> scenario-parallel sweep (sharded has no batch axis)
    assert engine.choose_backend(big, batch=8, ndev=4)[0] == "sweep"
    # small mesh: fixed collective cost keeps it off shard_map
    assert engine.choose_backend(small, batch=1, ndev=4)[0] == "sweep"
    # single device: sharding impossible
    assert engine.choose_backend(big, batch=1, ndev=1)[0] == "sweep"
    # cost model sanity: sharded cost falls with devices
    c2 = engine.backend_cost("sharded", 1, 65536, 2, (1, 2))
    c8 = engine.backend_cost("sharded", 1, 65536, 8, (2, 4))
    assert c8 < c2 < engine.backend_cost("sweep", 1, 65536, 1)


def test_forced_sharded_falls_back_on_one_device():
    """--sharded on 1 device (the old degeneracy) degrades to the dense
    backend with an explanatory note instead of asserting."""
    base = SimConfig(rows=4, cols=4, addr_bits=14,
                     centralized_directory=False)
    sc = engine.make_scenario(base, app="matmul", seed=0, refs_per_core=10)
    plan = engine.compile_plan([sc], ndev=1, force_backend="sharded")
    b = plan.buckets[0]
    assert b.backend == "sweep" and "fell back" in b.note
    # centralized directory is never eligible for sharding
    sc2 = engine.make_scenario(base, centralized_directory=True)
    plan2 = engine.compile_plan([sc2], ndev=4, force_backend="sharded")
    assert plan2.buckets[0].backend == "sweep"
    assert "centralized" in plan2.buckets[0].note


def test_sharded_plan_on_short_device_list_degrades():
    """A plan compiled for more devices than the process has (ndev is a
    caller-supplied compile parameter) refuses to run rather than
    silently swapping in the dense backend."""
    import jax
    base = SimConfig(rows=4, cols=4, addr_bits=14,
                     centralized_directory=False)
    sc = engine.make_scenario(base, app="matmul", seed=1, refs_per_core=10)
    ndev = 2 * len(jax.devices())
    plan = engine.compile_plan([sc], ndev=ndev, force_backend="sharded")
    assert plan.buckets[0].backend == "sharded"     # planned for 2x devices
    with pytest.raises(RuntimeError, match="planned for"):
        engine.execute_plan(plan, chunk=4)          # ...but we have half


def test_manifest_loading():
    base = SimConfig(addr_bits=14, centralized_directory=False)
    obj = {"base": {"addr_bits": 13, "mem_cycles": 40},
           "scenarios": [
               {"rows": 4, "cols": 4, "app": "matmul", "seed": 2,
                "refs_per_core": 11},
               {"rows": 8, "cols": 4, "app": "random",
                "migration_enabled": False},
           ]}
    scs = engine.load_manifest(obj, base=base)
    assert scs[0].cfg.addr_bits == 13 and scs[0].cfg.mem_cycles == 40
    assert scs[0].refs_per_core == 11 and scs[0].seed == 2
    assert scs[1].cfg.rows == 8 and not scs[1].cfg.migration_enabled
    # JSON string and bare-list forms
    assert engine.load_manifest(json.dumps(obj), base=base) == scs
    assert engine.load_manifest(obj["scenarios"], base=base)[1].app == "random"
    # compact CLI grammar
    c = engine.load_manifest("4x4:matmul:0:10; 8x8:equake:3", base=base)
    assert (c[0].cfg.rows, c[0].app, c[0].seed, c[0].refs_per_core) \
        == (4, "matmul", 0, 10)
    assert (c[1].cfg.rows, c[1].app, c[1].seed, c[1].refs_per_core) \
        == (8, "equake", 3, 200)
    with pytest.raises(ValueError):
        engine.load_manifest({"scenarios": [{"rows": 4, "bogus_key": 1}]})
    with pytest.raises(ValueError):
        engine.load_manifest("totally not a manifest")
    with pytest.raises(ValueError):
        engine.load_manifest({"scenarios": []})


def test_sharded_backend_via_planner():
    """The planner's sharded backend (8 host devices, auto tiling) matches
    the solo run bit-exactly (subprocess so the main pytest process keeps
    its single CPU device)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import sys, json
        sys.path.insert(0, "src")
        from repro.core.config import SimConfig
        from repro.core import engine
        from repro.core.sim import run
        from repro.core.workloads import app_trace

        base = SimConfig(rows=8, cols=8, addr_bits=16,
                         centralized_directory=False, migrate_threshold=2)
        sc = engine.make_scenario(base, app="mgrid", seed=2,
                                  refs_per_core=30)
        plan = engine.compile_plan([sc], force_backend="sharded")
        b = plan.buckets[0]
        got = engine.execute_plan(plan)[0]
        ref = run(sc.cfg, app_trace(sc.cfg, "mgrid", 30, 2))
        print("RESULT " + json.dumps({
            "backend": b.backend, "tiles": list(b.tiles),
            "match": got == ref}))
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                         capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            res = json.loads(line[len("RESULT "):])
            assert res["backend"] == "sharded", res
            assert res["tiles"][0] * res["tiles"][1] == 8, res
            assert res["match"], res
            return
    raise AssertionError(
        f"no result\nstdout={out.stdout}\nstderr={out.stderr[-2000:]}")


def test_composed_backend_mixed_shape_plan_bit_exact():
    """Acceptance check: a mixed-shape plan forced through the composed
    backend (8 host devices, scenario x row x col grids) produces
    per-scenario stats bit-identical to sequential solo runs, in order."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys, json, dataclasses
        sys.path.insert(0, "src")
        from repro.core.config import SimConfig
        from repro.core import engine
        from repro.core.sim import run
        from repro.core.workloads import app_trace

        base = SimConfig(addr_bits=16, centralized_directory=False)
        scs = [
            engine.make_scenario(base, 8, 8, "matmul", 0, 20),
            engine.make_scenario(base, 4, 4, "equake", 1, 15),
            engine.make_scenario(base, 8, 8, "mgrid", 2, 20,
                                 migration_enabled=False),
            engine.make_scenario(base, 4, 4, "matmul", 3, 15,
                                 migrate_threshold=1),
            engine.make_scenario(base, 8, 8, "equake", 4, 20),
        ]
        plan = engine.compile_plan(scs, force_backend="composed")
        got = engine.execute_plan(plan, chunk=4, sharded_chunk=64)
        ref = [run(dataclasses.replace(sc.cfg, dir_layout="home"),
                   app_trace(sc.cfg, sc.app, sc.refs_per_core, sc.seed),
                   chunk=4)
               for sc in scs]
        print("RESULT " + json.dumps({
            "backends": [b.backend for b in plan.buckets],
            "grids": [list(b.grid) for b in plan.buckets],
            "match": got == ref}))
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                         capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            res = json.loads(line[len("RESULT "):])
            assert res["backends"] == ["composed", "composed"], res
            for g in res["grids"]:
                assert g[0] >= 1 and g[1] * g[2] > 1, res
            assert res["match"], res
            return
    raise AssertionError(
        f"no result\nstdout={out.stdout}\nstderr={out.stderr[-2000:]}")


def test_plan_cli_smoke():
    """`--plan` end to end: compact manifest, two mesh shapes, JSON out."""
    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.simulate",
         "--plan", "4x4:matmul:0:10;6x6:equake:1:8",
         "--max-cycles", "50000", "--chunk", "4"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    payload = json.loads(out.stdout)
    assert payload["plan"]["n_buckets"] == 2
    assert payload["n_scenarios"] == 2
    assert all(s["finished"] for s in payload["scenarios"])


def test_sharded_flag_deprecation_warning():
    """`--sharded` still works but is a deprecated alias for
    `--backend sharded`: it must emit a DeprecationWarning (and a stderr
    note for shell users) while producing the same run."""
    out = subprocess.run(
        [sys.executable, "-W", "error::DeprecationWarning",
         "-m", "repro.launch.simulate",
         "--rows", "4", "--cols", "4", "--refs", "10", "--sharded"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0          # -W error promotes it to a crash
    assert "--sharded is deprecated" in out.stderr, out.stderr[-2000:]

    out = subprocess.run(
        [sys.executable, "-m", "repro.launch.simulate",
         "--rows", "4", "--cols", "4", "--refs", "10", "--sharded"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src", "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    assert "use --backend sharded" in out.stderr
    assert json.loads(out.stdout)["finished"]
