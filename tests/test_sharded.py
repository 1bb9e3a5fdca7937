"""Sharded NoC sim == single-device (run in a subprocess with 8 host
devices so the main pytest process keeps its single CPU device)."""
import json
import subprocess
import sys
import textwrap

import pytest

SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys, json
    sys.path.insert(0, "src")
    import jax
    from repro.core.config import SimConfig
    from repro.core.workloads import app_trace
    from repro.core.sim import run
    from repro.core.sharded import ShardedSim

    cfg = SimConfig(rows=8, cols=8, addr_bits=16,
                    centralized_directory=False, dir_layout="home",
                    migrate_threshold=2)
    tr = app_trace(cfg, "mgrid", 30, seed=2)
    ref = run(cfg, tr)
    mesh = jax.make_mesh(%s)
    sh = ShardedSim(cfg, tr, mesh, row_axes=%s, col_axes=("model",))
    got = sh.run(chunk=64)
    print("RESULT " + json.dumps({"match": ref == got,
                                  "cycles": [ref["cycles"], got["cycles"]]}))
""")


def run_case(mesh_expr, row_axes) -> dict:
    code = SCRIPT % (mesh_expr, row_axes)
    out = subprocess.run([sys.executable, "-c", code], cwd=".",
                         capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(f"no result\nstdout={out.stdout}\nstderr={out.stderr[-2000:]}")


def test_sharded_single_pod():
    res = run_case('(2, 4), ("data", "model")', '("data",)')
    assert res["match"], res


def test_sharded_multi_pod():
    res = run_case('(2, 2, 2), ("pod", "data", "model")', '("pod", "data")')
    assert res["match"], res


def test_sharded_clamps_max_cycles_to_dense_backend():
    """An unfinished capped run stops at exactly max_cycles even when the
    cap is not a multiple of the host chunk (the tail chunk is clamped),
    so sharded stats match the dense backend bit-for-bit.  A 1x1 mesh on
    the lone CPU device suffices — the clamp is host-loop logic."""
    import jax
    import numpy as np
    from repro.core.config import SimConfig
    from repro.core.sharded import ShardedSim
    from repro.core.sim import run
    from repro.core.workloads import app_trace

    cfg = SimConfig(rows=4, cols=4, addr_bits=14,
                    centralized_directory=False, dir_layout="home")
    tr = app_trace(cfg, "mgrid", 25, seed=1)
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    got = ShardedSim(cfg, tr, mesh).run(max_cycles=100, chunk=64)
    ref = run(cfg, tr, max_cycles=100)
    assert got["cycles"] == 100 and got["finished"] == 0
    assert got == ref, {k: (ref.get(k), got.get(k)) for k in ref
                        if ref.get(k) != got.get(k)}
