"""The simulator's names in a profiler trace (``repro.core.tracing``):
the device scopes reach the optimized HLO's ``op_name`` metadata, and
the host spans appear in a recorded trace around the work they name."""
import json
import os
import re
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import engine, sim, tracing
from repro.core.config import SimConfig
from repro.core.state import init_state
from repro.core.workloads import resolve_trace

ROOT = Path(__file__).resolve().parents[1]
_SEGMENT = re.compile(r"(?:[\w.-]+\()*([\w.-]+)\)*")


def scopes_in(hlo_text):
    """The :data:`tracing.SCOPES` named anywhere in the text's
    ``op_name`` paths (``vmap(phase2)`` counts as ``phase2``)."""
    found = set()
    for path in re.findall(r'op_name="([^"]+)"', hlo_text):
        for seg in path.split("/"):
            m = _SEGMENT.fullmatch(seg)
            if m and m.group(1) in tracing.SCOPES:
                found.add(m.group(1))
    return found


def host_spans(logdir):
    """Names of the host events of the one trace under ``logdir``."""
    from jax.profiler import ProfileData
    (path,) = Path(logdir).rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    return {e.name for p in pd.planes if p.name.startswith("/host:")
            for line in p.lines for e in line.events}


def _cfg(**kw):
    return SimConfig(rows=8, cols=8, addr_bits=12,
                     centralized_directory=False,
                     state_dtype_policy="packed", **kw)


def test_run_loop_hlo_carries_the_phase_and_driver_scopes():
    """The optimized 8x8 packed slice program names each of the four
    phases and the driver in its instructions' ``op_name``."""
    cfg = _cfg()
    s = init_state(cfg, resolve_trace(cfg, "equake", 12, 3))
    text = sim._run_jit.lower(s, cfg, jnp.asarray(64, jnp.int32),
                              64).compile().as_text()
    assert scopes_in(text) == {"phase1a", "phase1b", "phase2", "phase3",
                               "driver"}


def test_plan_run_records_the_program_host_spans(tmp_path):
    """A profiled plan run holds the spans of planning, trace synthesis,
    state placement and readback."""
    cfg = SimConfig(rows=4, cols=4, addr_bits=10,
                    centralized_directory=False)
    scs = [engine.make_scenario(cfg, app=app, refs_per_core=6, seed=1)
           for app in ("matmul", "tornado")]
    with jax.profiler.trace(str(tmp_path)):
        plan = engine.compile_plan(scs, ndev=1, force_backend="sweep")
        got = engine.execute_plan(plan, chunk=8)
    assert all(st["finished"] == 1 for st in got)
    assert {"repro.plan", "repro.trace_synthesis", "repro.place_state",
            "repro.readback"} <= host_spans(tmp_path)


def test_spans_do_not_import_jax():
    """Trace synthesis stays JAX-free: a span opened before jax is
    imported is a plain no-op, so ``engine.expose_host_devices`` still
    runs in time."""
    code = textwrap.dedent("""
        import sys
        from repro.core.config import SimConfig
        from repro.core.workloads import resolve_trace, stacked_traces
        cfg = SimConfig(rows=4, cols=4, addr_bits=10)
        resolve_trace(cfg, "equake", 6, 2)
        stacked_traces(cfg, [("matmul", 1), ("tornado", 2, 4)])
        print("jax" in sys.modules)
    """)
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]
    assert out.stdout.strip() == "False"


SHARDED_CHILD = textwrap.dedent("""
    import json, re, sys, tempfile
    from pathlib import Path
    import jax, numpy as np
    from jax.sharding import Mesh
    from jax.profiler import ProfileData
    from repro.core.config import SimConfig
    from repro.core.sharded import ShardedSim
    from repro.core.workloads import resolve_trace
    cfg = SimConfig(rows=8, cols=8, addr_bits=12,
                    centralized_directory=False, dir_layout="home",
                    state_dtype_policy="packed")
    mesh = Mesh(np.asarray(jax.devices()[:4]).reshape(2, 2),
                ("data", "model"))
    sim = ShardedSim(cfg, resolve_trace(cfg, "equake", 12, 3), mesh)
    text = sim.build_step(8).lower(sim.state, *sim.geo).compile().as_text()
    logdir = tempfile.mkdtemp()
    with jax.profiler.trace(logdir):
        stats = sim.run(48, chunk=16)
    (path,) = Path(logdir).rglob("*.xplane.pb")
    pd = ProfileData.from_file(str(path))
    spans = sorted({e.name for p in pd.planes if p.name.startswith("/host:")
                    for line in p.lines for e in line.events
                    if e.name.startswith("repro.")})
    print(json.dumps({"paths": re.findall(r'op_name="([^"]+)"', text),
                      "spans": spans, "cycles": stats["cycles"]}))
""")


def test_sharded_tile_step_carries_every_scope_and_the_monitor_span():
    """The tile step over four CPU devices names the phases, the halo
    exchange and the driver; a profiled run records the per-chunk host
    monitor and the readback."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=str(ROOT / "src"),
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, "-c", SHARDED_CHILD], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    assert scopes_in(" ".join(f'op_name="{p}"' for p in got["paths"])) \
        == set(tracing.SCOPES)
    assert any("/halo/" in p and "ppermute" in p for p in got["paths"])
    assert got["cycles"] == 48
    assert {"repro.host_monitor", "repro.readback"} <= set(got["spans"])


def test_span_keeps_the_decorated_function():
    """A decorated entry point keeps its name, docstring and signature,
    and a span nests and passes exceptions through."""
    import inspect
    from repro.core import workloads
    assert workloads.resolve_trace.__name__ == "resolve_trace"
    assert "Trace-source dispatch" in workloads.resolve_trace.__doc__
    assert list(inspect.signature(workloads.resolve_trace).parameters) == [
        "cfg", "app", "refs_per_core", "seed"]
    with pytest.raises(KeyError):
        with tracing.span("repro.plan"), tracing.span("repro.readback"):
            raise KeyError("passes through")
