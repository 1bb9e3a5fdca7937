"""The pluggable workload layer (repro.core.workloads).

Four contracts:

1. **Golden bit-identity** — every app/loop/random/model generator
   output hashes to a pinned digest, unchanged since the generators
   moved into this package.
2. **Registry consistency** — validation and dispatch share one parser,
   so every source spec ``valid_app`` accepts is resolvable (the old
   ``valid_app("loop:random")``-accepts / ``resolve_trace``-raises
   disagreement is structurally impossible now), and the grammar's
   error text is generated from the registry.
3. **Pattern properties** — each synthetic pattern's address stream
   realizes its destination pattern through the distributed-directory
   home map: permutation patterns hit exactly the permuted home,
   hotspot concentrates at least the configured fraction on the hot
   homes, the injection rate throttles non-local traffic, and padding
   with the ``-1`` exhaustion sentinel is semantically inert.
4. **Backend invariance** — a zoo slice of every pattern runs to
   completion bit-identically through solo runs and all three planner
   backends (sweep / sharded / composed; subprocess: 8 host devices).
"""
import hashlib
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from repro.core import workloads as W
from repro.core.config import SimConfig
from repro.core.workloads import (TRACE_APPS, app_trace, app_trace_loop,
                                  from_model_schedule, random_trace,
                                  resolve_trace, stacked_traces, valid_app)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _dig(a: np.ndarray) -> str:
    return hashlib.sha256(np.ascontiguousarray(a).tobytes()).hexdigest()[:16]


# ---------------------------------------------------------------------------
# 1. golden bit-identity across the trace.py -> workloads move
# ---------------------------------------------------------------------------

#: digests of the pre-refactor generators (captured at the commit that
#: introduced the workloads package, from the then-current trace.py)
GOLDEN = {
    "loop16:matmul": "2ca2ff3bb8e8f400",
    "loop:apsi": "54b1e1d70ff2c79d",
    "loop:equake": "cf6eebe5b1a4abd2",
    "loop:matmul": "b362693beb51f9d8",
    "loop:mgrid": "95ce921255a72c0a",
    "loop:wupwise": "573e8427aba1239a",
    "model": "1ceebaf709bcf8b8",
    "random": "82a6e49edcba00b3",
    "vec16:matmul": "8bdc403a12d295aa",
    "vec:apsi": "a7887f29047dd824",
    "vec:equake": "e88b1495a75f6a6a",
    "vec:matmul": "1490b6bd404e6e5b",
    "vec:mgrid": "7e3118eec85858f4",
    "vec:wupwise": "26b48a0836d5eda7",
}


def test_golden_digests_pin_the_refactor():
    cfg = SimConfig(rows=6, cols=6, centralized_directory=False)
    got = {}
    for app in sorted(TRACE_APPS):
        got[f"vec:{app}"] = _dig(app_trace(cfg, app, 64, 3))
        got[f"loop:{app}"] = _dig(app_trace_loop(cfg, app, 32, 3))
    got["random"] = _dig(random_trace(cfg, 64, 3))
    got["model"] = _dig(from_model_schedule(cfg, 1 << 16, 128, 4, 64, 3))
    cfg16 = SimConfig(rows=16, cols=16)
    got["vec16:matmul"] = _dig(app_trace(cfg16, "matmul", 40, 0))
    got["loop16:matmul"] = _dig(app_trace_loop(cfg16, "matmul", 20, 0))
    assert got == GOLDEN, {k: (got[k], GOLDEN[k])
                           for k in GOLDEN if got[k] != GOLDEN[k]}


def test_resolve_trace_dispatch_matches_direct_calls():
    """The registry dispatch path returns the exact same arrays as the
    direct generator calls (same digests as the golden table)."""
    cfg = SimConfig(rows=6, cols=6, centralized_directory=False)
    assert _dig(resolve_trace(cfg, "matmul", 64, 3)) == GOLDEN["vec:matmul"]
    assert _dig(resolve_trace(cfg, "loop:mgrid", 32, 3)) == GOLDEN["loop:mgrid"]
    assert _dig(resolve_trace(cfg, "random", 64, 3)) == GOLDEN["random"]


# ---------------------------------------------------------------------------
# 2. registry consistency: validation == dispatch
# ---------------------------------------------------------------------------

def _accepted_specs():
    """Every spelling valid_app accepts that the suite exercises: all
    bare registry names, every loop:<app>, and parameterized patterns."""
    specs = list(W.gen_names())
    specs += [f"loop:{a}" for a in TRACE_APPS]
    specs += ["loop:app=equake", "transpose:rate=0.5", "transpose:0.5",
              "bitcomp:rate=1.0", "hotspot:frac=0.8,hot=2",
              "hotspot:0.9", "tornado:rate=0.25", "neighbor:rate=0.1"]
    return specs


def test_every_accepted_name_is_resolvable():
    cfg = SimConfig(rows=4, cols=4, addr_bits=14)
    for spec in _accepted_specs():
        assert valid_app(spec), spec
        tr = resolve_trace(cfg, spec, 8, 0)
        assert tr.shape == (16, 8) and tr.dtype == np.int32, spec
        assert (tr >= 0).all() and (tr < (1 << cfg.addr_bits)).all(), spec


def test_rejected_names_fail_both_ways():
    """valid_app and resolve_trace agree on rejection too — including
    the historical loop:random disagreement (valid_app said yes,
    resolve_trace raised)."""
    cfg = SimConfig(rows=4, cols=4)
    for spec in ("loop:random", "bogus", "hotspot:bad=1", "loop:loop",
                 "transpose:rate=2.0", "transpose:rate=-1",
                 "hotspot:frac=1.5", "hotspot:hot=0", "transpose:0.5,1",
                 "matmul:rate=1.0"):
        assert not valid_app(spec), spec
        with pytest.raises(ValueError):
            resolve_trace(cfg, spec, 8, 0)


def test_scenario_validate_uses_the_registry():
    """engine.Scenario.validate accepts exactly what the registry
    resolves and its error text carries the registry roll-call."""
    from repro.core import engine
    base = SimConfig()
    for spec in _accepted_specs():
        engine.make_scenario(base, 4, 4, app=spec).validate()
    with pytest.raises(ValueError, match="known sources"):
        engine.make_scenario(base, 4, 4, app="bogus").validate()
    with pytest.raises(ValueError, match="random"):
        engine.make_scenario(base, 4, 4, app="loop:random").validate()


def test_grammar_errors_are_specific():
    with pytest.raises(ValueError, match="known sources"):
        W.parse_source("bogus")
    with pytest.raises(ValueError, match="unknown parameter"):
        W.parse_source("hotspot:heat=3")
    with pytest.raises(ValueError, match="duplicate"):
        W.parse_source("hotspot:frac=0.5,frac=0.6")
    with pytest.raises(ValueError, match="positional"):
        W.parse_source("transpose:0.5,0.9")
    with pytest.raises(ValueError, match="cannot parse"):
        W.parse_source("hotspot:hot=two")
    # canonical spec round-trips through the parser
    gen, params = W.parse_source("hotspot:frac=0.8,hot=2")
    assert gen.spec(**params) in ("hotspot:frac=0.8,hot=2",)
    assert W.parse_source(gen.spec(**params))[1] == params


def test_compact_manifest_grammar_carries_source_specs():
    from repro.core import engine
    scs = engine.load_manifest(
        "4x4:hotspot:frac=0.8,hot=2:1:30;8x8:transpose:rate=0.5,"
        "16x16:loop:matmul:0:20")
    assert [(s.cfg.rows, s.app, s.seed, s.refs_per_core) for s in scs] == [
        (4, "hotspot:frac=0.8,hot=2", 1, 30),
        (8, "transpose:rate=0.5", 0, 200),
        (16, "loop:matmul", 0, 20)]
    with pytest.raises(ValueError, match="known sources"):
        engine.load_manifest("4x4:bogus:0")


# ---------------------------------------------------------------------------
# 3. pattern destination-distribution properties
# ---------------------------------------------------------------------------

def _homes(cfg: SimConfig, tr: np.ndarray) -> np.ndarray:
    """Distributed-directory home node of every address (cache.dir_home_v
    semantics: tag % N with tag = addr >> l2_shift)."""
    return (tr >> cfg.cache.l2_shift) % cfg.num_nodes


PERM_PATTERNS = ("transpose", "bitcomp", "tornado", "neighbor")


@pytest.mark.parametrize("name", PERM_PATTERNS)
def test_permutation_patterns_hit_the_permuted_home(name):
    for rows, cols in ((4, 4), (4, 6)):   # square + non-square
        cfg = SimConfig(rows=rows, cols=cols, centralized_directory=False)
        tr = resolve_trace(cfg, name, 40, 0)
        want = W.dst_map(cfg, name)
        assert (_homes(cfg, tr) == want[:, None]).all(), (name, rows, cols)
        # destination maps are permutations of the node set
        assert sorted(want) == list(range(cfg.num_nodes)), name


def test_hotspot_concentrates_on_hot_homes():
    cfg = SimConfig(rows=6, cols=6, centralized_directory=False)
    n = cfg.num_nodes
    frac, hot = 0.7, 2
    tr = resolve_trace(cfg, f"hotspot:frac={frac},hot={hot}", 600, 0)
    homes = _homes(cfg, tr)
    hot_ids = (np.arange(hot) * n) // hot
    hot_share = np.isin(homes, hot_ids).mean()
    # >= the configured fraction (uniform leakage only adds hot hits)
    assert hot_share >= frac, hot_share
    # the uniform remainder still spreads over most of the mesh
    assert len(np.unique(homes)) > n // 2


def test_injection_rate_throttles_remote_traffic():
    cfg = SimConfig(rows=6, cols=6, centralized_directory=False)
    n = cfg.num_nodes
    own = np.arange(n)[:, None]
    for rate in (0.0, 0.3, 1.0):
        tr = resolve_trace(cfg, f"bitcomp:rate={rate}", 1500, 0)
        homes = _homes(cfg, tr)
        remote = (homes != own).mean()   # bitcomp never maps to self
        assert abs(remote - rate) < 0.05, (rate, remote)


def test_patterns_reject_undersized_directory():
    """dir_entries < num_nodes cannot realize one home per destination;
    the generator must refuse instead of silently wrapping the pattern
    (tag % entries would scramble both the homes and the rate
    throttle)."""
    cfg = SimConfig(rows=32, cols=32, addr_bits=14,
                    centralized_directory=False)
    assert cfg.dir_entries < cfg.num_nodes
    for spec in ("transpose", "hotspot:frac=0.5"):
        with pytest.raises(ValueError, match="dir_entries"):
            resolve_trace(cfg, spec, 4, 0)
    # apps are region-based, not home-targeted: they still work
    assert resolve_trace(cfg, "matmul", 4, 0).shape == (1024, 4)


def test_patterns_are_deterministic_and_seed_sensitive():
    cfg = SimConfig(rows=4, cols=4, centralized_directory=False)
    a = resolve_trace(cfg, "hotspot:frac=0.5", 32, 7)
    b = resolve_trace(cfg, "hotspot:frac=0.5", 32, 7)
    c = resolve_trace(cfg, "hotspot:frac=0.5", 32, 8)
    assert (a == b).all()
    assert (a != c).any()


def test_exhaustion_sentinel_padding_is_inert():
    """A pattern trace padded with -1 (stacked_traces) retires exactly
    its own references and matches the unpadded solo run bit-for-bit."""
    from repro.core.sim import run
    cfg = SimConfig(rows=4, cols=4, addr_bits=14,
                    centralized_directory=False)
    stack = stacked_traces(cfg, [("transpose", 0, 6), ("tornado", 1, 10)])
    assert stack.shape == (2, 16, 10)
    assert (stack[0, :, 6:] == -1).all()       # sentinel padding
    assert (stack[0, :, :6] >= 0).all()        # generators never emit -1
    padded = run(cfg, stack[0], chunk=4)
    solo = run(cfg, resolve_trace(cfg, "transpose", 6, 0), chunk=4)
    assert padded == solo


# ---------------------------------------------------------------------------
# 4. backend invariance on a zoo slice (subprocess: 8 host devices)
# ---------------------------------------------------------------------------

def test_patterns_bit_exact_across_backends():
    """Every synthetic pattern of the patterns-tiny zoo slice completes
    and is bit-identical through solo run / forced sweep / forced
    composed / forced sharded on an 8-device host mesh."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        os.environ["JAX_PLATFORMS"] = "cpu"
        import sys, json
        sys.path.insert(0, "src")
        from repro.core import engine
        from repro.core.sim import run
        from repro.core.workloads import resolve_trace
        from repro.core.zoo import expand_zoo

        scs = expand_zoo("patterns-tiny:refs=8,seeds=0")
        solo = []
        for sc in scs:
            tr = resolve_trace(sc.cfg, sc.app, sc.refs_per_core, sc.seed)
            solo.append(run(sc.cfg, tr, chunk=4))
        sweep = engine.plan_and_run(scs, chunk=4, force_backend="sweep")
        comp = engine.plan_and_run(scs, chunk=4, force_backend="composed")
        # sharded takes batch-1 buckets only: run each pattern solo
        shard = [engine.plan_and_run([sc], chunk=4,
                                     force_backend="sharded")[0]
                 for sc in scs]
        print("RESULT " + json.dumps({
            "finished": all(s["finished"] for s in solo),
            "sweep_match": sweep == solo,
            "composed_match": comp == solo,
            "sharded_match": shard == solo,
            "apps": [sc.app for sc in scs]}))
    """)
    out = subprocess.run([sys.executable, "-c", code], cwd=REPO_ROOT,
                         capture_output=True, text=True, timeout=900)
    for line in out.stdout.splitlines():
        if line.startswith("RESULT "):
            res = json.loads(line[len("RESULT "):])
            assert len(res["apps"]) == 5 and res["finished"], res
            assert res["sweep_match"], res
            assert res["composed_match"], res
            assert res["sharded_match"], res
            return
    raise AssertionError(
        f"no result\nstdout={out.stdout}\nstderr={out.stderr[-2000:]}")
