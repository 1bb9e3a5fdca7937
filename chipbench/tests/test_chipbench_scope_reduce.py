"""The scoped reduction: each op's device scope, per-device time per
scope, idle time per host span (the program's spans beside the
benchmark's), and every key of the plain reduction unchanged."""
import gzip
import json
from pathlib import Path

import pytest

from chipbench import scope_reduce as sr
from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Event
from chipbench.tests.test_chipbench_trace_reduce import ev, small_trace

RECORDED = Path(__file__).parent / "data"


@pytest.mark.parametrize("path, want", [
    ("jit(_run_jit)/driver/while/body/closed_call/phase1a/mul", "phase1a"),
    ("jit(_run_jit)/driver/while/body/vmap(phase2)/jit(sort)/sort",
     "phase2"),
    ("jit(step_tile)/shard_map/driver/while/body/closed_call/halo/ppermute",
     "halo"),
    ("jit(_run_jit)/driver/while/body/jvp(vmap(phase3))/add", "phase3"),
    ("jit(_run_jit)/driver/while/cond/lt", "driver"),
    ("jit(f)/phase1b_extra/add", None),
    ("", None),
    (None, None),
])
def test_scope_is_the_innermost_known_name(path, want):
    assert sr.scope_of(path) == want


def test_hlo_scopes_take_each_instruction_path():
    text = """HloModule jit_f, is_scheduled=true
%fused_computation.3 (p: s32[8]) -> s32[8] {
  %p = s32[8]{0} parameter(0)
  ROOT %or.1 = s32[8]{0} or(%p, %p), metadata={op_name="jit(f)/driver/vmap(phase1b)/or"}
}
ENTRY %main.5 (x: s32[8]) -> s32[8] {
  %x = s32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.3 = s32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(f)/driver/vmap(phase1b)/or" source_file="x.py"}
  %copy.4 = s32[8]{0} copy(%fusion.3), metadata={op_name="jit(f)/driver/copy"}
  %fusion.6 = s32[8]{0} fusion(%copy.4), kind=kCustom, calls=%fused_computation.3
  %copy-start.7 = (s32[8]{0}, s32[8]{0}, u32[]) copy-start(%fusion.6)
  %copy-done.8 = s32[8]{0} copy-done(%copy-start.7)
  ROOT %add.9 = s32[8]{0} add(%x, %x)
}
"""
    # own op_name first; a fused op without one takes what its fused
    # computation holds, an inserted copy what its operand has; the
    # parameter and an add outside every scope have none
    assert sr.hlo_scopes(text) == {
        "or.1": "phase1b", "fusion.3": "phase1b", "copy.4": "driver",
        "fusion.6": "phase1b", "copy-start.7": "phase1b",
        "copy-done.8": "phase1b"}


def scoped_trace():
    """``small_trace()`` with scopes on device A's ops and program spans
    inside the benchmark's: a readback in [60, 80) inside the benchmark's
    readback [55, 100), trace synthesis in [0, 5) inside the slice
    [0, 55)."""
    scopes = {("A", "fusion.1"): "phase1a", ("A", "fusion.2"): "driver",
              ("A", "collective-permute-done"): "halo"}
    pairs = [(e, scopes.get((e.where, e.name))) for e in small_trace()]
    pairs += [(ev("host", "python", "repro.readback", 60, 20), None),
              (ev("host", "python", "repro.trace_synthesis", 0, 5), None),
              (ev("device", "A", "while.4", 10, 20), "driver")]
    return pairs


def test_scopes_per_device():
    red = sr.reduce_scoped(scoped_trace())
    a, b = red["devices"]["A"], red["devices"]["B"]
    # the while container is left out, as in the op totals
    assert a["scopes"] == pytest.approx(
        {"phase1a": 15e-9, "driver": 10e-9, "halo": 10e-9})
    assert b["scopes"] == pytest.approx({sr.NONE: 30e-9})
    assert sum(a["scopes"].values()) == pytest.approx(sum(a["ops"].values()))


def test_idle_time_goes_to_the_innermost_span():
    red = sr.reduce_scoped(scoped_trace())
    # idle: [0,10) = [0,5) synthesis + [5,10) slice; [50,60) = [50,55)
    # slice + [55,60) benchmark readback; [70,100) = [70,80) program
    # readback + [80,100) benchmark readback
    assert red["idle_by_span"] == pytest.approx({
        "repro.trace_synthesis": 5e-9, "chipbench.slice": 10e-9,
        "chipbench.readback": 25e-9, "repro.readback": 10e-9,
        "chipbench.window": 0.0})
    assert sum(red["idle_by_span"].values()) == pytest.approx(
        red["window_s"] - 50e-9)


def test_gap_labels_may_be_program_spans():
    pairs = scoped_trace() + [(ev("host", "python", "repro.host_monitor",
                                  70, 30), None)]
    red = sr.reduce_scoped(pairs)
    assert red["idle_gaps"][0][0] == "repro.host_monitor"   # [70, 100)
    assert red["idle_by_span"]["repro.host_monitor"] == pytest.approx(20e-9)


def test_unscoped_events_read_like_the_plain_reduction():
    """On events without scopes or program spans the plain reduction's
    keys keep their values, and all op time counts as unscoped."""
    events = small_trace()
    old = tr.reduce_events(events)
    new = sr.reduce_scoped([(e, None) for e in events])
    for k, v in old.items():
        if k != "devices":
            assert new[k] == v
    for name, dev in old["devices"].items():
        got = dict(new["devices"][name])
        assert set(got.pop("scopes")) <= {sr.NONE}
        assert got == dev


@pytest.mark.parametrize("name", sorted(
    p.name for p in RECORDED.glob("*.json.gz")))
def test_recorded_trace_keeps_every_key(name):
    """A trace recorded on a TPU v5e (before the program had scopes):
    every key of the plain reduction is unchanged, and every op is
    unscoped."""
    with gzip.open(RECORDED / name, "rt") as f:
        events = [Event(*row) for row in json.load(f)]
    old = tr.reduce_events(events)
    new = sr.reduce_scoped([(e, None) for e in events])
    for k, v in old.items():
        if k != "devices":
            assert new[k] == v
    for dev_name, dev in old["devices"].items():
        got = dict(new["devices"][dev_name])
        scopes = got.pop("scopes")
        assert got == dev
        assert sum(scopes.values()) == pytest.approx(sum(dev["ops"].values()))
    # idle time per span adds up to the window less the busy union
    lo, hi = sr._window(events)
    busy = tr.clip(tr.union((e.start_ns, e.end_ns) for e in events
                            if e.kind != "host"), lo, hi)
    assert sum(new["idle_by_span"].values()) == pytest.approx(
        new["window_s"] - sum(e - s for s, e in busy) * 1e-9)


def test_a_recorded_cpu_trace_reads_its_scopes(tmp_path):
    """A profile of a small jitted function with two scopes and a
    program span, on the CPU: each op is found in the optimized HLO the
    trace holds, and the command line reduces the trace."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("driver"):
            with jax.named_scope("phase2"):
                y = jnp.sort(x * 3 + 1, axis=0)
            return (y * x).sum()

    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation("repro.readback"):
            f(x).block_until_ready()
    xplane = tr.find_xplane(str(tmp_path))
    programs = sr.module_op_scopes(Path(xplane).read_bytes())
    (prog,) = [p for p in programs if p.startswith("jit_f(")]
    assert set(programs[prog].values()) == {"phase2", "driver"}
    pairs = sr.load_scoped(xplane)
    assert any(e.kind == "host" and e.name == "repro.readback"
               for e, _ in pairs)
    assert {s for e, s in pairs if e.kind == "device"} >= {"phase2"}
    assert sr.main([str(tmp_path)]) == 0
