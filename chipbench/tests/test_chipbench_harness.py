"""The benchmark's definition and arithmetic, on the CPU: the keys and
limits of BENCHMARK.json, the discovery of configurations, traffic mixes,
modes and metric readers by name, the reference's agreement with the
program's inputs, the comparison and the lane-waste count."""
import dataclasses
import json
import re

import numpy as np
import pytest

from chipbench import harness
from chipbench.reference import machine, traces

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "chipbench/run.py"]
    assert BENCH["paths"] == ["chipbench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    names += CELLS + [c["name"] for c in BENCH["configs"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e and set(m["workloads"]) <= set(CELLS)
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(CELLS) // 2)
    assert len(json.dumps(BENCH)) < 64 * 1024


@pytest.mark.parametrize("cell", CELLS)
def test_cell_resolves_by_name(cell):
    """Each cell finds its configuration, traffic, mode and metric
    readers by name, reports set-up, another end-to-end metric and a
    per-layer metric, and every per-layer metric moves one it reports."""
    c = harness.load_cell(cell)
    assert c.chips in (1, 4) and c.config["chips"] == c.chips
    mode = harness.load_module("modes", c.traffic["mode"])
    assert callable(mode.run)
    e2e = [m["name"] for m in c.end_to_end]
    assert "setup_s" in e2e and len(e2e) >= 2
    assert c.per_layer
    for m in c.per_layer:
        assert m["moves"] in e2e
        assert callable(harness.load_module("metrics", m["name"]).read)
    cfg = harness.sim_config(c)
    cfg.validate()
    assert machine.Machine.from_sim(c.config["sim"]).num_nodes == \
        cfg.num_nodes


@pytest.mark.parametrize("entry", BENCH["configs"], ids=lambda e: e["name"])
def test_config_file_is_its_own(entry):
    cfg = harness.load_json(harness.ROOT / entry["file"])
    assert cfg["name"] == entry["name"]
    assert cfg["reduced"] == entry["reduced"]
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    assert all(k in cfg["sim"] for k in entry["reduced"])


def test_unknown_names_raise():
    with pytest.raises(harness.BenchError):
        harness.load_cell("no-such-cell")
    with pytest.raises(harness.BenchError):
        harness.load_module("metrics", "no_such_metric")
    with pytest.raises(harness.BenchError):
        harness.peaks("TPU v0 imaginary")
    assert harness.peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_rehearsal_pair_takes_the_metrics_of_its_kind():
    c = harness.load_cell(None, "tiny8-sweep-packed", "patterns4-b8-r20-tiny")
    assert {m["name"] for m in c.per_layer} == {
        "device_idle_share.sweep", "lane_waste_share.sweep"}
    assert "scenarios_per_s" in {m["name"] for m in c.end_to_end}


def _program_cfg(rows, addr_bits):
    from repro.core.config import SimConfig
    return SimConfig(rows=rows, cols=rows, addr_bits=addr_bits,
                     centralized_directory=False, state_dtype_policy="packed")


def _machine_of(cfg):
    sim = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    sim["cache"] = dataclasses.asdict(cfg.cache)
    sim["livelock_window"] = cfg.livelock_window_effective
    return machine.Machine.from_sim(sim)


@pytest.mark.parametrize("source", ["equake", "matmul", "transpose",
                                    "bitcomp", "tornado", "neighbor",
                                    "hotspot:frac=0.8,hot=2"])
@pytest.mark.parametrize("seed", [0, 2**31 + 7, 2 * (1000 * 2**31 + 3) + 1])
def test_reference_traces_equal_the_programs(source, seed):
    """The reference synthesizes the same trace from the same spec and
    seed as the program does (large seeds too)."""
    from repro.core.workloads import resolve_trace
    cfg = _program_cfg(6, 16)
    got = traces.trace(_machine_of(cfg), source, 12, seed)
    want = resolve_trace(cfg, source, 12, seed)
    assert got.dtype == want.dtype and np.array_equal(got, want)


def test_reference_equals_the_programs_golden_model():
    """The reference copy and the program's serial model agree on a
    small machine, to completion."""
    from repro.core.ref_serial import SerialSim
    from repro.core.workloads import resolve_trace
    from chipbench.reference import run_reference
    cfg = _program_cfg(4, 12)
    sim = json.loads(json.dumps(dataclasses.asdict(_machine_of(cfg))))
    for source in ("equake", "tornado"):
        want = SerialSim(cfg, resolve_trace(cfg, source, 8, 5)).run()
        assert want["finished"] == 1
        assert run_reference(sim, source, 5, 8, None) == want


def test_control_departs_from_the_reference():
    """The control (port-order arbitration) gives other statistics than
    the reference on a small machine, so the exact comparison fails it."""
    from chipbench.reference import run_reference
    cfg = _program_cfg(6, 16)
    sim = json.loads(json.dumps(dataclasses.asdict(_machine_of(cfg))))
    for source, seed in (("equake", 1), ("transpose", 2), ("tornado", 3)):
        ref = run_reference(sim, source, seed, 10, 400)
        ctl = run_reference(sim, source, seed, 10, 400, control=True)
        assert harness.compare([ctl], [ref])["stat_mismatches"] > 0


def test_compare_counts_each_differing_key():
    from chipbench.reference.serial import STAT_NAMES
    a = dict({k: 0 for k in STAT_NAMES}, hops=3, cycles=10, finished=1)
    assert harness.compare([a], [dict(a)]) == {"stat_mismatches": 0,
                                               "max_stat_gap": 0}
    b = dict(a, hops=5, finished=0)
    assert harness.compare([b], [a]) == {"stat_mismatches": 2,
                                         "max_stat_gap": 2}
    c = dict(a, aborted="livelock")
    assert harness.compare([c], [a])["stat_mismatches"] == 1
    assert harness.compare([a], [])["stat_mismatches"] == 1
    # a counter the program does not report at all is a mismatch
    d = dict(a)
    del d["stray"]
    assert harness.compare([d], [d])["stat_mismatches"] == 1


def test_sample_takes_one_answer_per_lane_and_the_longest():
    answers = [{"stats": {"cycles": c}, "lane": i % 3, "i": i}
               for i, c in enumerate([5, 9, 7, 3, 8, 6, 4, 2, 1])]
    s1 = harness.sample_answers(answers, 11)
    assert sorted(a["lane"] for a in s1) == [0, 1, 2]
    assert any(a["i"] == 1 for a in s1)           # the longest, 9 cycles
    assert s1 == harness.sample_answers(answers, 11)
    assert len({tuple(a["i"] for a in harness.sample_answers(answers, k))
                for k in range(20)}) > 1          # the seed draws the batch
    one = [{"stats": {"cycles": 128}}]
    assert harness.sample_answers(one, 3) == one


def test_lane_waste_arithmetic():
    read = harness.load_module("metrics", "lane_waste_share.sweep").read
    # batch 1: 4 x 10 stepped, 10+8+6+4 = 28 useful; batch 2: 2 x 5, 10
    rec = {"batch_cycles": [[10, 8, 6, 4], [5, 5]]}
    assert read({}, rec) == pytest.approx((40 - 28 + 0) / (40 + 10))
    assert read({}, {"batch_cycles": []}) is None


def test_device_readers_read_nothing_without_a_trace():
    for name in ("device_idle_share.mesh", "device_idle_share.sweep",
                 "device_ms_per_cycle.mesh", "halo_share.2x2"):
        assert harness.load_module("metrics", name).read(
            {"devices": {}, "window_s": 1.0}, {"window_cycles": 64}) is None
