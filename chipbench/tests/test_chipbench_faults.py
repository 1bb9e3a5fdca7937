"""A whole run, the look for a chip skipped, on tiny meshes on the CPU:
sound runs come out correct, and runs with the timed path broken
underneath come out not correct.

The faults a one-chip cell can have: a step that returns its state
unchanged, half of a batch left out (its lanes filled with the other
half's results), and an answer altered where it is produced.  The
exchange between chips is in ``test_chipbench_faults_sharded.py``.
"""
import dataclasses
import time

import pytest

from chipbench import harness
from chipbench.reference import run_reference

TINY = dict(rows=4, cols=4, addr_bits=12)


def inline_reference(sim, answers):
    return [run_reference(sim, a["source"], a["seed"], a["refs"],
                          a["max_cycles"]) for a in answers]


def tiny_cell(config, traffic):
    c = harness.load_cell(None, config, traffic)
    return dataclasses.replace(
        c, config=dict(c.config, sim=dict(c.config["sim"], **TINY)))


def run_cell(cell, seed, seconds=0.3, trace=False):
    t0 = time.time()
    record = harness.device_stage(cell, seed, seconds, trace, allow_cpu=True)
    return harness.host_stage(cell, record, seed, trace, t0,
                              reference=inline_reference)


ADVANCE = ("tiny8-dist-packed", "equake-r20-tiny")
BATCHES = ("tiny8-sweep-packed", "patterns4-b8-r20-tiny")


@pytest.mark.parametrize("pair", [ADVANCE, BATCHES], ids=["advance",
                                                           "batches"])
def test_sound_run_is_correct(pair):
    out = run_cell(tiny_cell(*pair), seed=2**31 + 9)
    assert out["correct"], out["checks"]
    assert out["checks"]["stat_mismatches"] == {"value": 0, "limit": 0}
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] >= 1


def test_traced_run_reads_its_layer_metrics():
    out = run_cell(tiny_cell(*BATCHES), seed=4, trace=True)
    assert out["correct"]
    assert "lane_waste_share.sweep" in out["metrics"]
    assert 0 <= out["metrics"]["device_idle_share.sweep"]["value"] < 1
    assert out["device"]["busy_s"] > 0
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _stuck(orig):
    """``_run_jit`` that returns its state unchanged: the cycle cap is
    the state's own clock."""
    import jax.numpy as jnp

    def run_jit(s, cfg, max_cycles, chunk):
        clock = jnp.min(s.cycle).astype(jnp.int32)
        return orig(s, cfg, clock, chunk)
    return run_jit


@pytest.mark.parametrize("pair", [ADVANCE, BATCHES], ids=["advance",
                                                           "batches"])
def test_step_returning_its_state_unchanged_is_caught(pair, monkeypatch):
    from repro.core import sim, sweep
    cell = tiny_cell(*pair)
    warm = {"done": False}
    orig = sim._run_jit

    def after_warm_up(s, cfg, max_cycles, chunk):
        # set-up runs as usual; the window's steps are stuck
        if not warm["done"]:
            warm["done"] = True
            return orig(s, cfg, max_cycles, chunk)
        return _stuck(orig)(s, cfg, max_cycles, chunk)

    monkeypatch.setattr(sim, "_run_jit", after_warm_up)
    monkeypatch.setattr(sweep, "_run_jit", after_warm_up)
    out = run_cell(cell, seed=21)
    assert not out["correct"]
    assert out["checks"]["stat_mismatches"]["value"] > 0


@pytest.mark.parametrize("pair", [ADVANCE, BATCHES], ids=["advance",
                                                           "batches"])
def test_answer_altered_where_produced_is_caught(pair, monkeypatch):
    from repro.core import sim, sweep
    orig = sim.stats_list

    def off_by_one(s, aux):
        return [dict(d, flits_delivered=d["flits_delivered"] + 1)
                for d in orig(s, aux)]

    monkeypatch.setattr(sim, "stats_list", off_by_one)
    monkeypatch.setattr(sweep, "stats_list", off_by_one)
    out = run_cell(tiny_cell(*pair), seed=22)
    assert not out["correct"]
    assert out["checks"]["max_stat_gap"]["value"] == 1


def test_half_of_the_batch_left_out_is_caught(monkeypatch):
    """The second half of every batch reports the first half's results."""
    from repro.core import engine
    orig = engine.execute_plan

    def half(plan, **kw):
        got = orig(plan, **kw)
        h = len(got) // 2
        return got[:h] + got[:h]

    monkeypatch.setattr(engine, "execute_plan", half)
    out = run_cell(tiny_cell(*BATCHES), seed=23)
    assert not out["correct"]
    assert out["checks"]["stat_mismatches"]["value"] > 0


def test_advance_checks_the_slice_its_traffic_names():
    """The answer is the state after window slice ``check_slice``, and
    the window runs at least that many slices, however short it is."""
    cell = tiny_cell(*ADVANCE)
    trf = cell.traffic
    record = harness.device_stage(cell, 24, 0.0, False, allow_cpu=True)
    (answer,) = record["answers"]
    want = (1 + trf["check_slice"]) * trf["slice_cycles"]
    assert answer["stats"]["cycles"] == answer["max_cycles"] == want
    assert record["attempted"] >= trf["check_slice"]
    assert record["window_cycles"] >= trf["check_slice"] * trf["slice_cycles"]


def test_batches_give_every_seed_the_same_work():
    """Two seeds run the same scenarios in another lane order."""
    cell = tiny_cell(*BATCHES)
    got = [harness.device_stage(cell, seed, 0.0, False, allow_cpu=True)
           for seed in (2**31 + 5, 2**31 + 6)]
    runs = [sorted((a["source"], a["seed"], a["stats"]["cycles"])
                   for a in r["answers"]) for r in got]
    assert runs[0] == runs[1]
    orders = [[(a["source"], a["seed"]) for a in r["answers"]] for r in got]
    assert orders[0] != orders[1]
