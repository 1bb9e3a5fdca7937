"""Benchmark-telemetry subsystem: schema round-trip, gate semantics,
the shared entry contract, the public ``repro.core`` surface, and a
subprocess smoke of ``scripts/bench_gate.py`` against fixture baselines.
"""
import argparse
import json
import os
import subprocess
import sys

import pytest

from repro.bench import (BenchReport, Benchmark, Metric, compare_reports,
                         gate_passes, render_findings, render_trend)
from repro.bench.contract import parse_bench_args

REPO_ROOT = os.path.join(os.path.dirname(__file__), "..")


# ---------------------------------------------------------------- schema

def make_report(**values):
    """A small report with one gated lower-better metric per (name, value)."""
    return BenchReport("toy", meta={"smoke": True}, metrics=[
        Metric(name, v, unit="cycles", direction="lower", slack=0.1)
        for name, v in values.items()])


def test_metric_roundtrip_and_validation():
    m = Metric("a.b", 3.5, unit="s", direction="higher", slack=0.25,
               gate=False, tags={"mesh": "8x8"})
    assert Metric.from_dict(m.to_dict()) == m
    assert Metric.from_dict(json.loads(json.dumps(m.to_dict()))) == m
    # bools normalize to ints so JSON round-trips exactly
    assert Metric("f", True).value == 1
    with pytest.raises(ValueError):
        Metric("bad", 1.0, direction="sideways")
    with pytest.raises(ValueError):
        Metric("bad", float("nan"))
    with pytest.raises(ValueError):
        Metric("bad", 1.0, slack=-0.1)
    with pytest.raises(ValueError):
        Metric("", 1.0)


def test_report_roundtrip(tmp_path):
    rep = BenchReport("toy", meta={"smoke": True, "params": {"rows": 8}},
                      metrics=[Metric("x", 1), Metric("y", 2.5, unit="s")],
                      raw={"free": ["form", 1]})
    assert BenchReport.from_json(rep.to_json()) == rep
    p = tmp_path / "BENCH_toy.json"
    rep.write(str(p))
    assert BenchReport.read(str(p)) == rep
    assert rep.names() == ("x", "y")
    assert rep.metric("x").value == 1
    assert rep.metric("nope") is None
    assert "BENCH toy" in rep.render()


def test_report_rejects_duplicates_and_future_schema():
    with pytest.raises(ValueError):
        BenchReport("toy", metrics=[Metric("x", 1), Metric("x", 2)])
    rep = BenchReport("toy")
    rep.add("x", 1)
    with pytest.raises(ValueError):
        rep.add("x", 2)
    d = rep.to_dict()
    d["schema_version"] = 99
    with pytest.raises(ValueError):
        BenchReport.from_dict(d)


# ------------------------------------------------------------------ gate

def test_gate_within_slack_passes():
    base, fresh = make_report(m=100), make_report(m=105)   # +5% < 10% slack
    f = compare_reports(base, fresh)
    assert [x.kind for x in f] == ["ok"] and gate_passes(f)


def test_gate_regression_beyond_slack_fails():
    base, fresh = make_report(m=100), make_report(m=120)   # +20% > 10%
    (f,) = compare_reports(base, fresh)
    assert f.kind == "regression" and f.fails
    assert not gate_passes([f])
    assert "FAIL" in render_findings("toy", [f])


def test_gate_improvement_direction_is_not_a_failure():
    base, fresh = make_report(m=100), make_report(m=50)    # lower = better
    (f,) = compare_reports(base, fresh)
    assert f.kind == "improvement" and not f.fails
    # and the same drift on a higher-is-better metric fails
    up = BenchReport("toy", metrics=[
        Metric("m", 100, direction="higher", slack=0.1)])
    down = BenchReport("toy", metrics=[
        Metric("m", 50, direction="higher", slack=0.1)])
    (f2,) = compare_reports(up, down)
    assert f2.kind == "regression" and f2.fails


def test_gate_vanished_and_new_metrics():
    base = make_report(kept=1, gone=2)
    fresh = BenchReport("toy", metrics=[
        Metric("kept", 1, direction="lower", slack=0.1),
        Metric("brand_new", 7)])
    f = {x.name: x for x in compare_reports(base, fresh)}
    assert f["gone"].kind == "vanished" and f["gone"].fails
    assert f["brand_new"].kind == "new" and not f["brand_new"].fails
    assert f["kept"].kind == "ok"
    # an *ungated* baseline metric may vanish freely
    base2 = BenchReport("toy", metrics=[Metric("info", 1, gate=False)])
    (f2,) = compare_reports(base2, BenchReport("toy"))
    assert f2.kind == "vanished" and not f2.fails


def test_gate_zero_baseline_uses_absolute_slack():
    base = BenchReport("toy", metrics=[
        Metric("drops", 0, direction="lower", slack=0.0)])
    ok = compare_reports(base, BenchReport("toy", metrics=[
        Metric("drops", 0)]))
    assert gate_passes(ok)
    bad = compare_reports(base, BenchReport("toy", metrics=[
        Metric("drops", 3)]))
    assert not gate_passes(bad)
    # slack interpreted as absolute units when baseline == 0
    base5 = BenchReport("toy", metrics=[
        Metric("drops", 0, direction="lower", slack=5.0)])
    assert gate_passes(compare_reports(base5, BenchReport("toy", metrics=[
        Metric("drops", 3)])))


def test_gate_ungated_metrics_never_fail():
    base = BenchReport("toy", metrics=[
        Metric("wall_s", 1.0, direction="lower", slack=0.0, gate=False)])
    fresh = BenchReport("toy", metrics=[Metric("wall_s", 50.0)])
    (f,) = compare_reports(base, fresh)
    assert f.kind == "info" and not f.fails


def test_gate_slack_scale_loosens():
    base, fresh = make_report(m=100), make_report(m=118)   # +18% > 10%
    assert not gate_passes(compare_reports(base, fresh))
    assert gate_passes(compare_reports(base, fresh, slack_scale=2.0))


def test_gate_area_mismatch_raises():
    with pytest.raises(ValueError):
        compare_reports(make_report(m=1), BenchReport("other"))


def test_trend_render():
    hist = [(lbl, make_report(m=v))
            for lbl, v in (("aaa111", 100), ("bbb222", 90), ("fresh", 80))]
    txt = render_trend(hist)
    assert "aaa111" in txt and "fresh" in txt and "80" in txt
    assert render_trend([]) == "(no history)"


# -------------------------------------------------------------- contract

def _toy_bench():
    def add_args(ap):
        ap.add_argument("--rows", type=int, default=16)
        ap.add_argument("--refs", type=int, default=100)

    def run(args):
        return BenchReport("toy", metrics=[Metric("rows", args.rows)])

    return Benchmark(area="toy", title="toy", add_args=add_args, run=run,
                     smoke={"rows": 4})


def test_contract_smoke_swaps_defaults_but_explicit_flags_win():
    b = _toy_bench()
    assert parse_bench_args(b, []).rows == 16
    assert parse_bench_args(b, ["--smoke"]).rows == 4
    assert parse_bench_args(b, ["--smoke"]).refs == 100   # untouched default
    assert parse_bench_args(b, ["--smoke", "--rows", "9"]).rows == 9


def test_contract_main_writes_out(tmp_path, capsys):
    from repro.bench import bench_main
    out = tmp_path / "BENCH_toy.json"
    rep = bench_main(_toy_bench(), ["--smoke", "--out", str(out)])
    assert rep.meta["smoke"] is True
    assert BenchReport.read(str(out)).metric("rows").value == 4
    assert "BENCH toy" in capsys.readouterr().out


def test_harness_registry_matches_module_areas():
    """benchmarks/run.py --list loads every registered module and asserts
    its BENCH.area matches the registry key (subprocess: several modules
    must manage XLA_FLAGS before jax loads)."""
    out = subprocess.run(
        [sys.executable, os.path.join("benchmarks", "run.py"), "--list"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    for area in ("trace", "sweep", "plan", "fig6", "table3", "table4"):
        assert area in out.stdout, out.stdout


# ----------------------------------------------------- bench_gate script

GATE = os.path.join("scripts", "bench_gate.py")


def run_gate(*argv):
    return subprocess.run(
        [sys.executable, GATE, *argv], cwd=REPO_ROOT, capture_output=True,
        text=True, timeout=300,
        env={**os.environ, "PYTHONPATH": "src"})


def fixture_dirs(tmp_path, base_value, fresh_value):
    basedir, freshdir = tmp_path / "base", tmp_path / "fresh"
    basedir.mkdir(), freshdir.mkdir()
    make_report(m=base_value).write(str(basedir / "BENCH_toy.json"))
    make_report(m=fresh_value).write(str(freshdir / "BENCH_toy.json"))
    return str(basedir), str(freshdir)


def test_bench_gate_passes_within_slack(tmp_path):
    basedir, freshdir = fixture_dirs(tmp_path, 100, 104)
    out = run_gate("--fresh-dir", freshdir, "--baseline-dir", basedir,
                   "--areas", "toy", "--no-trend")
    assert out.returncode == 0, out.stdout + out.stderr
    assert "bench gate: OK" in out.stdout


def test_bench_gate_fails_on_corrupted_baseline(tmp_path):
    # fresh deterministic value 104 vs a baseline corrupted well below
    # slack: exactly the acceptance drill for the committed BENCH files
    basedir, freshdir = fixture_dirs(tmp_path, 50, 104)
    out = run_gate("--fresh-dir", freshdir, "--baseline-dir", basedir,
                   "--areas", "toy", "--no-trend")
    assert out.returncode == 1, out.stdout + out.stderr
    assert "regression" in out.stdout


def test_bench_gate_missing_baseline_fails_then_update_seeds(tmp_path):
    basedir, freshdir = fixture_dirs(tmp_path, 100, 100)
    os.remove(os.path.join(basedir, "BENCH_toy.json"))
    out = run_gate("--fresh-dir", freshdir, "--baseline-dir", basedir,
                   "--areas", "toy", "--no-trend")
    assert out.returncode == 1
    assert "missing baseline" in out.stderr
    out = run_gate("--fresh-dir", freshdir, "--baseline-dir", basedir,
                   "--areas", "toy", "--no-trend", "--update")
    assert out.returncode == 0, out.stdout + out.stderr
    assert os.path.exists(os.path.join(basedir, "BENCH_toy.json"))


def test_bench_gate_update_refreshes_drifted_baseline(tmp_path):
    basedir, freshdir = fixture_dirs(tmp_path, 50, 104)
    out = run_gate("--fresh-dir", freshdir, "--baseline-dir", basedir,
                   "--areas", "toy", "--no-trend", "--update")
    assert out.returncode == 0, out.stdout + out.stderr
    rep = BenchReport.read(os.path.join(basedir, "BENCH_toy.json"))
    assert rep.metric("m").value == 104


def test_committed_baselines_parse_and_gate_expected_areas():
    """The repo-root BENCH_<area>.json baselines must always parse and
    carry at least one gated metric each (else the CI gate is vacuous)."""
    for area in ("plan", "sweep", "trace"):
        path = os.path.join(REPO_ROOT, f"BENCH_{area}.json")
        assert os.path.exists(path), f"committed baseline missing: {path}"
        rep = BenchReport.read(path)
        assert rep.area == area
        gated = [m for m in rep.metrics if m.gate]
        assert gated, f"{area}: no gated metrics"


# ------------------------------------------------- public core surface

def test_repro_core_public_surface():
    import repro.core as core
    expected = {"SimConfig", "run", "stats_list", "Scenario",
                "compile_plan", "execute_plan", "register", "parse_source",
                "expand_zoo", "make_scenario", "aggregate_stats",
                "network_health"}
    assert expected <= set(core.__all__)
    for name in core.__all__:
        assert getattr(core, name) is not None
    with pytest.raises(AttributeError):
        core.not_a_symbol
    # the lazy façade resolves to the same objects as the submodules
    from repro.core.config import SimConfig
    assert core.SimConfig is SimConfig


def test_network_health_helper():
    from repro.core import aggregate_stats, network_health
    stats = [{"hops": 100, "deflections": 10, "flits_delivered": 20,
              "send_drop": 2, "stray": 1, "cycles": 50, "finished": 1},
             {"hops": 100, "deflections": 0, "flits_delivered": 30,
              "send_drop": 0, "stray": 0, "cycles": 70, "finished": 1}]
    agg = aggregate_stats(stats)
    assert agg["hops"] == 200 and agg["cycles"] == 70 and agg["finished"] == 1
    h = network_health(agg)
    assert h["deflection_rate"] == pytest.approx(10 / 200)
    assert h["hops_per_flit"] == pytest.approx(200 / 50)
    assert h["drops_recovered"] == 2 and h["stray_responses"] == 1
    assert network_health({})["deflection_rate"] == 0.0
