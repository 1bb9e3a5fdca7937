"""The command line end to end on the CPU: a rehearsal prints the result
line last with the checks last in it and ends standard error with the
checks; without a TPU, or without the program beside it, a run exits
non-zero and prints no result."""
import json
import os
import shutil
import subprocess
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
RUN = ["python3", "chipbench/run.py"]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")


def run(args, cwd=ROOT, env=ENV):
    return subprocess.run(RUN + args, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=600)


def test_rehearsal_prints_the_result_last():
    p = run(["--config", "tiny8-dist-packed", "--traffic", "equake-r20-tiny",
             "--allow-cpu", "--seed", str(2**31 + 3), "--seconds", "0.5",
             "--trace", "0"])
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["correct"] is True
    assert list(out)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(out)[-1] == "checks"
    assert {"node_cycles_per_s", "setup_s"} <= set(out["metrics"])
    assert out["metrics"]["setup_s"]["value"] > 0
    tail = p.stderr.strip().splitlines()[-len(out["checks"]):]
    assert tail == [f"check {k} {v['value']} limit {v['limit']}"
                    for k, v in out["checks"].items()]


def test_no_tpu_no_result():
    p = run(["--workload", "paper208-equake", "--seed", "1", "--seconds",
             "1", "--trace", "0"])
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_benchmark_alone_does_not_run(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(["--config", "tiny8-dist-packed", "--traffic", "equake-r20-tiny",
             "--allow-cpu", "--seed", "1", "--seconds", "0.2", "--trace",
             "0"], cwd=tmp_path,
            env={k: v for k, v in ENV.items() if k != "PYTHONPATH"})
    assert p.returncode != 0
    assert not p.stdout.strip()
