"""The cell ``paper208-2x2-equake`` cut to meshes a CPU can run, over
four virtual CPU devices: the harness's device stage and host stage on
the cell's own configuration and traffic, with only ``rows``, ``cols``
and ``addr_bits`` cut.  At 16x16 every 8x8 tile has an interior; at
12x20 the tiles are 6x10, sides unequal.  Each comes out correct against
the plain reference at the traffic's check slice, and the sharded answer
equals the one-chip cell's (the dense backend, flat directory) key for
key at the same seed and cycle.

One child process runs every case: it gets its four devices from
XLA_FLAGS before it imports JAX."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
SEED = 3000015201
#: (rows, cols) of each cut; the cell's tiles stay (2, 2)
SHAPES = {"16x16": (16, 16), "12x20": (12, 20)}

CHILD = textwrap.dedent("""
    import dataclasses, json, sys, time
    sys.path[:0] = [{root!r}, {src!r}]
    from chipbench import harness
    from chipbench.reference import run_reference

    def reference(sim, answers):
        return [run_reference(sim, a["source"], a["seed"], a["refs"],
                              a["max_cycles"]) for a in answers]

    def cut(name, rows, cols):
        c = harness.load_cell(name)
        return dataclasses.replace(c, config=dict(c.config, sim=dict(
            c.config["sim"], rows=rows, cols=cols, addr_bits=12)))

    def run(cell):
        t0 = time.time()
        rec = harness.device_stage(cell, {seed}, 0.0, False, allow_cpu=True)
        out = harness.host_stage(cell, rec, {seed}, False, t0,
                                 reference=reference)
        return dict(out, answers=rec["answers"])

    res = {{k: run(cut("paper208-2x2-equake", r, c))
           for k, (r, c) in {shapes!r}.items()}}
    res["dense16x16"] = run(cut("paper208-equake", 16, 16))
    print(json.dumps(res))
""")


@pytest.fixture(scope="module")
def runs():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(root=str(ROOT), src=str(ROOT / "src"), seed=SEED,
                        shapes=SHAPES)
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("shape", sorted(SHAPES))
def test_sharded_cell_cut_to_cpu_size_is_correct(runs, shape):
    out = runs[shape]
    assert out["device"]["count"] == 4
    assert out["correct"], out["checks"]
    assert out["checks"]["stat_mismatches"]["value"] == 0
    assert out["checks"]["max_stat_gap"]["value"] == 0
    (answer,) = out["answers"]
    # the traffic's check slice: a warm slice and two window slices of 64
    assert answer["max_cycles"] == answer["stats"]["cycles"] == 192
    assert answer["stats"]["flits_delivered"] > 0


def test_sharded_cell_answers_as_the_one_chip_cell(runs):
    (sharded,) = runs["16x16"]["answers"]
    (dense,) = runs["dense16x16"]["answers"]
    assert runs["dense16x16"]["correct"]
    assert sharded["seed"] == dense["seed"] == SEED
    assert sharded["stats"] == dense["stats"]
