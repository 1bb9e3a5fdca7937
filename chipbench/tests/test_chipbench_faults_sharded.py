"""A whole run of the sharded backend over four virtual CPU devices, the
look for a chip skipped: a sound run comes out correct, and one with the
halo exchange between devices left out comes out not correct.

Runs in a child process, which gets its four devices from XLA_FLAGS
before it imports JAX."""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]

CHILD = textwrap.dedent("""
    import dataclasses, json, sys, time
    sys.path[:0] = [{root!r}, {src!r}]
    import jax.numpy as jnp
    from chipbench import harness
    from chipbench.reference import run_reference
    from repro.core import sharded

    def reference(sim, answers):
        return [run_reference(sim, a["source"], a["seed"], a["refs"],
                              a["max_cycles"]) for a in answers]

    c = harness.load_cell(None, "tiny8-dist-packed-2x2", "equake-r20-tiny")
    cell = dataclasses.replace(c, config=dict(c.config, sim=dict(
        c.config["sim"], rows=4, cols=4, addr_bits=12)))

    def run(seed):
        t0 = time.time()
        rec = harness.device_stage(cell, seed, 0.3, False, allow_cpu=True)
        return harness.host_stage(cell, rec, seed, False, t0,
                                  reference=reference)

    def no_exchange(out4, vp4, row_axes, col_axes):
        # every tile takes its own edge slabs for its neighbours': the
        # flits that should cross to another device never leave
        in_n = jnp.concatenate([out4[..., -1:, :, 2, :],
                                out4[..., :-1, :, 2, :]], axis=-3)
        in_s = jnp.concatenate([out4[..., 1:, :, 0, :],
                                out4[..., :1, :, 0, :]], axis=-3)
        in_w = jnp.concatenate([out4[..., :, -1:, 1, :],
                                out4[..., :, :-1, 1, :]], axis=-2)
        in_e = jnp.concatenate([out4[..., :, 1:, 3, :],
                                out4[..., :, :1, 3, :]], axis=-2)
        inp = jnp.stack([in_n, in_e, in_s, in_w], axis=-2)
        return jnp.where(vp4[..., None], inp, 0)

    sound = run(31)
    sharded._halo_transfer = no_exchange
    sharded._BUILD_CACHE.clear()
    broken = run(31)
    print(json.dumps({{"sound": sound, "broken": broken}}))
""")


def test_halo_exchange_left_out_is_caught():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = CHILD.format(root=str(ROOT), src=str(ROOT / "src"))
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    sound, broken = out["sound"], out["broken"]
    assert sound["device"]["count"] == 4
    assert sound["correct"], sound["checks"]
    assert not broken["correct"]
    assert broken["checks"]["stat_mismatches"]["value"] > 0
