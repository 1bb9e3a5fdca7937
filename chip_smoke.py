#!/usr/bin/env python
"""Chip smoke: the simulator's main paths, once each, on a TPU.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the paths that span four chips

One chip, in order:

1. **device** — platform, kind and count as jax reports them; anything
   but a TPU fails the run.
2. **twin** — the 16x16 ``loop:matmul`` seed-0 20-ref scenario (the
   former S14 wedge) through ``engine.compile_plan``/``execute_plan``
   under the ``wide`` and ``packed`` layouts; each stats dict must equal
   the serial golden model's (:class:`repro.core.ref_serial.SerialSim`)
   key for key.
3. **sweep** — eight 32x32 scenarios (four synthetic patterns x two
   seeds) as one ``--sweep`` bucket through the launcher; every one must
   finish, and two re-run solo must reproduce their stats bit for bit.
4. **pallas** — the paper's 208x208 mesh running a paper application
   for a fixed 256 cycles with the compiled Pallas router and with the
   XLA reference arbitration; the stats must be identical and the
   compiled program must carry the kernel (``tpu_custom_call``).
5. **full width** — 208x208 (43,264 cores), packed state, distributed
   directory, 20 refs/core, run to completion (``finished: 1``, no
   ``aborted``) through the plan path.  A workload that aborts is named
   and the next one is tried.  The cycle cap is what the remaining time
   budget allows at the per-cycle cost of a 256-cycle probe run;
   reaching it fails the run.

The 208x208 programs compile in background threads while phases 2-3
run.  With ``--chips 4`` only the multi-chip paths run, each against its
one-device twin (run concurrently on device 0): the 208x208 mesh on the
``sharded`` backend over a (2, 2) tile grid against the dense backend,
and four 64x64 scenarios on the ``composed`` backend against each run
through the sweep backend on one device, each pair at one cycle cap.

Every phase runs in this one process.  Progress goes to stdout as
``phase: {json}`` lines; the last line is ``{"ok": true, "device":
{...}}``.  Any failed check exits non-zero without that line.  Times are
single runs of this smoke, host wall clock around work that ends in a
device-to-host copy of the results.
"""
from __future__ import annotations

import argparse
import concurrent.futures
import contextlib
import dataclasses
import io
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

PAPER_ROWS = 208                  # the paper's 43,264-core mesh
#: a directory with at least one entry per node: 2**22 bytes in 64-byte
#: L2 blocks is 65,536 entries for 43,264 nodes
PAPER_ADDR_BITS = 22
#: the completion run's address width: a synthetic pattern draws each
#: reference's tag from ``dir_entries // nodes`` candidates per
#: destination, so 26 bits (24 per node) keep 20 references distinct
FULL_ADDR_BITS = 26
REFS = 20                         # memory references per core
#: the serial twin: the former S14 wedge (16x16, seed 0), which the
#: golden model runs in seconds
TWIN_ROWS, TWIN_APP = 16, "loop:matmul"
SWEEP_ROWS = 32
#: the paper application of the 208x208 A/B and the four-chip phases
PAPER_APP = "equake"
#: workloads the full-width phase runs to completion, in the order it
#: tries them.  The paper's applications need hundreds of thousands of
#: cycles at 208x208 (every node's shared references converge on one
#: directory home), hours at the ~90 ms a v5e takes per cycle there; the
#: nearest-neighbour pattern's cycle count does not grow with the mesh.
FULL_APPS = ("neighbor", "tornado")
#: the sweep batch's patterns (x2 seeds); ``hotspot`` runs tens of
#: thousands of cycles at 32x32, far past the smoke's time budget
SWEEP_APPS = ("neighbor", "tornado", "transpose", "bitcomp")
CHUNK = 64                        # cycles per in-graph termination check
PALLAS_CYCLES = 256
PROBE_CYCLES = 256                # times a cycle before the completion run
BUDGET_S = 1080                   # wall-clock budget for the whole smoke
SHARDED_CYCLES = 512
COMPOSED_ROWS = 64
COMPOSED_APPS = ("equake", "apsi", "wupwise", "matmul")
COMPOSED_CYCLES = 1024

T0 = time.perf_counter()


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def say(phase: str, **fields) -> None:
    print(f"{phase}: {json.dumps(fields)}", flush=True)


def stats_diff(a: dict, b: dict) -> dict:
    return {k: (a.get(k), b.get(k)) for k in sorted(set(a) | set(b))
            if a.get(k) != b.get(k)}


def expect_backend(plan, backend: str, devices: int) -> None:
    """Every bucket of ``plan`` runs on ``backend`` over ``devices``
    devices, and no planner fallback fired."""
    for b in plan.buckets:
        require(b.backend == backend and b.devices_needed == devices
                and "fell back" not in b.note,
                f"planned {b.backend} over {b.devices_needed} device(s) "
                f"({b.note!r}), expected {backend} over {devices}")


def device_info(chips: int) -> dict:
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    say("device", **info)
    require(info["platform"] == "tpu",
            f"no TPU: jax runs on {info['platform']}")
    require(len(devs) >= chips, f"need {chips} chips, jax sees {len(devs)}")
    return info


def peak_bytes() -> int | None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def paper_cfg(**kw):
    from repro.core.config import SimConfig
    cfg = SimConfig(rows=PAPER_ROWS, cols=PAPER_ROWS,
                    addr_bits=PAPER_ADDR_BITS, centralized_directory=False,
                    state_dtype_policy="packed")
    return dataclasses.replace(cfg, **kw)


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------

def phase_twin() -> None:
    from repro.core import engine
    from repro.core.config import SimConfig
    from repro.core.ref_serial import SerialSim
    from repro.core.workloads import resolve_trace
    rows, app = TWIN_ROWS, TWIN_APP
    for policy in ("wide", "packed"):
        cfg = SimConfig(rows=rows, cols=rows, centralized_directory=False,
                        state_dtype_policy=policy)
        t = time.perf_counter()
        want = SerialSim(cfg, resolve_trace(cfg, app, REFS, 0)).run()
        serial_s = time.perf_counter() - t
        plan = engine.compile_plan([engine.make_scenario(
            cfg, app=app, seed=0, refs_per_core=REFS)])
        expect_backend(plan, "sweep", 1)
        t = time.perf_counter()
        (got,) = engine.execute_plan(plan, chunk=16)
        chip_s = time.perf_counter() - t
        require(got == want, f"twin {policy}: chip != serial "
                             f"{stats_diff(got, want)}")
        require(got["finished"] == 1, f"twin {policy} did not finish")
        say("twin", mesh=f"{rows}x{rows}", app=app, seed=0, refs=REFS,
            policy=policy, cycles=got["cycles"], equal_to_serial=True,
            serial_s=serial_s, chip_s_with_compile=chip_s)


def _launch(argv) -> dict:
    """``repro.launch.simulate.main(argv)`` in this process; its JSON
    payload (which it prints) is returned instead of printed."""
    from repro.launch import simulate
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        simulate.main(argv)
    return json.loads(buf.getvalue())


def phase_sweep() -> None:
    from repro.core.ref_serial import STAT_NAMES
    rows, seeds = SWEEP_ROWS, (0, 1)
    mesh = ["--rows", str(rows), "--cols", str(rows), "--refs", str(REFS),
            "--chunk", "16"]
    t = time.perf_counter()
    batch = _launch(mesh + ["--sweep", "--apps", ",".join(SWEEP_APPS),
                            "--seeds", ",".join(map(str, seeds))])
    batch_s = time.perf_counter() - t
    (bucket,) = batch["plan"]["buckets"]
    require(bucket["backend"] == "sweep"
            and "fell back" not in bucket.get("note", ""),
            f"sweep bucket planned as {bucket}")
    scs = batch["scenarios"]
    require(len(scs) == len(SWEEP_APPS) * len(seeds) >= 8,
            f"{len(scs)} scenarios")
    require(all(s["finished"] == 1 and "aborted" not in s for s in scs),
            f"unfinished: {[s for s in scs if s['finished'] != 1]}")
    keys = list(STAT_NAMES) + ["cycles", "finished"]
    for s in (scs[0], scs[-1]):
        solo = _launch(mesh + ["--app", s["app"], "--seed", str(s["seed"])])
        require(solo["backend"] == "sweep", f"solo planned as {solo}")
        a = {k: s[k] for k in keys}
        b = {k: solo[k] for k in keys}
        require(a == b, f"solo {s['app']}/{s['seed']} != batch "
                        f"{stats_diff(a, b)}")
    say("sweep", mesh=f"{rows}x{rows}", scenarios=len(scs),
        apps=list(SWEEP_APPS), seeds=list(seeds), refs=REFS,
        all_finished=True, solo_reruns_identical=2,
        max_cycles=max(s["cycles"] for s in scs),
        batch_s_with_compile=batch_s)


def paper_plan(app: str, **kw):
    from repro.core import engine
    plan = engine.compile_plan([engine.make_scenario(
        paper_cfg(**kw), app=app, seed=0, refs_per_core=REFS)])
    expect_backend(plan, "sweep", 1)
    return plan


def warm(plan) -> float:
    """Compile ``plan``'s program by running its first chunk; returns
    the seconds that took."""
    from repro.core import engine
    t = time.perf_counter()
    engine.execute_plan(plan, max_cycles=CHUNK, chunk=CHUNK)
    return time.perf_counter() - t


def compile_pallas():
    """Compile the 208x208 loop with the Pallas router ahead of time;
    returns ``(executable, state, cap, seconds)``."""
    import jax.numpy as jnp
    from repro.core import sim
    from repro.core.state import init_state
    from repro.core.workloads import resolve_trace
    cfg = paper_cfg(use_pallas_router=True)
    state = init_state(cfg, resolve_trace(cfg, PAPER_APP, REFS, 0)[None])
    cap = jnp.asarray(PALLAS_CYCLES, jnp.int32)
    t = time.perf_counter()
    exe = sim._run_jit.lower(state, cfg, cap, CHUNK).compile()
    return exe, state, cap, time.perf_counter() - t


def phase_pallas(plan, ref_compile_s: float, pallas) -> None:
    """Runs the reference ``plan`` and the Pallas-router program for
    ``PALLAS_CYCLES`` at 208x208."""
    import jax
    from repro.core import engine, sim
    exe, state, cap, pallas_compile_s = pallas
    cycles = PALLAS_CYCLES
    t = time.perf_counter()
    (ref,) = engine.execute_plan(plan, max_cycles=cycles, chunk=CHUNK)
    ref_s = time.perf_counter() - t
    require("tpu_custom_call" in exe.as_text(),
            "the Pallas-router program carries no tpu_custom_call")
    t = time.perf_counter()
    out = jax.block_until_ready(exe(state, cap))
    pallas_s = time.perf_counter() - t
    (got,) = sim.stats_list(*out)
    require(got == ref, f"pallas != reference {stats_diff(got, ref)}")
    require(got["cycles"] == cycles, f"stopped at {got['cycles']}")
    say("pallas", mesh=f"{PAPER_ROWS}x{PAPER_ROWS}", app=PAPER_APP,
        cycles=cycles, identical=True, tpu_custom_call=True,
        ref_compile_s=ref_compile_s, ref_run_s=ref_s,
        pallas_compile_s=pallas_compile_s, pallas_run_s=pallas_s)


def phase_full(kind: str, plans) -> None:
    """Runs the first of ``plans`` (one per ``FULL_APPS`` entry) that
    does not abort to completion, capped by the time left."""
    from repro.core import engine
    n = PAPER_ROWS * PAPER_ROWS
    for app, plan in zip(FULL_APPS, plans):
        t = time.perf_counter()
        engine.execute_plan(plan, max_cycles=PROBE_CYCLES, chunk=CHUNK)
        s_per_cycle = (time.perf_counter() - t) / PROBE_CYCLES
        left = BUDGET_S - (time.perf_counter() - T0)
        cap = min(plan.buckets[0].cfg.max_cycles, int(left / s_per_cycle))
        require(cap > PROBE_CYCLES, "no time left for the full-width run")
        t = time.perf_counter()
        (st,) = engine.execute_plan(plan, max_cycles=cap, chunk=CHUNK)
        run_s = time.perf_counter() - t
        if "aborted" in st:
            say("full_aborted", app=app, aborted=st["aborted"],
                cycles=st["cycles"])
            continue
        require(st["finished"] == 1,
                f"{app} at 208x208 did not finish within {cap} cycles "
                f"({run_s:.1f} s)")
        say("full", mesh=f"{PAPER_ROWS}x{PAPER_ROWS}", nodes=n, app=app,
            refs=REFS, addr_bits=FULL_ADDR_BITS, policy="packed",
            directory="distributed", s_per_cycle_probe=s_per_cycle,
            cycles=st["cycles"], finished=st["finished"], run_s=run_s,
            node_cycles_per_s=n * st["cycles"] / run_s, device_kind=kind,
            peak_bytes_in_use=peak_bytes(),
            flits_delivered=st["flits_delivered"],
            l1_misses=st["l1_misses"], deflections=st["deflections"])
        return
    raise SmokeFailure(f"every workload aborted at 208x208: {FULL_APPS}")


def one_chip(kind: str) -> None:
    # the 208x208 programs compile in the background while the small
    # phases run: compiling is host work, and the paper mesh takes
    # minutes of it
    ref = paper_plan(PAPER_APP)
    full = [paper_plan(app, addr_bits=FULL_ADDR_BITS) for app in FULL_APPS]
    with concurrent.futures.ThreadPoolExecutor(3) as pool:
        ref_compile = pool.submit(warm, ref)
        pallas = pool.submit(compile_pallas)
        full_compile = pool.submit(warm, full[0])
        phase_twin()
        phase_sweep()
        phase_pallas(ref, ref_compile.result(), pallas.result())
        full_compile.result()
    phase_full(kind, full)


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------

def paper_scenario():
    from repro.core import engine
    return engine.make_scenario(paper_cfg(), app=PAPER_APP, seed=0,
                                refs_per_core=REFS)


def dense_paper_run():
    """The sharded phase's twin: the dense backend on one device."""
    from repro.core import engine
    plan = engine.compile_plan([paper_scenario()], ndev=1)
    expect_backend(plan, "sweep", 1)
    t = time.perf_counter()
    (st,) = engine.execute_plan(plan, max_cycles=SHARDED_CYCLES, chunk=CHUNK)
    return st, time.perf_counter() - t


def composed_scenarios():
    from repro.core import engine
    from repro.core.config import SimConfig
    cfg = SimConfig(rows=COMPOSED_ROWS, cols=COMPOSED_ROWS,
                    centralized_directory=False, state_dtype_policy="packed")
    return [engine.make_scenario(cfg, app=app, seed=seed, refs_per_core=REFS)
            for seed, app in enumerate(COMPOSED_APPS)]


def one_device_solos():
    """The composed phase's twin: each scenario through the sweep
    backend on one device, as a batch of one (one compiled program for
    all four; a vmapped batch of four compiles for minutes)."""
    from repro.core import engine
    t = time.perf_counter()
    got = []
    for sc in composed_scenarios():
        plan = engine.compile_plan([sc], ndev=1)
        expect_backend(plan, "sweep", 1)
        got += engine.execute_plan(plan, max_cycles=COMPOSED_CYCLES,
                                   chunk=CHUNK)
    return got, time.perf_counter() - t


def phase_sharded(twin) -> None:
    from repro.core import engine
    plan = engine.compile_plan([paper_scenario()], force_backend="sharded")
    expect_backend(plan, "sharded", 4)
    require(plan.buckets[0].tiles == (2, 2),
            f"tiles {plan.buckets[0].tiles}")
    t = time.perf_counter()
    (got,) = engine.execute_plan(plan, max_cycles=SHARDED_CYCLES)
    sharded_s = time.perf_counter() - t
    ref, dense_s = twin.result()
    require(got == ref, f"sharded != dense {stats_diff(got, ref)}")
    say("sharded", mesh=f"{PAPER_ROWS}x{PAPER_ROWS}", tiles=[2, 2],
        devices=4, cycles=got["cycles"], identical_to_dense=True,
        sharded_s_with_compile=sharded_s, dense_s_with_compile=dense_s)


def phase_composed(twin) -> None:
    from repro.core import engine
    scs = composed_scenarios()
    plan = engine.compile_plan(scs, force_backend="composed")
    expect_backend(plan, "composed", 4)
    grid = plan.buckets[0].grid
    require(grid[0] > 1, f"grid {grid} does not split the scenario axis")
    t = time.perf_counter()
    got = engine.execute_plan(plan, max_cycles=COMPOSED_CYCLES)
    composed_s = time.perf_counter() - t
    ref, one_s = twin.result()
    require(got == ref, f"composed != one device "
                        f"{[stats_diff(a, b) for a, b in zip(got, ref)]}")
    say("composed", mesh=f"{COMPOSED_ROWS}x{COMPOSED_ROWS}",
        scenarios=len(scs), grid=list(grid), devices=4,
        identical_to_one_device=True, cycles=[s["cycles"] for s in got],
        finished=[s["finished"] for s in got],
        composed_s_with_compile=composed_s, one_device_s_with_compile=one_s)


def four_chips() -> None:
    # the one-device twins run on device 0 in background threads while
    # the four-chip programs run in this one: only this thread issues
    # multi-device programs, so every device sees them in one order
    with concurrent.futures.ThreadPoolExecutor(2) as pool:
        dense = pool.submit(dense_paper_run)
        solos = pool.submit(one_device_solos)
        phase_sharded(dense)
        phase_composed(solos)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the one-chip phases; 4: only the paths that "
                         "span four chips and their one-device twins")
    args = ap.parse_args(argv)

    from repro.core import engine
    engine.enable_compile_cache()
    try:
        info = device_info(args.chips)
        if args.chips == 4:
            four_chips()
        else:
            one_chip(info["kind"])
    except SmokeFailure as e:
        print(f"chip smoke FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    say("total", seconds=time.perf_counter() - T0)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
