"""The benchmark's machinery: cells, the device stage and the host stage.

A cell of ``BENCHMARK.json`` names a configuration and a traffic mix.
Both are data files found by name (``configs/<config>.json``,
``traffic/<traffic>.json``); the traffic file's ``mode`` names the window
driver (``modes/<mode>.py``), and each per-layer metric is a reader of
its own (``metrics/<metric>.py``).  Adding a cell adds files and an entry
in ``BENCHMARK.json``; it edits nothing here.

A run has two stages in two processes.  The device stage
(:func:`device_stage`) holds the chips: it checks the device, loads or
compiles the cell's program, warms it up, measures the window and, in a
traced run, reduces the profiler trace.  It hands its record to the host
stage (:func:`host_stage`), which never touches JAX: it runs the plain
reference over the answers the window produced, compares them, and
builds the result line.  The chips are free while the reference runs.
"""
from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import os
import random
import shutil
import sys
import tempfile
import threading
import time
from concurrent.futures import ProcessPoolExecutor
from multiprocessing import get_context
from pathlib import Path
from typing import Callable, Dict, List, Optional, Sequence

from . import trace_reduce

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
#: the persistent compile cache: fixed, inside the checkout, gitignored
COMPILE_CACHE = ROOT / ".jax_cache"
#: keys of a statistics dict the comparison covers besides the counters
RUN_KEYS = ("cycles", "finished")


class BenchError(Exception):
    """The run cannot produce a result (no chip, unknown cell or device)."""


# ---------------------------------------------------------------------------
# cells
# ---------------------------------------------------------------------------

def load_json(path: Path) -> Dict:
    with open(path) as f:
        return json.load(f)


def named_file(kind: str, name: str, suffix: str = ".json") -> Path:
    """``chipbench/<kind>/<name><suffix>``; ``BenchError`` if absent."""
    path = HERE / kind / f"{name}{suffix}"
    if not path.is_file():
        raise BenchError(f"no {kind} file {path.relative_to(ROOT)}")
    return path


@dataclasses.dataclass(frozen=True)
class Cell:
    """One cell as a run needs it."""

    name: str
    config: Dict
    traffic: Dict
    chips: int
    #: end-to-end metric entries of BENCHMARK.json this cell reports
    end_to_end: Sequence[Dict] = ()
    #: per-layer metric entries this cell reports in a traced run
    per_layer: Sequence[Dict] = ()


def _applies(metric: Dict, cell: str, e2e_names: Sequence[str]) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves", None) in (None, *e2e_names)


def load_cell(workload: Optional[str], config: Optional[str] = None,
              traffic: Optional[str] = None,
              bench_path: Path = ROOT / "BENCHMARK.json") -> Cell:
    """The cell ``workload`` of ``BENCHMARK.json``, or, for a rehearsal,
    the pair of files ``config`` x ``traffic`` with every metric whose
    cells list names a cell of the same traffic mode."""
    if workload is not None:
        bench = load_json(bench_path)
        cells = {w["name"]: w for w in bench["workloads"]}
        if workload not in cells:
            raise BenchError(f"no workload {workload!r} in BENCHMARK.json")
        w = cells[workload]
        e2e = [m for m in bench["end_to_end"] if _applies(m, workload, ())]
        names = [m["name"] for m in e2e]
        per = [m for m in bench["per_layer"] if _applies(m, workload, names)]
        return Cell(workload, load_json(named_file("configs", w["config"])),
                    load_json(named_file("traffic", w["traffic"])),
                    int(w["chips"]), e2e, per)
    if config is None or traffic is None:
        raise BenchError("name a --workload, or a --config and a --traffic")
    cfg = load_json(named_file("configs", config))
    trf = load_json(named_file("traffic", traffic))
    e2e, per = [], []
    if bench_path.is_file():
        bench = load_json(bench_path)
        like = {w["name"] for w in bench["workloads"]
                if load_json(named_file("traffic", w["traffic"]))["mode"]
                == trf["mode"]
                and load_json(named_file("configs", w["config"]))["backend"]
                == cfg["backend"]}
        e2e = [m for m in bench["end_to_end"]
               if "workloads" not in m or like & set(m["workloads"])]
        per = [m for m in bench["per_layer"]
               if like & set(m.get("workloads", ()))]
    return Cell(f"{config}.{traffic}", cfg, trf, int(cfg["chips"]), e2e, per)


def sim_config(cell: Cell):
    """The program's ``SimConfig`` for the cell's configuration file."""
    from repro.core.config import CacheConfig, SimConfig
    sim = dict(cell.config["sim"])
    cache = CacheConfig(**sim.pop("cache"))
    return SimConfig(cache=cache, **sim)


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = named_file(kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_{kind}_{name.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ---------------------------------------------------------------------------
# device stage
# ---------------------------------------------------------------------------

class Window:
    """What a mode driver needs from the harness: the run's parameters,
    host spans for the trace, and the measured window.

    ``span(name)`` records a host span (a no-op cost when not tracing).
    ``window()`` brackets the measured window: in a traced run it starts
    the profiler before and stops it after, and it counts the compiles
    that happen inside.  Where the traffic file sets ``trace_seconds``,
    the profiler stops that long after the window starts, while the
    window runs on: a batch of thousands of cycles makes millions of op
    events, more than the tracer keeps or a run has time to export."""

    def __init__(self, cell: Cell, seed: int, seconds: float, trace: bool):
        self.cell = cell
        self.seed = seed
        self.seconds = seconds
        self.trace = trace
        self.logdir: Optional[str] = None
        self.compiles_in_window = 0
        self._counting = False
        self._tracing = False
        self._lock = threading.Lock()

    def _stop_trace(self) -> None:
        import jax
        with self._lock:
            if self._tracing:
                jax.profiler.stop_trace()
                self._tracing = False

    def span(self, name: str):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def _on_event(self, event: str, *args, **kw) -> None:
        if self._counting and event.endswith("jaxpr_to_mlir_module_duration"):
            self.compiles_in_window += 1

    @contextlib.contextmanager
    def window(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)
        timer = None
        if self.trace:
            self.logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
            jax.profiler.start_trace(self.logdir)
            self._tracing = True
            with self.span(trace_reduce.WINDOW_START):
                pass
            limit = self.cell.traffic.get("trace_seconds")
            if limit:
                timer = threading.Timer(float(limit), self._stop_trace)
                timer.start()
        self._counting = True
        try:
            with self.span(trace_reduce.WINDOW_SPAN):
                yield
        finally:
            self._counting = False
            if timer is not None:
                timer.cancel()
            self._stop_trace()


def check_device(chips: int, allow_cpu: bool) -> Dict:
    """Platform, kind and count as JAX reports them.  No TPU, fewer chips
    than the cell needs, or a kind missing from ``peaks.json`` raise."""
    import jax
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if info["platform"] != "tpu" and not allow_cpu:
        raise BenchError(f"no TPU: JAX runs on {info['platform']}")
    if len(devs) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    if info["platform"] == "tpu":
        peaks(info["kind"])
    return info


def peaks(kind: str) -> Dict:
    """The published peaks of ``kind``; an unknown device raises."""
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise BenchError(f"device kind {kind!r} is not in peaks.json")
    return table[kind]


def memory_peak_bytes(chips: int) -> Optional[int]:
    """``peak_bytes_in_use`` of the fullest of the first ``chips``
    devices, or ``None`` where the backend keeps no such count."""
    import jax
    got = [(d.memory_stats() or {}).get("peak_bytes_in_use")
           for d in jax.devices()[:chips]]
    got = [g for g in got if g is not None]
    return max(got) if got else None


def device_stage(cell: Cell, seed: int, seconds: float, trace: bool,
                 allow_cpu: bool = False) -> Dict:
    """Set up, warm up and measure one window of ``cell``; returns the
    record the host stage reads.  Must run in the process that holds the
    chips."""
    import jax
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    COMPILE_CACHE.mkdir(exist_ok=True)   # jax does not create it
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    device = check_device(cell.chips, allow_cpu)
    win = Window(cell, seed, seconds, trace)
    mode = load_module("modes", cell.traffic["mode"])
    record = mode.run(win, sim_config(cell))
    record["device"] = dict(device,
                            memory_peak_bytes=memory_peak_bytes(cell.chips))
    record["compiles_in_window"] = win.compiles_in_window
    if trace:
        try:
            events = trace_reduce.load_events(
                trace_reduce.find_xplane(win.logdir))
            record["reduced"] = trace_reduce.reduce_events(events)
            record["trace_events"] = len(events)
        finally:
            shutil.rmtree(win.logdir, ignore_errors=True)
    return record


# ---------------------------------------------------------------------------
# host stage: the reference, the comparison and the result line
# ---------------------------------------------------------------------------

def reference_stats(sim: Dict, source: str, seed: int, refs: int,
                    max_cycles: Optional[int], control: bool = False
                    ) -> Dict[str, int]:
    """Statistics of the plain reference (or, with ``control``, of the
    control) for one scenario; runs in a worker process."""
    from .reference import run_reference
    return run_reference(sim, source, seed, refs, max_cycles, control)


def run_references(sim: Dict, answers: Sequence[Dict], control: bool = False
                   ) -> List[Dict[str, int]]:
    """The reference's statistics for every answer, one worker process
    per answer (the reference is serial Python; the answers are
    independent)."""
    if not answers:
        return []
    ctx = get_context("spawn")
    with ProcessPoolExecutor(len(answers), mp_context=ctx) as pool:
        futs = [pool.submit(reference_stats, sim, a["source"], a["seed"],
                            a["refs"], a["max_cycles"], control)
                for a in answers]
        return [f.result() for f in futs]


def compare(got: Sequence[Dict], want: Sequence[Dict]) -> Dict[str, int]:
    """``stat_mismatches``: (answer, key) pairs that differ, a key missing
    on one side counting as one; ``max_stat_gap``: the largest absolute
    difference of a key both sides have."""
    from .reference.serial import STAT_NAMES
    mism, gap = 0, 0
    for g, w in zip(got, want):
        keys = set(STAT_NAMES) | set(RUN_KEYS) | set(g) | set(w)
        for k in sorted(keys):
            if k not in g or k not in w:
                mism += 1
            elif g[k] != w[k]:
                mism += 1
                if isinstance(g[k], int) and isinstance(w[k], int):
                    gap = max(gap, abs(g[k] - w[k]))
    mism += abs(len(got) - len(want))
    return {"stat_mismatches": mism, "max_stat_gap": gap}


#: the limit of each number compared: the comparison is exact
LIMITS = {"stat_mismatches": 0, "max_stat_gap": 0}


def sample_answers(answers: Sequence[Dict], seed: int) -> List[Dict]:
    """One answer per lane (position in its batch), the batch drawn from
    ``seed``, with the longest answer (most cycles) always among them."""
    lanes: Dict[int, List[int]] = {}
    for i, a in enumerate(answers):
        lanes.setdefault(a.get("lane", 0), []).append(i)
    rng = random.Random(seed)
    pick = {lane: rng.choice(idx) for lane, idx in sorted(lanes.items())}
    if answers:
        longest = max(range(len(answers)),
                      key=lambda i: answers[i]["stats"].get("cycles", 0))
        pick[answers[longest].get("lane", 0)] = longest
    return [answers[i] for i in sorted(pick.values())]


def host_stage(cell: Cell, record: Dict, seed: int, trace: bool, t0: float,
               reference: Callable = run_references) -> Dict:
    """The result line for ``record``: metrics, device, correctness.
    ``t0`` is the wall-clock time the run started; set-up lasts from it
    to the start of the window."""
    answers = sample_answers(record["answers"], seed)
    t = time.perf_counter()
    want = reference(cell.config["sim"], answers)
    print(f"reference: {len(answers)} answers in "
          f"{time.perf_counter() - t:.1f} s", file=sys.stderr, flush=True)
    checks = compare([a["stats"] for a in answers], want)
    correct = bool(answers) and all(v <= LIMITS[k] for k, v in checks.items())

    metrics: Dict[str, Dict] = {}
    if trace:
        reduced = record.get("reduced", {})
        for m in cell.per_layer:
            value = load_module("metrics", m["name"]).read(reduced, record)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        have = dict(record["end_to_end"])
        have["setup_s"] = record["window_start"] - t0
        peak = record["device"]["memory_peak_bytes"]
        if peak is not None:
            have["peak_hbm_mb"] = peak / 1e6
        for m in cell.end_to_end:
            if m["name"] in have:
                metrics[m["name"]] = {"value": have[m["name"]],
                                      "unit": m["unit"]}
            elif record["device"]["platform"] == "tpu":
                raise BenchError(f"the run measured no {m['name']}")

    device = dict(record["device"])
    out = {"correct": correct, "attempted": record["attempted"],
           "failed": record["failed"], "metrics": metrics, "device": device}
    if trace and record.get("reduced"):
        red = record["reduced"]
        device["busy_s"] = red["busy_s_mean"]
        device["window_s"] = red["window_s"]
        out["breakdown"] = {"device_ops": red["device_ops"],
                            "idle_gaps": red["idle_gaps"]}
    out["checks"] = {k: {"value": v, "limit": LIMITS[k]}
                     for k, v in checks.items()}
    return out
