"""Driver-level progress monitors: livelock + directory saturation.

Both monitors live inside the single batched driver (`sim._run_jit`), so a
solo ``run`` exercises exactly the code path a batched ``run_sweep`` or a
planned bucket uses (solo = batch of one).  The assertions here use the
*real* pathologies catalogued in ROADMAP, not synthetic state:

* livelock — 16x16 / matmul / seed 0 / refs 20 with the seed loop-trace
  generator: ~255 nodes wedge in WAIT_DIR/WAIT_DATA with ~193 flits
  circulating forever (S14 backpressure / ejection-bar cycle);
* saturation — any centralized-directory run at 256 nodes drowns node 0
  (the paper's own observation, the reason it distributes the directory).

Both pathologies require ``pc_depth=1`` (the paper-faithful single S14
completion register) since the pending-completion queue's ejection
guarantee resolves them — the detectors now watch those runs *complete*
at the default depth (see ``tests/test_pc_queue.py``), so the tests here
pin the compatibility escape hatch to keep a real livelock to detect.
"""
from repro.core.config import SimConfig
from repro.core.sim import run
from repro.core.workloads import app_trace, app_trace_loop

_DIAG_KEYS = ("circulating_flits", "wait_dir_nodes", "wait_data_nodes",
              "stalled_queues", "flits_to_node0")


def test_livelock_detector_aborts_roadmap_freeze():
    cfg = SimConfig(rows=16, cols=16, centralized_directory=False,
                    livelock_window=256, max_cycles=30_000, pc_depth=1)
    tr = app_trace_loop(cfg, "matmul", 20, 0)    # the exact ROADMAP combo
    st = run(cfg, tr, chunk=16)
    assert st["aborted"] == "livelock"
    assert st["finished"] == 0
    # aborted long before the cycle budget instead of burning it
    assert st["cycles"] < 15_000
    # the diagnostic surfaces the wedge: circulating flits + wait states
    assert st["circulating_flits"] > 50
    assert st["wait_dir_nodes"] + st["wait_data_nodes"] > 128
    for k in _DIAG_KEYS:
        assert k in st


def test_saturation_detector_aborts_centralized_hotspot():
    cfg = SimConfig(rows=16, cols=16, centralized_directory=True,
                    livelock_window=0,           # isolate the sat monitor
                    sat_window=1024, max_cycles=30_000, pc_depth=1)
    tr = app_trace(cfg, "matmul", 20, 1)
    st = run(cfg, tr, chunk=16)
    assert st["aborted"] == "dir_saturation"
    assert st["finished"] == 0
    assert st["cycles"] < 15_000
    assert st["cycles"] % 1024 == 0              # fires at a window edge
    # node-0 hotspot diagnostic
    assert st["wait_dir_nodes"] + st["wait_data_nodes"] >= 128
    assert st["flits_to_node0"] > 0


def test_healthy_run_reports_classic_keys_only():
    """Monitors never touch a healthy run: same key set, finished, and no
    abort — the bit-exactness guarantee the sweep/plan tests rely on."""
    from repro.core.ref_serial import STAT_NAMES
    cfg = SimConfig(rows=4, cols=4, addr_bits=14,
                    centralized_directory=False)
    st = run(cfg, app_trace(cfg, "equake", 25, seed=1), chunk=8)
    assert st["finished"] == 1
    assert set(st) == set(STAT_NAMES) | {"cycles", "finished"}


def test_monitors_match_serial_golden_model():
    """The golden-model equivalence contract covers the monitors: with an
    aggressively small window (freezes during ordinary memory stalls),
    SerialSim and the vectorized driver must produce the SAME dict —
    abort or no abort, same cycle, same diagnostics."""
    from repro.core.ref_serial import SerialSim
    cfg = SimConfig(rows=4, cols=4, addr_bits=14, mem_cycles=200,
                    migrate_threshold=2, centralized_directory=False,
                    livelock_window=16)
    tr = app_trace(cfg, "matmul", 12, seed=2)
    ref = SerialSim(cfg, tr).run()
    got = run(cfg, tr)
    assert ref == got, {k: (ref.get(k), got.get(k))
                        for k in set(ref) | set(got)
                        if ref.get(k) != got.get(k)}
    # the aggressive window must actually have fired for this to be a
    # meaningful parity check (a 200-cycle memory stall freezes stats)
    assert ref.get("aborted") == "livelock"


def test_livelock_window_zero_disables():
    cfg = SimConfig(rows=16, cols=16, centralized_directory=False,
                    livelock_window=0, sat_window=0, max_cycles=4_000,
                    pc_depth=1)
    tr = app_trace_loop(cfg, "matmul", 20, 0)
    st = run(cfg, tr, chunk=16)
    assert "aborted" not in st
    assert st["cycles"] == 4_000 and st["finished"] == 0
