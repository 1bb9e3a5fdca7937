"""End-to-end behaviour tests for the paper's system."""
import numpy as np

from repro.core.config import SimConfig
from repro.core.sim import run
from repro.core.workloads import TRACE_APPS, app_trace


def test_paper_table3_shape():
    """Per-application statistics exist and balance (paper Table 3)."""
    for app in TRACE_APPS:
        cfg = SimConfig(rows=4, cols=4, addr_bits=14)
        stats = run(cfg, app_trace(cfg, app, 25, seed=1))
        assert stats["finished"] == 1, app
        assert stats["req_rcvd"] == stats["req_made"] + stats["redirection"]
        assert stats["dir_search"] > 0
        assert stats["l1_hits"] + stats["l1_misses"] > 0


def test_scaling_is_sublinear_per_node():
    """The vectorized simulator's cost per node per cycle shrinks with N —
    the paper's Fig. 6 speedup story, reproduced on one host.

    Wall-clock assertions flake on loaded CI runners, so measure where the
    effect is unambiguous: a 16x span of mesh sizes (4x4 vs 16x16), a
    chunked device loop (dispatch overhead otherwise dominates the small
    mesh), an unsaturated distributed directory, best-of-three timing, and
    a generous threshold (observed ratio is ~0.4; assert < 0.8)."""
    import time
    times = {}
    for rc in ((4, 4), (16, 16)):
        cfg = SimConfig(rows=rc[0], cols=rc[1], centralized_directory=False)
        tr = app_trace(cfg, "equake", 25, seed=1)
        run(cfg, tr, chunk=8)  # warm compile for this shape
        best = float("inf")
        for _ in range(3):
            t0 = time.time()
            stats = run(cfg, tr, chunk=8)
            best = min(best, time.time() - t0)
        assert stats["finished"] == 1, rc
        times[rc] = best / (stats["cycles"] * rc[0] * rc[1])
    assert times[(16, 16)] < times[(4, 4)] * 0.8, times
