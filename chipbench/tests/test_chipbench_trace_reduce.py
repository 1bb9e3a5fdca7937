"""The reduction from trace events to busy time, idle gaps, op totals and
collective time, on hand-made events and on a recorded trace."""
import gzip
import json
from pathlib import Path

import pytest

from chipbench import trace_reduce as tr
from chipbench.trace_reduce import Event

RECORDED = Path(__file__).parent / "data"


def ev(kind, where, name, start, dur):
    return Event(kind, where, name, float(start), float(dur))


def small_trace():
    """Two devices over a 100 ns window [0, 100): device A busy in
    [10, 30) (two overlapping ops) and [60, 70) (a collective permute);
    device B busy in [20, 50) (an all-reduce).  Host spans: a slice over
    [0, 55), a readback over [55, 100)."""
    return [
        ev("host", "python", "chipbench.window", 0, 100),
        ev("host", "python", "chipbench.slice", 0, 55),
        ev("host", "python", "chipbench.readback", 55, 45),
        ev("device", "A", "fusion.1", 10, 15),
        ev("device", "A", "fusion.2", 20, 10),
        ev("device", "A", "collective-permute-done", 60, 10),
        ev("device", "B", "all-reduce.3", 20, 30),
        # outside the window: clipped away
        ev("device", "B", "fusion.1", 150, 10),
    ]


def test_union_merges_overlaps_and_drops_empty():
    assert tr.union([(5, 7), (0, 2), (1, 3), (4, 4)]) == [(0, 3), (5, 7)]
    assert tr.clip([(0, 3), (5, 7)], 2, 6) == [(2, 3), (5, 6)]


def test_busy_union_per_device():
    red = tr.reduce_events(small_trace())
    a, b = red["devices"]["A"], red["devices"]["B"]
    assert red["window_s"] == pytest.approx(100e-9)
    assert a["busy_s"] == pytest.approx(30e-9)     # [10,30) + [60,70)
    assert b["busy_s"] == pytest.approx(30e-9)     # [20,50)
    assert red["busy_s_mean"] == pytest.approx(30e-9)
    assert tr.idle_share(red) == pytest.approx(0.7)
    assert tr.busiest(red)["busy_s"] == pytest.approx(30e-9)


def test_op_totals_are_means_over_devices():
    red = tr.reduce_events(small_trace())
    ops = dict(red["device_ops"])
    assert red["devices"]["A"]["ops"]["fusion.1"] == pytest.approx(15e-9)
    # fusion.1 ran 15 ns on A inside the window, 0 on B: mean 7.5 ns
    assert ops["fusion.1"] == pytest.approx(7.5e-9)
    assert ops["all-reduce.3"] == pytest.approx(15e-9)
    assert [n for n, _ in red["device_ops"]][0] == "all-reduce.3"


def test_collective_and_permute_time():
    red = tr.reduce_events(small_trace())
    a, b = red["devices"]["A"], red["devices"]["B"]
    assert a["collective_s"] == pytest.approx(10e-9)
    assert a["permute_s"] == pytest.approx(10e-9)
    assert b["collective_s"] == pytest.approx(30e-9)
    assert b["permute_s"] == 0


def test_idle_gaps_are_labelled_by_host_span():
    red = tr.reduce_events(small_trace())
    # no device busy in [70,100), [0,10), [50,60), longest first; [50,60)
    # overlaps both spans by 5 ns and goes to the shorter (innermost) one
    gaps = red["idle_gaps"]
    assert [g[0] for g in gaps] == ["chipbench.readback", "chipbench.slice",
                                    "chipbench.readback"]
    assert [g[1] for g in gaps] == pytest.approx([30e-9, 10e-9, 10e-9])
    assert sum(g[1] for g in gaps) == pytest.approx(
        red["window_s"] - 50e-9)           # the window minus [10,50)+[60,70)


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce_events([e for e in small_trace()
                          if e.name != "chipbench.window"])


def test_no_device_events_reads_nothing():
    red = tr.reduce_events([e for e in small_trace() if e.kind == "host"])
    assert tr.idle_share(red) is None and tr.busiest(red) is None


@pytest.mark.parametrize("name", sorted(
    p.name for p in RECORDED.glob("*.json.gz")))
def test_recorded_trace_adds_up(name):
    """A trace recorded on a TPU v5e: busy time and idle gaps partition
    the window, every device lies inside it, and each op total is at most
    the window."""
    with gzip.open(RECORDED / name, "rt") as f:
        events = [Event(*row) for row in json.load(f)]
    red = tr.reduce_events(events)
    assert red["devices"], "the recorded trace holds device operations"
    assert all(k.startswith("/device:TPU") for k in red["devices"])
    for dev in red["devices"].values():
        assert 0 < dev["busy_s"] <= red["window_s"] * (1 + 1e-9)
        assert 0 <= dev["permute_s"] <= dev["collective_s"] <= dev["busy_s"]
        assert all(0 < v <= red["window_s"] * (1 + 1e-9)
                   for v in dev["ops"].values())
    assert 0 <= tr.idle_share(red) < 1
    assert len(red["idle_gaps"]) <= tr.TOP
    assert all(name.startswith("chipbench.") for name, _ in red["idle_gaps"])


def test_trace_cut_short_runs_from_the_start_marker_to_its_last_event():
    """Where the profiler stopped inside the window, the window span is
    missing: the traced window runs from the start marker to the last
    event the trace holds."""
    events = [e for e in small_trace() if e.name != "chipbench.window"
              and e.start_ns < 100]
    events.append(ev("host", "python", "chipbench.window_start", 0, 0))
    red = tr.reduce_events(events)
    assert red["window_s"] == pytest.approx(100e-9)   # readback ends at 100
    assert red["devices"]["A"]["busy_s"] == pytest.approx(30e-9)
