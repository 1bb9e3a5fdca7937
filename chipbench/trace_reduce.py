"""Reduce a profiler trace to device intervals, op totals and idle gaps.

The profiler writes an ``.xplane.pb``.  :func:`load_events` turns it into
plain event tuples, and :func:`reduce_events` turns those into the
numbers the per-layer readers take: per device, the union of the
intervals in which an operation ran (its busy time) inside the measured
window, the time per operation name, the time in collectives, and the
idle gaps between operations, each labelled with the host span of the
benchmark it fell in.

Device operations are the events of the ``XLA Ops`` line of each
``/device:...`` plane, and program executions those of its ``XLA
Modules`` line.  A device is busy while either runs: the tracer keeps a
bounded number of op events, and a long loop of small ops (a whole
batch of scenarios) outruns it, while its program execution is one
event.  Op totals leave out the control-flow ops (``while``,
``conditional``, ``call``) that contain other ops, and name each op by
its HLO name.  A trace without device planes (the CPU backend in a
rehearsal) takes the events that carry an ``hlo_op`` statistic, one
device per ``device_ordinal``.
Host spans are the events whose name starts with ``chipbench.``; the
``chipbench.window`` span marks the measured window.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: host span that marks the measured window
WINDOW_SPAN = "chipbench.window"
#: empty host span at the window's start; where the profiler stopped
#: before the window ended, the traced window runs from it to the last
#: event the trace holds
WINDOW_START = "chipbench.window_start"
SPAN_PREFIX = "chipbench."
#: names of device operations that move data between chips
COLLECTIVE = re.compile(
    r"collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all"
    r"|ppermute|psum", re.IGNORECASE)
#: the point-to-point ones among them (a halo exchange)
PERMUTE = re.compile(r"collective-permute|ppermute", re.IGNORECASE)
#: ops that only contain other ops
CONTAINER = re.compile(r"^(while|conditional|call)(\.|$)")
TOP = 10


@dataclasses.dataclass(frozen=True)
class Event:
    """One trace event: ``kind`` is ``"device"`` (an op), ``"module"`` (a
    program execution) or ``"host"`` (a benchmark span); ``where`` names
    the device plane (or host thread line); times in nanoseconds."""

    kind: str
    where: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def find_xplane(logdir: str) -> str:
    """The one ``.xplane.pb`` the profiler wrote under ``logdir``."""
    found = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True))
    if len(found) != 1:
        raise FileNotFoundError(
            f"expected one .xplane.pb under {logdir}, found {found}")
    return found[0]


def load_events(path: str) -> List[Event]:
    """Device operations and benchmark host spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    out: List[Event] = []
    device_planes = [p for p in pd.planes if p.name.startswith("/device:")]
    kinds = {"XLA Ops": "device", "XLA Modules": "module"}
    for plane in device_planes:
        for line in plane.lines:
            if line.name in kinds:
                out += [Event(kinds[line.name], plane.name,
                              short_name(e.name), e.start_ns, e.duration_ns)
                        for e in line.events]
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    out.append(Event("host", line.name, e.name, e.start_ns,
                                     e.duration_ns))
                elif not device_planes:
                    st = dict(e.stats)
                    if "hlo_op" in st:
                        out.append(Event(
                            "device", f"cpu:{st.get('device_ordinal', 0)}",
                            e.name, e.start_ns, e.duration_ns))
    return out


def short_name(name: str) -> str:
    """``fusion.12`` for the TPU's ``%fusion.12 = s32[...] fusion(...)``."""
    return name.split(" = ", 1)[0].lstrip("%")


def union(intervals: Iterable[Tuple[float, float]]
          ) -> List[Tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def _span_at(spans: Sequence[Event], lo: float, hi: float) -> str:
    """Name of the innermost benchmark span overlapping ``[lo, hi)`` most
    (the window span itself when no other does)."""
    best, best_key = WINDOW_SPAN, (0.0, 0.0)
    for sp in spans:
        ov = min(sp.end_ns, hi) - max(sp.start_ns, lo)
        if ov <= 0 or sp.name == WINDOW_SPAN:
            continue
        key = (ov, -sp.dur_ns)
        if key > best_key:
            best, best_key = sp.name, key
    return best


def reduce_events(events: Sequence[Event]) -> Dict:
    """Per-device busy, collective and op time inside the window, and the
    longest idle gaps with their host spans.

    Returns a dict with ``window_s``; ``devices`` (per device plane:
    ``busy_s``; ``collective_s`` and, of it, ``permute_s``; ``ops`` as
    ``{name: seconds}``);
    ``busy_s_mean``; ``device_ops`` and ``idle_gaps`` as lists of
    ``[name, seconds]`` (at most :data:`TOP` each; op seconds are means
    over devices).  Raises ``ValueError`` when the trace has no window
    span (or, for a trace the profiler stopped early, no
    :data:`WINDOW_START` span)."""
    windows = [e for e in events if e.kind == "host" and e.name == WINDOW_SPAN]
    starts = [e for e in events if e.kind == "host" and e.name == WINDOW_START]
    if len(windows) == 1:
        lo, hi = windows[0].start_ns, windows[0].end_ns
    elif not windows and len(starts) == 1:
        lo, hi = starts[0].start_ns, max(e.end_ns for e in events)
    else:
        raise ValueError(f"expected one {WINDOW_SPAN} span, or one "
                         f"{WINDOW_START} span of a trace cut short")
    spans = [e for e in events if e.kind == "host"]
    by_dev: Dict[str, List[Event]] = {}
    for e in events:
        if e.kind in ("device", "module"):
            by_dev.setdefault(e.where, []).append(e)

    devices: Dict[str, Dict] = {}
    op_sum: Dict[str, float] = {}
    for dev, both in sorted(by_dev.items()):
        busy = clip(union((e.start_ns, e.end_ns) for e in both), lo, hi)
        evs = [e for e in both if e.kind == "device"]
        coll = clip(union((e.start_ns, e.end_ns) for e in evs
                          if COLLECTIVE.search(e.name)), lo, hi)
        perm = clip(union((e.start_ns, e.end_ns) for e in evs
                          if PERMUTE.search(e.name)), lo, hi)
        ops: Dict[str, float] = {}
        for e in evs:
            if CONTAINER.match(e.name):
                continue
            d = min(e.end_ns, hi) - max(e.start_ns, lo)
            if d > 0:
                ops[e.name] = ops.get(e.name, 0.0) + d * 1e-9
        for k, v in ops.items():
            op_sum[k] = op_sum.get(k, 0.0) + v
        devices[dev] = dict(busy_s=sum(e - s for s, e in busy) * 1e-9,
                            collective_s=sum(e - s for s, e in coll) * 1e-9,
                            permute_s=sum(e - s for s, e in perm) * 1e-9,
                            ops=ops)

    # idle gaps: stretches of the window in which no device ran anything
    any_busy = clip(union((e.start_ns, e.end_ns) for e in events
                          if e.kind != "host"), lo, hi)
    edges = [lo] + [x for iv in any_busy for x in iv] + [hi]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    ndev = max(len(devices), 1)
    top_ops = sorted(op_sum.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:TOP]
    return dict(
        window_s=(hi - lo) * 1e-9,
        devices=devices,
        busy_s_mean=(sum(d["busy_s"] for d in devices.values()) / ndev),
        device_ops=[[k, v / ndev] for k, v in top_ops],
        idle_gaps=[[_span_at(spans, s, e), (e - s) * 1e-9]
                   for s, e in top_gaps],
    )


def idle_share(reduced: Dict) -> Optional[float]:
    """1 - mean device busy time over the window; ``None`` when the trace
    holds no device operation."""
    if not reduced.get("devices") or reduced["window_s"] <= 0:
        return None
    return 1.0 - reduced["busy_s_mean"] / reduced["window_s"]


def busiest(reduced: Dict) -> Optional[Dict]:
    """The device entry with the most busy time, or ``None``."""
    devs = list(reduced.get("devices", {}).values())
    if not devs:
        return None
    best = max(devs, key=lambda d: d["busy_s"])
    return best if best["busy_s"] > 0 else None
