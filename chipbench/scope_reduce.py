"""Per-scope device time and per-span idle time from a profiler trace.

What :mod:`chipbench.trace_reduce` does not keep yet: the device scope
of each operation and the program's own host spans.

The program names its device work with ``jax.named_scope``
(:data:`SCOPES`, the list of ``repro.core.tracing``).  A scope is part of
the ``op_name`` metadata of every HLO instruction traced inside it, a
path such as ``jit(_run_jit)/driver/while/body/vmap(phase2)/...``; a
fusion carries its root instruction's.  The op events of a trace carry
no such path (neither on a TPU nor on the CPU), but the profiler stores
each program's optimized HLO in the trace's ``/host:metadata`` plane:
:func:`module_op_scopes` reads it, and each op is looked up by its
program and its instruction name.  An op whose path holds no known scope
counts as ``"(none)"``.  The program names its host work with
``jax.profiler.TraceAnnotation`` spans whose names start with
``repro.``; they sit beside the benchmark's ``chipbench.`` spans.

:func:`load_scoped` reads an ``.xplane.pb`` into the events of
``trace_reduce`` (host spans of both prefixes) and the scope of each;
:func:`reduce_scoped` returns everything ``trace_reduce.reduce_events``
returns, with per device ``scopes`` (``{scope: seconds}`` of the
non-container ops inside the window) and ``idle_by_span`` (``{span:
seconds}``: every stretch of the window in which no device ran anything,
cut at host span edges and each piece put down to the innermost span
around it by the rule of ``trace_reduce``'s gap labels).

    python3 chipbench/scope_reduce.py <logdir>

prints that reduction for any trace that holds a ``chipbench.window``
span, and for one that does not, over the whole trace.
"""
from __future__ import annotations

import bisect
import json
import re
import sys
from collections import Counter
from pathlib import Path
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from chipbench import trace_reduce as tr  # noqa: E402
from chipbench.trace_reduce import Event  # noqa: E402

#: the program's device scopes; the innermost one in an op's path wins
SCOPES = ("phase1a", "phase1b", "phase2", "phase3", "halo", "driver")
#: an op whose path holds none of them
NONE = "(none)"
#: host spans kept: the benchmark's and the program's
SPAN_PREFIXES = (tr.SPAN_PREFIX, "repro.")
#: a path segment, with the transforms wrapped around a scope name
#: (``vmap(phase2)``, ``jvp(vmap(driver))``) peeled off
_SEGMENT = re.compile(r"(?:[\w.-]+\()*([\w.-]+)\)*")
#: an instruction of HLO text, a computation's first line, an
#: ``op_name``, a called computation, a reference to an instruction
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.-]+) = (.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([\w.-]+) .*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%([\w.-]+)")
_REF = re.compile(r"%([\w.-]+)")


def scope_of(path: Optional[str]) -> Optional[str]:
    """The innermost of :data:`SCOPES` in a framework-op path, or
    ``None``."""
    for seg in reversed((path or "").split("/")):
        m = _SEGMENT.fullmatch(seg)
        if m and m.group(1) in SCOPES:
            return m.group(1)
    return None


def hlo_scopes(hlo_text: str) -> Dict[str, str]:
    """``{instruction name: scope}`` for the instructions of optimized
    HLO text.  An instruction takes the scope of its own ``op_name`` (a
    fusion's is its root's); where XLA left it none (a fusion a compiler
    pass cloned, a copy or an async start it inserted), the scope most
    instructions of the computation it calls carry, else the scope of
    its first operand that has one."""
    own: Dict[str, str] = {}
    inside: Dict[str, Counter] = {}
    instrs: List[Tuple[str, str]] = []
    computation = None
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                computation = c.group(1)
            continue
        name, rest = m.groups()
        op_name = _OP_NAME.search(rest)
        scope = scope_of(op_name.group(1)) if op_name else None
        if scope is not None:
            own[name] = scope
            inside.setdefault(computation, Counter())[scope] += 1
        instrs.append((name, rest))
    out: Dict[str, str] = {}
    for name, rest in instrs:
        scope = own.get(name)
        calls = _CALLS.search(rest)
        if scope is None and calls and calls.group(1) in inside:
            scope = inside[calls.group(1)].most_common(1)[0][0]
        if scope is None:
            refs = _REF.findall(rest.split(" metadata=", 1)[0])
            scope = next((out[r] for r in refs if r in out), None)
        if scope is not None:
            out[name] = scope
    return out


def _varint(buf, i: int) -> Tuple[int, int]:
    value = shift = 0
    while True:
        byte = buf[i]
        i += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """``(field number, value)`` of a serialized protobuf message: an int
    for a varint, a slice of ``buf`` otherwise."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield key >> 3, value


def _field(buf, number: int, default=b""):
    for f, value in _fields(buf):
        if f == number:
            return value
    return default


def module_op_scopes(xplane: bytes) -> Dict[str, Dict[str, str]]:
    """``{program name: {instruction name: scope}}`` from the optimized
    HLO the profiler keeps in the ``/host:metadata`` plane of an XSpace
    (program names as ``jit_f(5)``: the HLO module and its program id).
    Field numbers are those of ``xplane.proto`` (XSpace.planes 1;
    XPlane.name 2, event_metadata 4, stat_metadata 5; XEventMetadata.name
    2, stats 5; XStat.metadata_id 1, bytes_value 6) and ``hlo.proto``
    (HloProto.hlo_module 1)."""
    from jax._src.lib import xla_client
    out: Dict[str, Dict[str, str]] = {}
    for f, plane in _fields(memoryview(xplane)):
        if f != 1 or bytes(_field(plane, 2)) != b"/host:metadata":
            continue
        stat_names, metas = {}, []
        for g, entry in _fields(plane):
            if g == 5:
                meta = _field(entry, 2)
                stat_names[_field(meta, 1, 0)] = bytes(_field(meta, 2))
            elif g == 4:
                metas.append(_field(entry, 2))
        for meta in metas:
            for g, stat in _fields(meta):
                if g == 5 and stat_names.get(_field(stat, 1, 0)) \
                        == b"Hlo Proto":
                    module = xla_client._xla.HloModule \
                        .from_serialized_hlo_module_proto(
                            bytes(_field(_field(stat, 6), 1)))
                    out[bytes(_field(meta, 2)).decode()] = \
                        hlo_scopes(module.to_string())
    return out


class _Programs:
    """Looks an op up in the HLO of the program that ran it."""

    def __init__(self, scopes: Dict[str, Dict[str, str]]):
        self.scopes = scopes
        base: Dict[str, List[str]] = {}
        for name in scopes:
            base.setdefault(name.split("(", 1)[0], []).append(name)
        self.base = base

    def find(self, module: Optional[str]) -> Optional[Dict[str, str]]:
        """The instruction scopes of the program ``module`` names (a
        program name, or a module name that only one program has)."""
        if module is None:
            return None
        if module in self.scopes:
            return self.scopes[module]
        names = self.base.get(module.split("(", 1)[0], [])
        return self.scopes[names[0]] if len(names) == 1 else None


def load_scoped(path: str) -> List[Tuple[Event, Optional[str]]]:
    """Device operations and program executions of one ``.xplane.pb``,
    each op with its scope (``None`` for host spans, executions and ops
    outside the known scopes), and the host spans of the benchmark and
    of the program.  An op of a device plane belongs to the program
    execution around it; a CPU op names its program in its statistics."""
    from jax.profiler import ProfileData
    programs = _Programs(module_op_scopes(Path(path).read_bytes()))
    pd = ProfileData.from_file(path)
    out: List[Tuple[Event, Optional[str]]] = []
    device_planes = [p for p in pd.planes if p.name.startswith("/device:")]
    for plane in device_planes:
        lines = {line.name: list(line.events) for line in plane.lines}
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns, e.name)
                      for e in lines.get("XLA Modules", ()))
        starts = [m[0] for m in mods]
        out += [(Event("module", plane.name, tr.short_name(name), s,
                       e - s), None) for s, e, name in mods]
        for e in lines.get("XLA Ops", ()):
            k = bisect.bisect_right(starts, e.start_ns) - 1
            inside = mods[k][2] if k >= 0 and e.start_ns < mods[k][1] \
                else None
            name = tr.short_name(e.name)
            scopes = programs.find(inside) or {}
            out.append((Event("device", plane.name, name, e.start_ns,
                              e.duration_ns), scopes.get(name)))
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIXES):
                    out.append((Event("host", line.name, e.name, e.start_ns,
                                      e.duration_ns), None))
                elif not device_planes:
                    st = dict(e.stats)
                    if "hlo_op" in st:
                        prog = (f"{st.get('hlo_module')}"
                                f"({st.get('program_id')})")
                        scopes = programs.find(prog) or {}
                        out.append((Event(
                            "device", f"cpu:{st.get('device_ordinal', 0)}",
                            e.name, e.start_ns, e.duration_ns),
                            scopes.get(e.name)))
    return out


def _window(events: Sequence[Event]) -> Tuple[float, float]:
    """The measured window as ``trace_reduce.reduce_events`` finds it."""
    wins = [e for e in events if e.kind == "host" and e.name == tr.WINDOW_SPAN]
    starts = [e for e in events
              if e.kind == "host" and e.name == tr.WINDOW_START]
    if len(wins) == 1:
        return wins[0].start_ns, wins[0].end_ns
    return starts[0].start_ns, max(e.end_ns for e in events)


def reduce_scoped(pairs: Sequence[Tuple[Event, Optional[str]]]) -> Dict:
    """``trace_reduce.reduce_events`` of the events, plus ``scopes`` per
    device and ``idle_by_span``.  Every host span name seen in the window
    is a key of ``idle_by_span`` (0.0 where no device waited in it);
    stretches in no other span go to the window span."""
    events = [e for e, _ in pairs]
    red = tr.reduce_events(events)
    lo, hi = _window(events)
    for dev in red["devices"].values():
        dev["scopes"] = {}
    for e, scope in pairs:
        if e.kind != "device" or tr.CONTAINER.match(e.name):
            continue
        d = min(e.end_ns, hi) - max(e.start_ns, lo)
        if d > 0:
            sc = red["devices"][e.where]["scopes"]
            key = scope or NONE
            sc[key] = sc.get(key, 0.0) + d * 1e-9

    spans = [e for e in events if e.kind == "host"
             and min(e.end_ns, hi) > max(e.start_ns, lo)]
    busy = tr.clip(tr.union((e.start_ns, e.end_ns) for e in events
                            if e.kind != "host"), lo, hi)
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    cuts = sorted({x for sp in spans for x in (sp.start_ns, sp.end_ns)
                   if lo < x < hi})
    idle = {sp.name: 0.0 for sp in spans}
    idle.setdefault(tr.WINDOW_SPAN, 0.0)
    for i in range(0, len(edges), 2):
        s, e = edges[i], edges[i + 1]
        if e <= s:
            continue
        pts = [s] + [c for c in cuts if s < c < e] + [e]
        for a, b in zip(pts, pts[1:]):
            name = tr._span_at(spans, a, b)
            idle[name] += (b - a) * 1e-9
    red["idle_by_span"] = idle
    return red


def main(argv=None) -> int:
    logdir = (argv if argv is not None else sys.argv[1:])[0]
    pairs = load_scoped(tr.find_xplane(logdir))
    if not any(e.kind == "host" and e.name in (tr.WINDOW_SPAN,
                                               tr.WINDOW_START)
               for e, _ in pairs):
        lo = min(e.start_ns for e, _ in pairs)
        pairs.append((Event("host", "python", tr.WINDOW_SPAN, lo,
                            max(e.end_ns for e, _ in pairs) - lo), None))
    print(json.dumps(reduce_scoped(pairs), indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
