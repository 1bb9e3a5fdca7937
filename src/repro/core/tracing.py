"""Names the simulator gives its work in a profiler trace.

Device work carries a ``jax.named_scope`` per layer of the cycle loop
(:data:`SCOPES`).  A scope is trace-time only: it adds no operation and
changes nothing of the compiled program but the ``op_name`` metadata of
its operations, e.g. ``jit(_run_jit)/driver/while/body/.../phase2/...``
(a vmapped scope reads ``vmap(phase2)``).  XLA names a fusion by its
root instruction, so a fusion counts toward the scope of its root.

Host work carries a ``jax.profiler.TraceAnnotation`` per layer boundary
(:data:`SPANS`), on the profiler's clock beside the device planes, so an
idle stretch of a device can be put down to the host work around it.

Record a trace around any run with ``jax.profiler.trace(logdir)``; the
trace viewer (or any reader of the ``.xplane.pb``) shows each device
operation with its ``op_name`` and each host span by name.
"""
from __future__ import annotations

import contextlib
import sys

__all__ = ["SCOPES", "SPANS", "span"]

#: device scopes: the four cycle phases (``cache.phase1a``/``phase1b``,
#: ``noc.phase2``/``phase3``), the sharded backend's halo exchange, and
#: everything else the cycle drivers run (widen/narrow, the stats fold,
#: termination, monitors, loop bookkeeping); the innermost one wins
SCOPES = ("phase1a", "phase1b", "phase2", "phase3", "halo", "driver")

#: host spans: trace synthesis, state placement, planning, statistics
#: readback and the sharded driver's per-chunk progress check
SPANS = ("repro.trace_synthesis", "repro.place_state", "repro.plan",
         "repro.readback", "repro.host_monitor")


@contextlib.contextmanager
def span(name: str):
    """Mark host work ``name`` (one of :data:`SPANS`) in a profiler
    trace; a context manager or a function decorator.  A few
    microseconds when no profiler runs; a plain no-op before jax is
    imported (no profiler can run then, and importing jax here would
    come too early for ``engine.expose_host_devices``)."""
    if "jax" not in sys.modules:
        yield
        return
    from jax.profiler import TraceAnnotation
    with TraceAnnotation(name):
        yield
