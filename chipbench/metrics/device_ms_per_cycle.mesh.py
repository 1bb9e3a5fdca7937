"""device_ms_per_cycle.mesh: busy time of the busiest device in the
traced window over the simulated cycles the window advanced, in ms.
With the idle share it splits the wall time of a cycle into device work
and waiting."""
from chipbench.trace_reduce import busiest


def read(reduced, record):
    dev = busiest(reduced)
    cycles = record.get("window_cycles", 0)
    if dev is None or cycles <= 0:
        return None
    return dev["busy_s"] * 1e3 / cycles
