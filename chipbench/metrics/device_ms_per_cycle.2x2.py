"""device_ms_per_cycle.2x2: busy time of the busiest of the four
devices in the traced window over the simulated cycles the window
advanced, in ms: the slowest tile sets the pace of the mesh."""
from chipbench.trace_reduce import busiest


def read(reduced, record):
    dev = busiest(reduced)
    cycles = record.get("window_cycles", 0)
    if dev is None or cycles <= 0:
        return None
    return dev["busy_s"] * 1e3 / cycles
