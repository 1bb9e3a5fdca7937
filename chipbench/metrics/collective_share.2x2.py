"""collective_share.2x2: the share of the busiest device's busy time in
the traced window spent in collectives: the four halo permutes of every
cycle, the all-reduce of the tile-local finished flags every cycle, and
the all-reduce of the statistics after every chunk."""
from chipbench.trace_reduce import busiest


def read(reduced, record):
    dev = busiest(reduced)
    if dev is None or dev["collective_s"] <= 0:
        return None
    return dev["collective_s"] / dev["busy_s"]
