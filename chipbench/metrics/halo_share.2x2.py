"""halo_share.2x2: the share of the busiest device's busy time in the
traced window spent in collective permutes: the four halo exchanges of
every cycle."""
from chipbench.trace_reduce import busiest


def read(reduced, record):
    dev = busiest(reduced)
    if dev is None or dev["permute_s"] <= 0:
        return None
    return dev["permute_s"] / dev["busy_s"]
