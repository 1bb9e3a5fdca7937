"""device_idle_share.sweep: the share of the traced window in which the
device ran nothing, in a cell that runs batches of scenarios.
1 - (union of device-op intervals / window)."""
from chipbench.trace_reduce import idle_share


def read(reduced, record):
    return idle_share(reduced)
