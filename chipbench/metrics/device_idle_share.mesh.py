"""device_idle_share.mesh: the share of the traced window in which the
devices ran nothing, averaged over the devices (one large mesh advancing
slice after slice).  1 - (union of device-op intervals / window)."""
from chipbench.trace_reduce import idle_share


def read(reduced, record):
    return idle_share(reduced)
