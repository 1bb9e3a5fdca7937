"""device_idle_share.2x2: the share of the traced window in which the
devices ran nothing, averaged over the four devices of the sharded mesh.
The gaps are the per-chunk host monitor's (``repro.host_monitor``: the
finished check and the livelock watch after each slice) and the
slice's statistics readback.  1 - (union of device-op intervals /
window)."""
from chipbench.trace_reduce import idle_share


def read(reduced, record):
    return idle_share(reduced)
