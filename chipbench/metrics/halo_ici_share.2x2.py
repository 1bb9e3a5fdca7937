"""halo_ici_share.2x2: the halo exchange's share of its roofline, the
inter-chip link.  The bytes the busiest device's collective permutes
move per simulated cycle, over its permute time per cycle, over the
device's published interconnect bandwidth (``peaks.json``).  The
permutes are asynchronous start/done pairs: their op time is what the
device spends issuing and waiting for them, not the link's busy time.

Each cycle every tile sends four slabs, one per direction (the permutes
wrap, so on a 2x2 each device sends all four): a row of the tile for the
north and south neighbours, a column for the east and west ones, each
node's outgoing flit as ``FLIT_FIELDS`` int32 fields."""
import json
import math
from pathlib import Path

from chipbench.trace_reduce import busiest

#: the tile grid of the cell's configuration
TILES = (2, 2)
#: int32 fields of a flit between phases (the program's NUM_F)
FLIT_FIELDS = 10
FIELD_BYTES = 4
PEAKS = Path(__file__).resolve().parent.parent / "peaks.json"


def halo_bytes_per_cycle(rows: int, cols: int) -> int:
    """Bytes one device's four halo slabs hold each cycle."""
    rt, ct = rows // TILES[0], cols // TILES[1]
    return 2 * (rt + ct) * FLIT_FIELDS * FIELD_BYTES


def read(reduced, record):
    dev = busiest(reduced)
    cycles = record.get("window_cycles", 0)
    nodes = record.get("nodes", 0)
    side = math.isqrt(nodes)
    kind = record.get("device", {}).get("kind")
    with open(PEAKS) as f:
        peak = json.load(f).get(kind, {}).get("ici_bits_per_s")
    if (dev is None or dev["permute_s"] <= 0 or cycles <= 0 or not peak
            or not nodes or side * side != nodes):
        return None
    rate = halo_bytes_per_cycle(side, side) * cycles / dev["permute_s"]
    return rate / (peak / 8)
