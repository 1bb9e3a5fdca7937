"""The simulated machine as the reference sees it.

Message types, packet lengths (paper Table 1), FSM states and ports, and
a :class:`Machine` built from a configuration file's ``sim`` group.  A
copy of the simulator's own definitions, so that the reference depends on
nothing of the program.
"""
from __future__ import annotations

import dataclasses
from typing import Mapping

# message types (paper Table 1 + the control messages of its §3.3/§3.4)
MSG_REQ = 0       # remote L2 read request                        (1 flit)
MSG_RA = 1        # data reply carrying one L1 block              (4 flits)
MSG_NACK = 2      # trap reply: block not found at owner          (1 flit)
MSG_DA = 3        # directory lookup request                      (1 flit)
MSG_DR = 4        # directory reply (payload: owner or -1)        (1 flit)
MSG_DU = 5        # directory update (payload: owner or -1=del)   (1 flit)
MSG_WB = 6        # L1 victim write-back to its L2 home           (4 flits)
MSG_B2 = 7        # L2 block migration / replacement transfer     (16 flits)
MSG_MIG_ACK = 8   # migration installed at destination            (1 flit)
MSG_REQ_FWD = 9   # redirected request (paper's RR)               (1 flit)

#: packet length in flits, indexed by message type
FLITS_OF = (1, 4, 1, 1, 1, 1, 4, 16, 1, 1)

# FSM states of a core
ST_IDLE, ST_L1_WAIT, ST_L2_WAIT, ST_WAIT_DIR, ST_WAIT_DATA, ST_WAIT_MEM, \
    ST_DONE = range(7)

# router ports
PORT_N, PORT_E, PORT_S, PORT_W = 0, 1, 2, 3
NUM_PORTS = 4

# memory-install targets
INSTALL_L2 = 0
INSTALL_L1_ONLY = 1


@dataclasses.dataclass(frozen=True)
class Caches:
    """Per-node cache geometry (paper Table 4)."""

    l1_sets: int
    l1_ways: int
    l1_block: int
    l2_sets: int
    l2_ways: int
    l2_block: int

    @property
    def l1_shift(self) -> int:
        return self.l1_block.bit_length() - 1

    @property
    def l2_shift(self) -> int:
        return self.l2_block.bit_length() - 1


@dataclasses.dataclass(frozen=True)
class Machine:
    """Every parameter of the simulated machine the reference reads.

    Built by :meth:`from_sim` from a configuration file's ``sim`` group,
    which states each of them; a missing key is an error, not a default.
    """

    rows: int
    cols: int
    cache: Caches
    l1_miss_cycles: int
    l2_hit_cycles: int
    mem_cycles: int
    addr_bits: int
    migration_enabled: bool
    migrate_threshold: int
    fwd_entries: int
    centralized_directory: bool
    rob_slots: int
    send_queue: int
    max_cycles: int
    pc_depth: int
    eject_age_threshold: int
    req_timeout: int
    livelock_window: int
    sat_window: int
    #: ``"packed"`` wraps packet ids at 2**14, ``"wide"`` at 2**30
    state_dtype_policy: str

    @classmethod
    def from_sim(cls, sim: Mapping) -> "Machine":
        names = [f.name for f in dataclasses.fields(cls)]
        missing = [k for k in names if k not in sim]
        if missing:
            raise KeyError(f"configuration lacks {missing}")
        kw = {k: sim[k] for k in names}
        kw["cache"] = Caches(**sim["cache"])
        return cls(**kw)

    @property
    def num_nodes(self) -> int:
        return self.rows * self.cols

    @property
    def livelock_window_effective(self) -> int:
        return self.livelock_window

    @property
    def dir_entries(self) -> int:
        return (1 << self.addr_bits) >> self.cache.l2_shift

    @property
    def pkt_wrap(self) -> int:
        return (1 << 14) if self.state_dtype_policy == "packed" else (1 << 30)

    def dir_home(self, tag: int) -> int:
        """Node holding the directory entry of ``tag``."""
        return 0 if self.centralized_directory else tag % self.num_nodes

    def validate(self) -> None:
        if self.rows < 2 or self.cols < 2:
            raise ValueError("mesh must be at least 2x2")
        if self.cache.l2_block % self.cache.l1_block:
            raise ValueError("L2 block must be a multiple of the L1 block")
        if self.pc_depth < 1 or self.rob_slots < 2:
            raise ValueError("pc_depth >= 1 and rob_slots >= 2 required")
