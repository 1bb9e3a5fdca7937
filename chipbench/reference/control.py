"""The control: the reference with one stated guarantee broken.

The configurations state age-priority deflection arbitration: at each
router the oldest flit takes its productive port first.  Sorting the
four or five candidates of every router by age each cycle is work a
change for speed is tempted to drop.  :class:`PortOrderSim` drops it and
assigns ports in input-port order, the injected flit still last.  Every
other rule is the reference's.  A comparison that cannot tell this model
from the reference cannot hold the simulator to its arbitration.
"""
from __future__ import annotations

from typing import List, Tuple

from .serial import Flit, SerialSim


class PortOrderSim(SerialSim):
    """The serial model with port-order instead of age-order arbitration."""

    @staticmethod
    def arbitration_order(cands: List[Tuple[int, Flit]]
                          ) -> List[Tuple[int, Flit]]:
        return sorted(cands, key=lambda pf: pf[0])
