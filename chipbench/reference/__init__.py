"""The plain reference: the serial golden model, its trace synthesis and
its control.  Imports nothing of the program."""
from __future__ import annotations

from typing import Dict, Mapping, Optional

from .machine import Machine


def run_reference(sim: Mapping, source: str, seed: int, refs: int,
                  max_cycles: Optional[int], control: bool = False
                  ) -> Dict[str, int]:
    """Statistics of one scenario: machine ``sim`` (a configuration
    file's ``sim`` group) running trace ``source`` at ``seed`` with
    ``refs`` references per core, to completion or to ``max_cycles``.
    With ``control`` the control model runs instead."""
    from . import traces
    from .control import PortOrderSim
    from .serial import SerialSim
    machine = Machine.from_sim(sim)
    trace = traces.trace(machine, source, refs, seed)
    model = (PortOrderSim if control else SerialSim)(machine, trace)
    return model.run(max_cycles)
