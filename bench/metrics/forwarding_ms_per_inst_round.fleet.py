"""Busy device time under the engine's `alt.forwarding` scope (the T_phi
forwarding sweeps, their Neumann solves included), per instance-round of a
profiled call, in ms."""
from harness.scopes import per_inst_round


def read(reading):
    v = per_inst_round(reading, "busy_s", "alt.forwarding")
    return None if v is None else 1e3 * v
