"""Busy device time under the `neumann` scope (the propagation solves,
wherever they are nested), per instance-round of a profiled call, in ms."""
from harness.scopes import per_inst_round


def read(reading):
    v = per_inst_round(reading, "busy_s", "neumann")
    return None if v is None else 1e3 * v
