"""Device idle inside the engine program (between two of its consecutive
operations, and at its two ends), per instance-round of a profiled call,
in ms."""
from harness.scopes import per_inst_round


def read(reading):
    v = per_inst_round(reading, "gap_s")
    return None if v is None else 1e3 * v
