"""Leaf operations the engine program executed, per instance-round of a
profiled call: a count, which tells fewer operations apart from faster
ones."""
from harness.scopes import per_inst_round


def read(reading):
    return per_inst_round(reading, "ops")
