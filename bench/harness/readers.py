"""Helpers the per-layer metric readers (bench/metrics/*.py) share."""
from __future__ import annotations


def idle_share_pct(reading):
    """Percent of the captured window in which no device operation ran."""
    t = reading.trace
    if t is None or t["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
