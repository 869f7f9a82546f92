"""Percent of the profiled capture in which no device operation ran."""
from harness.readers import idle_share_pct as read  # noqa: F401
