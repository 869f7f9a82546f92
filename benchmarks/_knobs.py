"""Kernel-path knobs for the benchmark runner.

`benchmarks.run --use-pallas [--interpret]` exports the launch profile to
the individual benches through the environment (the bench modules are plain
`run()` functions), mirroring the `launch/fleet.py` CLI contract: benches
thread `**pallas_knobs()` into their solver calls, so a TPU/GPU deployment
benchmarks the real kernel path with the same one-flag flip as the launcher.
With the flags unset this returns {} and every bench keeps its default
(pure-XLA) path — committed BENCH baselines are XLA-path numbers.
"""
from __future__ import annotations

import os


def pallas_knobs() -> dict:
    """use_pallas/interpret kwargs from the runner environment (or {})."""
    if not os.environ.get("REPRO_BENCH_USE_PALLAS"):
        return {}
    return {
        "use_pallas": True,
        "interpret": os.environ.get("REPRO_BENCH_INTERPRET") == "1",
    }
