"""The reduction from a profile to busy time, program time, top operations
and idle gaps by host span."""
import json
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import tracing  # noqa: E402

# A small trace in the shape `tracing.load` returns (ns): two programs on one
# device, ops that overlap, host spans around them.
OPS = [[("fusion.1", 100, 300), ("fusion.2", 250, 400), ("copy", 600, 700),
        ("fusion.1", 900, 1000), ("late", 1200, 1300)]]
MODULES = [[("jit_engine_solve", 100, 400), ("jit_take", 600, 700),
            ("jit_engine_solve", 900, 1000)]]
HOST = [("bench.window", 50, 1100), ("bench.fleet.call", 60, 1040),
        ("solve_fleet.stack", 400, 600), ("solve_fleet.gather", 700, 900)]


def test_union_and_gaps():
    assert tracing.union([(3, 4), (1, 2), (2, 3)]) == [(1, 4)]
    assert tracing.gaps([(2, 3), (5, 6)], 0, 8) == [(0, 2), (3, 5), (6, 8)]


def test_reduce_small_trace():
    r = tracing.reduce(OPS, MODULES, HOST, (50, 1100))
    assert r["window_s"] == pytest.approx(1050e-9)
    # busy: [100, 400] + [600, 700] + [900, 1000]; the op at 1200 is outside
    assert r["busy_s"] == pytest.approx(500e-9)
    assert r["programs"]["jit_engine_solve"] == pytest.approx(400e-9)
    assert r["device_ops"][0] == ["fusion.1", pytest.approx(300e-9)]
    idle = dict((k, v) for k, v in r["idle_gaps"])
    # gap [50,100] lies in the call only, [400,600] in the stack span,
    # [700,900] in the gather span, [1000,1100] after the call
    assert idle["solve_fleet.stack"] == pytest.approx(200e-9)
    assert idle["solve_fleet.gather"] == pytest.approx(200e-9)
    assert idle["bench.fleet.call"] == pytest.approx(50e-9)
    assert idle["no host span"] == pytest.approx(100e-9)
    assert r["busy_s"] + sum(idle.values()) == pytest.approx(r["window_s"])


def test_busy_is_averaged_over_devices():
    r = tracing.reduce([[("a", 0, 100)], [("a", 0, 50)]], [[], []], [], (0, 100))
    assert r["busy_s"] == pytest.approx(75e-9)


RECORDED = Path(__file__).with_name("trace_v5e_fleet.json")


def test_recorded_chip_trace():
    """3 ms of a real v5e profile: raw op events with while ops nesting
    their bodies, reduced over leaf operations."""
    rec = json.loads(RECORDED.read_text())
    ops = [tuple(e) for e in rec["ops"]]
    leaves = tracing.leaves(ops)
    assert len(leaves) < len(ops)  # the slice holds nested while ops
    r = tracing.reduce([leaves], [[tuple(e) for e in rec["modules"]]],
                       [tuple(e) for e in rec["host"]], tuple(rec["window"]))
    for key, want in rec["expect"].items():
        assert r[key] == pytest.approx(want, rel=1e-9)
    assert 0 < r["busy_s"] < r["window_s"]
    assert r["busy_s"] + r["idle_s"] == pytest.approx(r["window_s"], rel=1e-6)
