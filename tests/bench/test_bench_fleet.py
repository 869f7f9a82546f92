"""The `fleet` driver through the whole run at a tiny size on the CPU: a
sound run is correct, and the control and every planted fault are not."""
import numpy as np
import pytest

from harness import checks, faults
from harness.cell import ROOT, Window, compare, lookup, run_cell

SEED = 2**31 + 101
W = "paper4.loadsweep"
TINY = {W: {"traffic": {"load_grid": [0.8, 1.0], "pool": 2, "sample_per_call": 4}}}


def quiet(*_):
    pass


@pytest.mark.parametrize("workload", sorted(TINY))
def test_sound_run_is_correct(workload):
    out = run_cell(workload, SEED, 1.0, False, root=ROOT, overrides=TINY[workload], log=quiet)
    assert out["correct"], out["checks"]
    assert out["attempted"] > 0 and out["failed"] == 0
    assert out["metrics"]["solved_inst_per_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert out["compiles_in_window"]["lowered"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
def test_planted_fault_is_not_correct(fault):
    out = run_cell(W, SEED, 1.0, False, root=ROOT, overrides=TINY[W], fault=fault, log=quiet)
    assert not out["correct"], out["checks"]


def test_control_is_not_correct():
    _, config, traffic, limits, driver, _, _ = lookup(W, ROOT)
    traffic = {**traffic, **TINY[W]["traffic"]}
    ctx = driver.setup(config, traffic, SEED, log=quiet)
    win = Window(1.0)
    win.start()
    driver.window(ctx, win)
    win.finish()
    got, missing = driver.answers(ctx)
    assert got and missing == 0
    program = compare({**checks.numbers(got), "unanswered": 0}, limits)
    control = compare({**checks.numbers(got, control=True), "unanswered": 0}, limits)
    assert all(ok for *_, ok in program)
    assert not all(ok for *_, ok in control)


def test_traced_run_reads_layers_without_a_chip():
    out = run_cell(W, SEED, 1.0, True, root=ROOT, overrides=TINY[W], log=quiet)
    m = out["metrics"]
    assert m["rounds_per_inst.fleet"]["value"] > 0
    assert m["stack_ms_per_inst.fleet"]["value"] > 0
    # no TPU plane in a CPU profile: the device readers return nothing
    assert "device_idle_share.fleet" not in m
    assert "device_ms_per_inst_round.fleet" not in m
    assert out["correct"]


def test_same_seed_same_inputs():
    from harness import gen

    _, config, traffic, *_ = lookup(W, ROOT)
    a = gen.draw(config, traffic, 7)
    b = gen.draw(config, traffic, 7)
    c = gen.draw(config, traffic, 8)
    assert len(a) == len(config["topologies"]) * len(traffic["load_grid"])
    for x, y, z in zip(a, b, c):
        assert np.array_equal(x.adj, y.adj) and np.array_equal(x.lam, y.lam)
        assert np.array_equal(x.adj, z.adj) and not np.array_equal(x.lam, z.lam)

