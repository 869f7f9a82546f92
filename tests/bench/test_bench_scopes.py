"""The split of the engine program's device time by the program's named
scopes (bench/harness/scopes.py), and the readers of its metrics."""
import json
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))

from harness import scopes, tracing  # noqa: E402
from harness.cell import load_module  # noqa: E402

P = scopes.PROGRAM
E = ("alt.engine",)
R = E + ("alt.round",)
FWD = R + ("alt.forwarding",)
NEU = FWD + ("neumann",)
EVAL = R + ("alt.round_eval",)

# One engine program [100, 1000] and another program after it (ns). Ops:
# two Neumann hops, the forwarding op after them, a round_eval op, a
# bookkeeping op of the round, and an op of the other program.
OPS = [[("a", 110, 200, NEU), ("b", 250, 300, NEU), ("c", 300, 400, FWD),
        ("d", 500, 600, EVAL), ("e", 650, 700, R), ("f", 1100, 1200, ())]]
MODULES = [[(P, 100, 1000), ("jit_take", 1100, 1200)]]


def test_scope_path_reads_the_scopes_of_an_op_name():
    op = ("jit(engine_solve)/alt.engine/while/body/closed_call/while/body/alt.round/"
          "vmap(alt.forwarding)/jit(forwarding_update)/closed_call/neumann/while/body/dot")
    assert scopes.scope_path(op, scopes.ENGINE_SCOPES) == NEU
    assert scopes.scope_path("jit(engine_solve)/alt.round_eval/x", scopes.ENGINE_SCOPES) == (
        "alt.round_eval",)
    assert scopes.scope_path("jit(f)/neumann_solve/alt.rounds/add", scopes.ENGINE_SCOPES) == ()
    assert scopes.scope_path(op, ()) == ()


def test_busy_partitions_the_programs_busy_time_exactly():
    t = scopes.reduce(OPS, MODULES)
    busy = sum(v["busy_s"] for v in t["scopes"].values())
    assert busy == pytest.approx(t["busy_s"])
    # the engine's leaf ops alone, as the existing reduction counts busy time
    leaves = [[(n, s, e) for n, s, e, _ in OPS[0][:5]]]
    assert t["busy_s"] == pytest.approx(tracing.reduce(leaves, [[]], [], (100, 1000))["busy_s"])
    assert t["busy_s"] + t["gap_s"] == pytest.approx(t["program_s"]) == pytest.approx(900e-9)
    assert t["ops"] == 5 and t["unmapped_busy_s"] == 0.0
    assert scopes.under(t, "neumann") == pytest.approx(140e-9)
    assert scopes.under(t, "alt.forwarding") == pytest.approx(240e-9)
    assert scopes.under(t, "alt.round") == pytest.approx(390e-9)
    assert scopes.under(t, "alt.round_eval", "ops") == 1


def test_overlapping_ops_are_counted_once():
    ops = [[("a", 0, 100, NEU), ("b", 50, 120, FWD)]]
    t = scopes.reduce(ops, [[(P, 0, 120)]])
    assert t["scopes"]["/".join(NEU)]["busy_s"] == pytest.approx(100e-9)
    assert t["scopes"]["/".join(FWD)]["busy_s"] == pytest.approx(20e-9)
    assert t["gap_s"] == 0.0


def test_gap_goes_to_the_deepest_common_scope():
    t = scopes.reduce(OPS, MODULES)
    gap = {k: v["gap_s"] for k, v in t["scopes"].items()}
    assert gap["/".join(NEU)] == pytest.approx(50e-9)  # between the two hops
    assert gap["/".join(R)] == pytest.approx(150e-9)  # forwarding -> round_eval -> round
    assert gap[""] == pytest.approx(310e-9)  # the program's two ends
    assert sum(gap.values()) == pytest.approx(t["gap_s"])


def test_gap_between_programs_stays_with_its_host_span():
    ops = [[("x", 0, 100, E), ("y", 200, 300, ())]]
    modules = [[(P, 0, 100), ("jit_take", 200, 300)]]
    host = [("bench.fleet.call", 0, 300), ("solve_fleet.gather", 100, 200)]
    t = scopes.reduce(ops, modules, (0, 300))
    assert t["program_s"] == pytest.approx(100e-9) and t["gap_s"] == 0.0
    r = tracing.reduce([[o[:3] for o in ops[0]]], modules, host, (0, 300))
    assert dict(r["idle_gaps"]) == {"solve_fleet.gather": pytest.approx(100e-9)}


def test_the_existing_reduction_reads_as_before():
    """The scope split reads the same leaf ops and leaves every key of
    `tracing.reduce` as it was."""
    flat = [[(n, s, e) for n, s, e, _ in OPS[0]]]
    r = tracing.reduce(flat, MODULES, [], (100, 1200))
    assert set(r) == {"busy_s", "window_s", "programs", "device_ops", "idle_gaps", "idle_s"}
    assert r["busy_s"] == pytest.approx(490e-9)
    assert r["programs"] == {P: pytest.approx(900e-9), "jit_take": pytest.approx(100e-9)}


def test_window_clips_program_and_ops():
    t = scopes.reduce(OPS, MODULES, (150, 550))
    assert t["program_s"] == pytest.approx(400e-9)
    assert t["busy_s"] + t["gap_s"] == pytest.approx(400e-9)
    assert scopes.under(t, "neumann") == pytest.approx(100e-9)


def test_an_op_without_metadata_takes_its_loops_path():
    events = [("%while.1 while", 0, 100, NEU), ("%copy.3 copy", 10, 20, ()),
              ("%fusion.2 fusion", 20, 50, FWD), ("%copy.4 copy", 200, 210, ())]
    got = {n: p for n, _, _, p in tracing.leaves(scopes.inherit(events))}
    assert got == {"%copy.3 copy": NEU, "%fusion.2 fusion": FWD, "%copy.4 copy": ()}


def test_hlo_op_names_from_a_cpu_profile(tmp_path):
    """The metadata plane of a real (CPU) profile holds the program's HLO,
    with each instruction's op_name."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def f(x):
        with jax.named_scope("alt.engine"):
            def body(c):
                with jax.named_scope("neumann"):
                    return c[0] + 1, jnp.tanh(c[1] @ c[1].T) + 1

            return jax.lax.while_loop(lambda c: c[0] < 3, body, (0, x))[1].sum()

    x = jnp.ones((16, 16))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    f(x).block_until_ready()
    jax.profiler.stop_trace()
    raw = next(tmp_path.glob("**/*.xplane.pb")).read_bytes()
    names = scopes.hlo_op_names(raw)
    prog = next(k for k in names if k.startswith("jit_f("))
    paths = {scopes.scope_path(op, scopes.ENGINE_SCOPES) for op in names[prog].values()}
    assert ("alt.engine", "neumann") in paths and ("alt.engine",) in paths


RECORDED = Path(__file__).with_name("trace_v5e_scopes.json")


def test_recorded_chip_trace_with_scopes():
    """The start of a real v5e profile of the engine, its leaf ops carrying
    the scope paths the program's HLO gave them."""
    rec = json.loads(RECORDED.read_text())
    paths = [tuple(p.split("/")) if p else () for p in rec["paths"]]
    ops = [(rec["names"][n], s, e, paths[p]) for n, s, e, p in rec["ops"]]
    modules = [tuple(m) for m in rec["modules"]]
    t = scopes.reduce([ops], [modules], tuple(rec["window"]))
    for key, want in rec["expect"].items():
        assert t[key] == pytest.approx(want, rel=1e-9), key
    flat = [[(n, s, e) for n, s, e, _ in ops]]
    assert t["busy_s"] == pytest.approx(
        tracing.reduce(flat, [modules], [], tuple(rec["window"]))["busy_s"], rel=1e-12)
    assert t["busy_s"] + t["gap_s"] == pytest.approx(t["program_s"], rel=1e-12)
    assert t["unmapped_busy_s"] <= 0.01 * t["busy_s"]
    for scope in ("alt.init", "alt.placement", "neumann"):
        assert scopes.under(t, scope) > 0


METRICS = ["placement_ms_per_inst_round.fleet", "forwarding_ms_per_inst_round.fleet",
           "round_eval_ms_per_inst_round.fleet", "neumann_ms_per_inst_round.fleet",
           "engine_gap_ms_per_inst_round.fleet", "engine_ops_per_inst_round.fleet"]


@pytest.mark.parametrize("metric", METRICS)
def test_reader_without_a_trace_reads_nothing(metric):
    mod = load_module(ROOT / "bench" / "metrics" / f"{metric}.py", "m_" + metric.replace(".", "_"))
    reading = SimpleNamespace(trace=None, ctx=SimpleNamespace(records=[]))
    assert mod.read(reading) is None


# What each reader reads from the synthetic table at 2 instance-rounds: no
# op is under placement; the gap holds the program's two ends.
WANT = {"placement_ms_per_inst_round.fleet": 0.0,
        "forwarding_ms_per_inst_round.fleet": 240e-6 / 2,
        "round_eval_ms_per_inst_round.fleet": 100e-6 / 2,
        "neumann_ms_per_inst_round.fleet": 140e-6 / 2,
        "engine_gap_ms_per_inst_round.fleet": 510e-6 / 2,
        "engine_ops_per_inst_round.fleet": 5 / 2}


@pytest.mark.parametrize("metric", METRICS)
def test_reader_reads_the_table(metric):
    mod = load_module(ROOT / "bench" / "metrics" / f"{metric}.py", "m_" + metric.replace(".", "_"))
    table = scopes.reduce(OPS, MODULES)
    table["inst_rounds"] = 2
    reading = SimpleNamespace(trace={}, engine_scopes=table)
    assert mod.read(reading) == pytest.approx(WANT[metric])


def test_scope_readers_read_nothing_from_a_program_without_scopes():
    mod = load_module(ROOT / "bench" / "metrics" / "neumann_ms_per_inst_round.fleet.py", "m_n")
    table = scopes.reduce([[(n, s, e, ()) for n, s, e, _ in OPS[0]]], MODULES)
    table["inst_rounds"] = 2
    reading = SimpleNamespace(trace={}, engine_scopes=table)
    assert mod.read(reading) is None
