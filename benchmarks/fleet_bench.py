"""Fleet engine benchmark: batched multi-scenario solving vs the sequential
per-instance loop, plus the nilpotent-propagation solver axis.

Section 1 (batched-vs-sequential): a fresh ER/BA ensemble at the acceptance
regime — B=12 instances at the native envelope (V=64, A=24), so the warm
comparison isolates the engine's round-body layout from envelope padding
(see the ENGINE_FLEET_KW comment). Both paths are timed end-to-end from
cold caches (symmetric: each gets `jax.clear_caches()` first), then warm
as a paired median of `WARM_REPS` interleaved repeats (see `_paired_warm`)
for the steady-state re-optimization rate. The engine runs its default
round-body layout (`lane_chunk` auto ->
lax.map lane chunks on the CPU, DESIGN.md section 18), which is what
closed the historical ~0.65x warm gap there; the full tier asserts
`warm_batched_vs_sequential_ratio >= 1.0`.

Section 2 (early exit): both paths now run the shared round engine
(core/engine.py) whose while_loop predicate is "any live instance below
m_max" — a converged fleet at the default tol/patience must exit before its
m_max budget instead of burning fixed-length-scan rounds.

Section 3 (--solver axis): the ALT hot loop's linear fixed points on the
propagation path (`neumann`, O(H V^2) hops) vs dense LU (O(V^3)), measured
as warm per-outer-round wall time on a V >= 64 fleet — the regime where the
LU cost dominates the control plane (ISSUE 2 / DESIGN.md section 10).

Section 4 (parity): Neumann-vs-LU objective agreement across all four
methods on the paper's four topologies.

Section 5 (partition axis): the stage-generic P sweep (DESIGN.md section
13) — the same IoT-tree control-plane workload at split depths P = 1..4
(each its own compiled K envelope), plus a mixed-P fleet padded with
phantom stages and solved as ONE compiled batch, verified against the
per-instance sequential path.

Section 6 (--shard axis): the engine over a real instance-axis mesh. Runs
whenever >= 2 devices are visible (CI simulates 8 CPU devices via
XLA_FLAGS=--xla_force_host_platform_device_count=8); measures warm
sharded-vs-unsharded throughput on a non-divisible batch (exercising the
pad-and-trim path) and enforces rtol 1e-5 parity plus the
`ShardPlan.output_sharded` guarantee. The throughput ratio is recorded for
trend visibility but not asserted: simulated host devices oversubscribe the
same cores, so the ratio only means something on real multi-chip hardware.

Section 7 (obs): the round-trace overhead budget (ISSUE 6). The engine's
trace buffers ride in the while_loop carry; this section measures warm
per-round wall time with tracing on vs off (interleaved best-of-N) and
asserts the traced solve stays within 5% of the untraced one, plus bitwise
identity of every solved output across the two settings.

`REPRO_FLEET_SECTIONS=engine,obs` (comma list of section names) runs a
subset; skipped sections are recorded as `{"skipped": true}` so
`benchmarks/run.py --check-trend` can refuse a partial baseline.

Checks enforced:
  * per-instance J equivalence between batched and sequential (rtol 1e-3)
  * >= 2x cold end-to-end batched speedup at batch >= 6 on CPU
  * warm batched/sequential ratio >= 1.0 at (B=12, V=64) (full tier only;
    the small tier records the ratio without asserting — B=6 at reduced
    round budgets is too noisy for a hard gate)
  * converged-fleet while_loop early exit (rounds executed < m_max)
  * >= 2x warm per-outer-round Neumann speedup over LU at V >= 64 on CPU
  * Neumann == LU objectives to rtol 1e-3 for all methods x topologies
  * mixed-P batched == sequential objectives to rtol 1e-3 (P in {1,2,3,4})
  * sharded == unsharded objectives to rtol 1e-5 with sharded outputs
    (when >= 2 devices are visible)
  * trace=True warm per-round wall time within 5% of trace=False, with
    bitwise-identical J/history/hosts/iters

The warm batched-vs-sequential throughput ratio — the ROADMAP item tracked
at ~0.65x through PR 9 and closed by the lane-chunked round layout — is
persisted as `warm_batched_vs_sequential_ratio` in BENCH_fleet.json.
"""
from __future__ import annotations

import argparse
import os
import time

import jax
import numpy as np

from benchmarks._knobs import pallas_knobs
from repro.core import SCENARIOS
from repro.fleet import METHODS, sample_fleet, solve_fleet, solve_sequential
from repro.fleet.generator import erdos_renyi, iot_hierarchy

_SMALL = bool(os.environ.get("SCALE_SMALL"))

BATCH = 6 if _SMALL else 12
SOLVE_KW = dict(m_max=3, t_phi=3) if _SMALL else dict(m_max=6, t_phi=5)
WARM_REPS = 3 if _SMALL else 7

# The headline batched-vs-sequential fleet (ISSUE 10): the acceptance regime
# (B=12, V=64). BOTH envelope axes are pinned to the native sizes (V=64,
# A=24) so the warm comparison measures the ENGINE LAYOUT and nothing else:
# with a heterogeneous fleet the batched side pays envelope padding the
# sequential side never sees (measured ~1.3x at apps 20-28 under an A=28
# envelope), which is a property of padding — covered by the inertness
# contract and envelope caps — not of the round body this section gates.
# The cold comparison still favors the fleet on compile count alone (one
# 12-lane program vs a compile plus twelve dispatch-heavy runs).
ENGINE_FLEET_KW = dict(
    seed=2026, n_range=(64, 64), apps_range=(24, 24),
    families=("erdos_renyi", "barabasi_albert"),
)

# Solver-axis workload: the acceptance regime (V >= 64).
SOLVER_V = 64
SOLVER_BATCH = 2 if _SMALL else 4
SOLVER_KW = dict(m_max=2 if _SMALL else 4, t_phi=5, patience=10)
SOLVER_REPS = 2 if _SMALL else 3


def _paired_warm(fn_a, fn_b) -> tuple[float, float]:
    """Medians of WARM_REPS warm wall times with the two sides interleaved.

    The sides alternate inside ONE measurement window: warm batched vs
    sequential sits near 1.0x, and on a shared host the slow drift between
    two back-to-back windows can exceed the margin under test, so timing
    side A's reps and then side B's reps skews the ratio by whatever the
    load did in between. Interleaving lands the drift on both medians.
    """
    times_a, times_b = [], []
    for _ in range(WARM_REPS):
        t0 = time.perf_counter()
        fn_a()
        times_a.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        fn_b()
        times_b.append(time.perf_counter() - t0)
    return float(np.median(times_a)), float(np.median(times_b))


def _bench_batched_vs_sequential(print_fn, solver: str) -> dict:
    fleet = sample_fleet(BATCH, **ENGINE_FLEET_KW)
    shapes = {(p.net.n_nodes, p.apps.n_apps) for p in fleet}
    kw = dict(solver=solver, **SOLVE_KW, **pallas_knobs())

    # --- fresh-ensemble (cold) end-to-end, sequential then batched ---------
    jax.clear_caches()
    t0 = time.time()
    seq = solve_sequential(fleet, **kw)
    t_seq_cold = time.time() - t0

    jax.clear_caches()
    t0 = time.time()
    res = solve_fleet(fleet, **kw)
    t_fleet_cold = time.time() - t0
    res2 = solve_fleet(fleet, **kw)

    # clear_caches before the fleet cold run also dropped the sequential
    # side's compiled programs — re-warm it (untimed) so both sides enter
    # the paired warm loop compiled.
    solve_sequential(fleet, **kw)
    t_seq_warm, t_fleet_warm = _paired_warm(
        lambda: solve_sequential(fleet, **kw),
        lambda: solve_fleet(fleet, **kw),
    )

    # --- equivalence guarantee --------------------------------------------
    for b, r in enumerate(seq):
        np.testing.assert_allclose(res.J[b], r.J, rtol=1e-3)
        np.testing.assert_allclose(res2.J[b], r.J, rtol=1e-3)

    cold_speedup = t_seq_cold / t_fleet_cold
    warm_speedup = t_seq_warm / t_fleet_warm
    out = {
        "batch": BATCH,
        "V": ENGINE_FLEET_KW["n_range"][1],
        "solver": solver,
        "block_apps": 1,
        "lane_chunk": "auto",
        "warm_reps": WARM_REPS,
        "distinct_shapes": len(shapes),
        # Through PR 9 this ratio tracked a ~0.65x warm gap (ROADMAP item);
        # the lane-chunked round layout closed it. Persisted as an explicit
        # top-level field so BENCH_fleet.json shows the trajectory
        # PR-over-PR instead of burying it in `warm.speedup`.
        "warm_batched_vs_sequential_ratio": round(warm_speedup, 3),
        # while_loop trips executed vs the m_max budget (engine early exit).
        "rounds_executed": int(res.rounds),
        "m_max": SOLVE_KW["m_max"],
        "cold": {
            "sequential_s": round(t_seq_cold, 2),
            "fleet_s": round(t_fleet_cold, 2),
            "sequential_inst_per_s": round(BATCH / t_seq_cold, 3),
            "fleet_inst_per_s": round(BATCH / t_fleet_cold, 3),
            "speedup": round(cold_speedup, 2),
        },
        "warm": {
            "sequential_s": round(t_seq_warm, 2),
            "fleet_s": round(t_fleet_warm, 2),
            "sequential_inst_per_s": round(BATCH / t_seq_warm, 3),
            "fleet_inst_per_s": round(BATCH / t_fleet_warm, 3),
            "speedup": round(warm_speedup, 2),
        },
    }
    print_fn(
        f"fleet,B={BATCH} V={out['V']} shapes={len(shapes)} solver={solver} "
        f"cold: seq={t_seq_cold:6.1f}s fleet={t_fleet_cold:6.1f}s "
        f"({out['cold']['fleet_inst_per_s']:.2f} inst/s) speedup={cold_speedup:.2f}x"
    )
    print_fn(
        f"fleet,B={BATCH} warm (paired median of {WARM_REPS}): seq={t_seq_warm:6.2f}s "
        f"fleet={t_fleet_warm:6.2f}s "
        f"({out['warm']['fleet_inst_per_s']:.2f} inst/s) ratio={warm_speedup:.2f}x"
    )
    print_fn(
        f"fleet,B={BATCH} engine rounds={res.rounds}/{SOLVE_KW['m_max']} "
        f"(while_loop early exit saves {SOLVE_KW['m_max'] - res.rounds} rounds)"
    )
    assert BATCH >= 6
    assert cold_speedup >= 2.0, (
        f"fleet engine must be >= 2x faster end-to-end on a fresh ensemble "
        f"(got {cold_speedup:.2f}x)"
    )
    if not _SMALL:
        assert warm_speedup >= 1.0, (
            f"warm batched/sequential ratio regressed below parity at "
            f"(B={BATCH}, V={out['V']}): {warm_speedup:.3f}x — the "
            f"lane-chunked round layout (lane_chunk auto) is supposed to "
            f"keep the batched engine at least sequential-rate warm"
        )
    return out


def _bench_early_exit(print_fn) -> dict:
    """Engine while_loop early exit: a converged B=12 fleet at the default
    tol/patience must execute fewer outer rounds than its m_max budget
    (the old fixed-length scan always burned all m_max rounds)."""
    batch = 6 if _SMALL else 12
    m_max = 30
    fleet = sample_fleet(batch, seed=7)
    res = solve_fleet(fleet, m_max=m_max, t_phi=5)  # default tol/patience
    print_fn(
        f"fleet,early-exit B={batch} m_max={m_max} rounds={res.rounds} "
        f"iters={res.iters.min()}-{res.iters.max()}"
    )
    assert res.rounds < m_max, (
        f"converged fleet must exit the while_loop before m_max "
        f"({res.rounds} vs {m_max})"
    )
    assert res.rounds == int(res.iters.max())
    return {"batch": batch, "m_max": m_max, "rounds_executed": int(res.rounds)}


def _bench_solver_axis(print_fn) -> dict:
    """Warm per-outer-round cost of the two fixed-point solvers at V >= 64."""
    fleet = [erdos_renyi(SOLVER_V, 12, seed=s) for s in range(SOLVER_BATCH)]
    rounds = SOLVER_KW["m_max"]
    skw = dict(**SOLVER_KW, **pallas_knobs())
    per_round = {}
    J = {}
    for solver in ("neumann", "lu"):
        solve_fleet(fleet, solver=solver, **skw)  # compile + warm
        best = np.inf
        for _ in range(SOLVER_REPS):
            t0 = time.time()
            res = solve_fleet(fleet, solver=solver, **skw)
            best = min(best, time.time() - t0)
        per_round[solver] = best / rounds
        J[solver] = np.asarray(res.J)
        print_fn(
            f"fleet,solver={solver:8s} V={SOLVER_V} B={SOLVER_BATCH} "
            f"warm={best:.3f}s  per-round={per_round[solver] * 1e3:.1f}ms"
        )
    speedup = per_round["lu"] / per_round["neumann"]
    np.testing.assert_allclose(J["neumann"], J["lu"], rtol=1e-3)
    hop_bound = fleet[0].hop_bound
    print_fn(
        f"fleet,solver-axis V={SOLVER_V} hop_bound={hop_bound} "
        f"warm per-round speedup neumann/lu = {speedup:.2f}x"
    )
    assert speedup >= 2.0, (
        f"neumann must be >= 2x faster per warm outer round than LU at "
        f"V={SOLVER_V} (got {speedup:.2f}x)"
    )
    return {
        "V": SOLVER_V,
        "batch": SOLVER_BATCH,
        "hop_bound": hop_bound,
        "per_round_ms": {k: round(v * 1e3, 2) for k, v in per_round.items()},
        "warm_per_round_speedup": round(speedup, 2),
    }


def _bench_solver_parity(print_fn) -> dict:
    """Neumann-vs-LU objective parity: 4 methods x 4 paper topologies."""
    fleet = [make() for make in SCENARIOS.values()]
    kw = dict(m_max=3 if _SMALL else 6, t_phi=5, **pallas_knobs())
    out = {}
    for method in METHODS:
        Js = {}
        for solver in ("neumann", "lu"):
            res = solve_fleet(fleet, method=method, solver=solver, **kw)
            Js[solver] = np.asarray(res.J)
        np.testing.assert_allclose(Js["neumann"], Js["lu"], rtol=1e-3)
        rel = np.max(
            np.abs(Js["neumann"] - Js["lu"]) / np.maximum(np.abs(Js["lu"]), 1e-30)
        )
        out[method] = {"max_rel_diff": float(rel)}
        print_fn(
            f"fleet,parity method={method:12s} scenarios={list(SCENARIOS)} "
            f"max|J_ne - J_lu|/J_lu = {rel:.2e}  (rtol 1e-3 OK)"
        )
    return out


def _bench_partition_axis(print_fn) -> dict:
    """The new P axis: per-depth warm solve cost and the mixed-P padded
    batch (the ISSUE 5 tentpole's user-visible payoff)."""
    p_set = (1, 2, 3, 4)
    batch = 3 if _SMALL else 6
    kw = dict(m_max=2 if _SMALL else 4, t_phi=4, **pallas_knobs())

    def depth_fleet(p):
        return [
            iot_hierarchy(seed=s, n_edge=4, devices_per_edge=3, n_apps=8,
                          n_parts=p)
            for s in range(batch)
        ]

    per_p = {}
    for p in p_set:
        fleet = depth_fleet(p)
        solve_fleet(fleet, **kw)  # compile + warm
        t0 = time.time()
        res = solve_fleet(fleet, **kw)
        warm = time.time() - t0
        per_p[str(p)] = {
            "warm_s": round(warm, 3),
            "J_med": round(float(np.median(res.J)), 3),
            "rounds_executed": int(res.rounds),
        }
        print_fn(
            f"fleet,partitions P={p} K={p + 1} B={batch} warm={warm:.2f}s "
            f"J_med={per_p[str(p)]['J_med']:.2f}"
        )

    # Mixed-P fleet: one padded batch vs the per-instance sequential path.
    mixed = sample_fleet(batch * 2, seed=2028, partitions=(1, 2, 3, 4))
    res = solve_fleet(mixed, **kw)
    seq = solve_sequential(mixed, **kw)
    for b, r in enumerate(seq):
        np.testing.assert_allclose(res.J[b], r.J, rtol=1e-3)
    k_env = res.hosts.shape[-1] + 1
    print_fn(
        f"fleet,partitions mixed-P B={len(mixed)} K_env={k_env} "
        f"rounds={res.rounds} (one compiled padded batch; == sequential "
        f"rtol 1e-3)"
    )
    return {
        "per_p": per_p,
        "mixed": {
            "batch": len(mixed),
            "k_envelope": k_env,
            "rounds_executed": int(res.rounds),
            "matches_sequential": True,
        },
    }


def _bench_shard_axis(print_fn) -> dict:
    """The engine over a real instance-axis mesh: parity + layout guarantees
    on a non-divisible batch, warm throughput recorded for trend context."""
    n_dev = len(jax.devices())
    if n_dev < 2:
        print_fn(
            "fleet,shard skipped: 1 device visible (set "
            "XLA_FLAGS=--xla_force_host_platform_device_count=8)"
        )
        return {"n_devices": 1, "skipped": True}
    batch = BATCH if BATCH % n_dev else BATCH + 1  # force pad-and-trim
    fleet = sample_fleet(batch, seed=2027)
    kw = dict(**SOLVE_KW)

    res_u = solve_fleet(fleet, **kw)  # compile + warm, unsharded
    res_s = solve_fleet(fleet, shard=True, **kw)
    t0 = time.time()
    res_u = solve_fleet(fleet, **kw)
    t_warm_u = time.time() - t0
    t0 = time.time()
    res_s = solve_fleet(fleet, shard=True, **kw)
    t_warm_s = time.time() - t0

    np.testing.assert_allclose(res_s.J, res_u.J, rtol=1e-5)
    assert res_s.shard.sharded and res_s.shard.output_sharded, res_s.shard
    assert res_s.shard.padded_batch % n_dev == 0
    assert res_s.n_instances == batch

    ratio = t_warm_u / t_warm_s
    out = {
        "n_devices": n_dev,
        "batch": batch,
        "padded_batch": res_s.shard.padded_batch,
        "warm_unsharded_s": round(t_warm_u, 3),
        "warm_sharded_s": round(t_warm_s, 3),
        # NOT trend-linted (key avoids 'ratio'/'speedup'): on a simulated
        # host-device mesh all shards share the same cores, so this is a
        # sanity readout, not a performance claim.
        "warm_sharded_vs_unsharded_x": round(ratio, 3),
    }
    print_fn(
        f"fleet,shard n_dev={n_dev} B={batch}->"
        f"{res_s.shard.padded_batch} warm: unsharded={t_warm_u:.2f}s "
        f"sharded={t_warm_s:.2f}s ({ratio:.2f}x)  parity rtol 1e-5 OK"
    )
    return out


def _bench_obs(print_fn) -> dict:
    """Round-trace overhead budget: tracing must be (close to) free.

    The trace buffers are written by the same masked dynamic-column updates
    as the J history and never read inside the loop, so the compiled-loop
    cost is a handful of [B] stores per round. Warm per-round wall times are
    measured interleaved (best-of-N each) to cancel drift; the acceptance
    bound is 5% relative plus a 1 ms/round absolute grace so CPU timer noise
    on a fast loop cannot flake the bench."""
    fleet = [erdos_renyi(SOLVER_V, 12, seed=100 + s) for s in range(SOLVER_BATCH)]
    kw = dict(**SOLVER_KW)
    rounds = kw["m_max"]
    reps = 5

    res_on = solve_fleet(fleet, trace=True, **kw)  # compile both variants
    res_off = solve_fleet(fleet, trace=False, **kw)

    # --- tracing must not change a single bit of the solved result --------
    assert res_off.trace is None and res_on.trace is not None
    assert np.array_equal(res_on.J, res_off.J)
    assert np.array_equal(res_on.history, res_off.history, equal_nan=True)
    assert np.array_equal(res_on.hosts, res_off.hosts)
    assert np.array_equal(res_on.iters, res_off.iters)
    # The trace's NaN mask IS the history's freeze mask.
    assert np.array_equal(
        np.isnan(res_on.trace.J_comm), np.isnan(res_on.history)
    )

    best = {True: np.inf, False: np.inf}
    for _ in range(reps):
        for traced in (True, False):
            t0 = time.time()
            solve_fleet(fleet, trace=traced, **kw)
            best[traced] = min(best[traced], time.time() - t0)
    per_round_on = best[True] / rounds
    per_round_off = best[False] / rounds
    overhead = per_round_on / per_round_off - 1.0
    print_fn(
        f"fleet,obs V={SOLVER_V} B={SOLVER_BATCH} warm per-round: "
        f"traced={per_round_on * 1e3:.1f}ms untraced={per_round_off * 1e3:.1f}ms "
        f"overhead={overhead * 100:+.1f}%  bitwise-identical OK"
    )
    assert per_round_on <= per_round_off * 1.05 + 1e-3, (
        f"round-trace overhead budget blown: traced {per_round_on * 1e3:.2f}"
        f"ms/round vs untraced {per_round_off * 1e3:.2f}ms/round "
        f"({overhead * 100:+.1f}%, budget 5%)"
    )
    return {
        "V": SOLVER_V,
        "batch": SOLVER_BATCH,
        "per_round_traced_ms": round(per_round_on * 1e3, 2),
        "per_round_untraced_ms": round(per_round_off * 1e3, 2),
        # Keep the key clear of 'ratio'/'speedup'/'per_round' so the trend
        # lint never flags timer noise on a bounded-by-assert quantity.
        "trace_overhead_frac": round(max(overhead, 0.0), 4),
        "mean_churn_per_round": round(res_on.trace.mean_churn(), 3),
        "bitwise_identical": True,
    }


def _bench_chaos(print_fn) -> dict:
    """Section 8 (fault-injection control plane, DESIGN.md section 15).

    A seeded fault trace (>= 5 node failures, >= 3 link degradations, 1
    flash crowd at full scale) over the IoT-tree fleet, driven through
    `launch.control.run_control` with warm-started re-solves. Asserted:
    every epoch feasible (no live partition on a dead node, finite J), zero
    non-finite epochs, and warm event-epochs re-solve in <= 0.5x the engine
    rounds of the matching solve-from-scratch (compare_cold) — the
    warm-start carry + freeze-mask machinery actually earning its keep
    under adversity. `warm/cold_rounds_executed` are trend-linted as
    machine-portable convergence metrics (lower is better)."""
    from repro.chaos import generate_trace
    from repro.launch.control import run_control

    epochs = 16 if _SMALL else 50
    batch = 6
    fleet = [
        iot_hierarchy(seed=40 + s, n_edge=4, devices_per_edge=3, n_apps=8)
        for s in range(batch)
    ]
    n_fail, n_deg, n_crowd = (3, 2, 1) if _SMALL else (5, 3, 1)
    trace = generate_trace(
        fleet, epochs, seed=4096, node_failures=n_fail,
        link_degradations=n_deg, flash_crowds=n_crowd,
    )
    counts = trace.counts()
    assert counts["node_down"] >= n_fail
    assert counts["link_degrade"] >= n_deg
    assert counts["flash_crowd"] >= n_crowd

    t0 = time.time()
    ctl = run_control(
        fleet, trace=trace, m_max=20, t_phi=5, round_to=8,
        compare_cold=True,
    )
    wall = time.time() - t0
    s = ctl.summary()

    assert s["feasible_fraction"] == 1.0, (
        f"chaos: {s['infeasible_epochs']} infeasible epochs"
    )
    assert s["nonfinite_epochs"] == 0, (
        f"chaos: {s['nonfinite_epochs']} epochs with non-finite J"
    )
    warm_r = s["warm_rounds_executed"]
    cold_r = s.get("cold_rounds_executed", 0.0)
    assert cold_r > 0, "chaos: compare_cold produced no baseline epochs"
    frac = warm_r / cold_r
    assert frac <= 0.5, (
        f"chaos: warm event-epochs averaged {warm_r:.2f} engine rounds vs "
        f"{cold_r:.2f} from scratch ({frac:.2f}x > 0.50x budget)"
    )
    print_fn(
        f"fleet,chaos B={batch} epochs={epochs} "
        f"events[down={counts['node_down']} degrade={counts['link_degrade']} "
        f"crowd={counts['flash_crowd']}] feasible=100% "
        f"warm={warm_r:.1f} vs cold={cold_r:.1f} rounds ({frac:.2f}x) "
        f"fallback={s['fallback_rate']:.0%} "
        f"p95-recovery={s['p95_recovery_latency_s'] * 1e3:.0f}ms"
    )
    return {
        "batch": batch,
        "epochs": epochs,
        "event_counts": counts,
        "feasible_fraction": s["feasible_fraction"],
        "nonfinite_epochs": s["nonfinite_epochs"],
        "fallback_rate": s["fallback_rate"],
        "warm_rounds_executed": warm_r,
        "cold_rounds_executed": cold_r,
        # Bounded by the assert above; key avoids 'ratio' so the trend lint
        # does not treat lower-is-better as a regression direction error.
        "warm_vs_cold_rounds_frac": round(frac, 3),
        "p95_recovery_latency_s": s["p95_recovery_latency_s"],
        "epochs_per_s": round(epochs / wall, 3),
    }


def _bench_pareto(print_fn) -> dict:
    """Section 9 (split-point Pareto search, DESIGN.md section 17).

    A compact end-to-end split search — candidate enumeration, mixed-P
    phantom padding, ONE batched solve, front extraction with dominance
    hard gates — so BENCH_fleet.json tracks the fleet engine's first
    at-scale batch consumer (`candidates_per_s`, higher is better) next to
    the engine sections it stresses. The full-scale search over the whole
    zoo lives in BENCH_pareto.json (benchmarks/pareto_bench.py)."""
    from benchmarks.pareto_bench import sweep_section

    return sweep_section(
        print_fn,
        archs=("qwen1.5-0.5b", "hymba-1.5b"),
        topologies=("iot",),
        max_per_p=4 if _SMALL else 8,
        m_max=SOLVE_KW["m_max"],
        t_phi=SOLVE_KW["t_phi"],
        min_per_cell=20 if _SMALL else 50,
    )


SECTIONS_ENV = "REPRO_FLEET_SECTIONS"


def run(print_fn=print, solver: str = "neumann") -> dict:
    sections = {
        "engine": lambda: _bench_batched_vs_sequential(print_fn, solver),
        "early_exit": lambda: _bench_early_exit(print_fn),
        "solver_axis": lambda: _bench_solver_axis(print_fn),
        "solver_parity": lambda: _bench_solver_parity(print_fn),
        "partition_axis": lambda: _bench_partition_axis(print_fn),
        "shard_axis": lambda: _bench_shard_axis(print_fn),
        "obs": lambda: _bench_obs(print_fn),
        "chaos": lambda: _bench_chaos(print_fn),
        "pareto": lambda: _bench_pareto(print_fn),
    }
    requested = os.environ.get(SECTIONS_ENV)
    if requested:
        want = {s.strip() for s in requested.split(",") if s.strip()}
        unknown = want - sections.keys()
        if unknown:
            raise ValueError(
                f"{SECTIONS_ENV} names unknown sections {sorted(unknown)}; "
                f"known: {sorted(sections)}"
            )
    else:
        want = set(sections)
    out = {}
    for name, fn in sections.items():
        if name in want:
            out[name] = fn()
        else:
            # An explicit marker, not an omission: --check-trend refuses to
            # baseline against a section that never ran.
            out[name] = {"skipped": True}
            print_fn(f"fleet,{name} skipped ({SECTIONS_ENV})")
    return out


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--solver",
        choices=("neumann", "lu"),
        default="neumann",
        help="fixed-point solver for the batched-vs-sequential section "
        "(the solver-axis section always measures both)",
    )
    ap.add_argument(
        "--shard",
        action="store_true",
        help="run ONLY the shard-axis section (multi-device smoke)",
    )
    args = ap.parse_args()
    if args.shard:
        _bench_shard_axis(print)
        return 0
    run(solver=args.solver)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
