"""Per-phase round profile: where one ALT round actually spends its time.

The engine's round body is a single fused device program (placement sweep ->
T_phi forwarding sweeps -> round_eval), so host spans around `solve_fleet`
can never say which *phase* dominates. This module re-runs the three phases
as separately-jitted vmapped programs over the same stacked fleet and warm
state, timing each one (best-of-N, blocked on the outputs) under the obs
host spans

    round.placement   round.forwarding   round.round_eval

so the numbers land in any configured trace (REPRO_TRACE) next to the
solve-level spans, and `benchmarks/fleet_bench.py` can persist them as the
`phases` section of BENCH_fleet.json.

One honest caveat, stated here because the split drove a design decision
(DESIGN.md section 18): phase times measured as separate dispatches bound
the fused round body from above — XLA fuses across phase boundaries inside
the engine loop — so treat the split as a dominance profile, not an exact
decomposition. It is how we established that the placement sweep is a few
percent of the round and forwarding dominates.
"""
from __future__ import annotations

import time

import jax

from .trace import span

PHASES = ("placement", "forwarding", "round_eval")


def _best_of(fn, reps: int) -> float:
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def profile_round_phases(
    problems,
    *,
    t_phi: int,
    alpha: float = 0.5,
    colocate: bool = False,
    use_pallas: bool = False,
    interpret: bool = False,
    solver: str = "neumann",
    block_apps: int = 1,
    round_to: int = 1,
    reps: int = 3,
) -> dict:
    """Time each round phase over a stacked fleet at a warm round-1 state.

    The state driven through the phases is the one round 1 of the engine
    would see (structured init + one evaluation), so the profile reflects
    the real in-loop tensor shapes and placement churn. Returns per-phase
    warm best-of-`reps` milliseconds plus the share of their sum:

        {"batch", "t_phi", "block_apps",
         "placement_ms", "forwarding_ms", "round_eval_ms",
         "placement_share", "forwarding_share", "round_eval_share"}
    """
    # Imported here, not at module top: obs is a leaf package the solver
    # layers import freely, so pulling core/fleet in at import time would
    # close a cycle (fleet -> obs -> fleet) that only resolves by luck of
    # initialization order.
    from repro.core.forwarding import forwarding_update
    from repro.core.marginals import round_eval
    from repro.core.placement import placement_update, structured_init
    from repro.fleet.pad import stack_problems

    stacked, _ = stack_problems(problems, round_to=round_to)

    @jax.jit
    def init(p):
        def one(q):
            s = structured_init(
                q, colocate=colocate, use_pallas=use_pallas,
                interpret=interpret,
            )
            J, aux = round_eval(
                q, s, solver=solver, use_pallas=use_pallas,
                interpret=interpret,
            )
            return s, aux["ctg"]

        return jax.vmap(one)(p)

    state, ctg = jax.block_until_ready(init(stacked))

    place = jax.jit(
        jax.vmap(
            lambda p, s, c: placement_update(
                p, s, c, colocate=colocate, use_pallas=use_pallas,
                interpret=interpret, solver=solver, block_apps=block_apps,
            )
        )
    )
    fwd = jax.jit(
        jax.vmap(
            lambda p, s: forwarding_update(
                p, s, t_phi=t_phi, alpha=alpha, solver=solver,
                use_pallas=use_pallas, interpret=interpret,
            )
        )
    )
    ev = jax.jit(
        jax.vmap(
            lambda p, s: round_eval(
                p, s, solver=solver, use_pallas=use_pallas,
                interpret=interpret,
            )
        )
    )

    placed = jax.block_until_ready(place(stacked, state, ctg))  # compile
    forwarded = jax.block_until_ready(fwd(stacked, placed))
    jax.block_until_ready(ev(stacked, forwarded))

    times = {}
    with span("round.phases", batch=len(problems), block_apps=block_apps):
        with span("round.placement", block_apps=block_apps):
            times["placement"] = _best_of(
                lambda: place(stacked, state, ctg), reps
            )
        with span("round.forwarding", t_phi=t_phi):
            times["forwarding"] = _best_of(
                lambda: fwd(stacked, placed), reps
            )
        with span("round.round_eval"):
            times["round_eval"] = _best_of(
                lambda: ev(stacked, forwarded), reps
            )

    total = sum(times.values())
    out = {
        "batch": len(problems),
        "t_phi": t_phi,
        "block_apps": block_apps,
    }
    for k in PHASES:
        out[f"{k}_ms"] = round(times[k] * 1e3, 3)
        out[f"{k}_share"] = round(times[k] / total, 4)
    return out
