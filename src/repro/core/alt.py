"""Algorithm 1 (ALT) and the paper's three baselines (section IV).

  ALT         alternating congestion-aware placement + forwarding (ours)
  OneShot     same init/objective, a single placement/forwarding round
  CongUnaware shortest extended path under linear (congestion-blind) costs
  CoLocated   all partitions forced to one node, forwarding optimized

All four share the structured initialization so comparisons isolate exactly
one design axis each (alternation / congestion awareness / split flexibility).

The iterative methods (ALT, OneShot, CoLocated) are thin wrappers over the
shared device-resident round engine (core/engine.py): the whole alternating
loop — placement sweep fed by the previous round's `round_eval`, T_phi
forwarding sweeps, best-iterate tracking, tol/patience stall logic — runs as
ONE jitted `lax.while_loop` at B=1 and exits the moment the instance stalls.
There is no per-round host sync any more: the only device->host transfer is
the final result read-out. The batched fleet solver (fleet/solve.py) runs
the exact same engine at B>1, so sequential and fleet can never diverge.

`solver` selects the fixed-point path: "neumann" (default, hop-capped
propagation) or "lu" (dense reference). See DESIGN.md sections 10-11.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .engine import engine_solve_single
from .flow import objective
from .placement import structured_init
from .structs import CostModel, Problem, State


@dataclasses.dataclass
class Result:
    name: str
    state: State
    J: float
    J_comm: float
    J_comp: float
    history: list
    iters: int

    def summary(self) -> str:
        return (
            f"{self.name:12s} J={self.J:10.4f}  comm={self.J_comm:10.4f} "
            f"comp={self.J_comp:10.4f}  iters={self.iters}"
        )


def _result(problem, state, aux, name, history, iters) -> Result:
    return Result(
        name=name,
        state=state,
        J=float(aux["J"]),
        J_comm=float(aux["J_comm"]),
        J_comp=float(aux["J_comp"]),
        history=[float(h) for h in history],
        iters=iters,
    )


def _engine_result(problem: Problem, name: str, **engine_kw) -> Result:
    """Run the shared round engine at B=1 and package a sequential Result."""
    out = engine_solve_single(problem, **engine_kw)
    history = np.asarray(out["history"])
    history = history[~np.isnan(history)]
    return Result(
        name=name,
        state=out["state"],
        J=float(out["J"]),
        J_comm=float(out["J_comm"]),
        J_comp=float(out["J_comp"]),
        history=[float(h) for h in history],
        iters=int(out["iters"]),
    )


def solve_alt(
    problem: Problem,
    *,
    m_max: int = 30,
    t_phi: int = 10,
    alpha: float = 0.5,
    tol: float = 1e-3,
    patience: int = 4,
    colocate: bool = False,
    use_pallas: bool = False,
    interpret: bool = False,
    solver: str = "neumann",
    block_apps: int = 1,
    name: str = "ALT",
) -> Result:
    """The full alternating method (Algorithm 1), with best-iterate tracking.

    One outer round = placement reassignment under the current congested
    marginals, then T_phi forwarding sweeps (a cyclic rotation of Algorithm
    1's line order so J is always measured on smoothed routing). Terminates
    when the best J stops improving by tol for `patience` rounds — via the
    engine's batch-wide early exit, which at B=1 is exactly the sequential
    per-instance break.
    """
    return _engine_result(
        problem,
        name,
        m_max=m_max,
        t_phi=t_phi,
        alpha=alpha,
        tol=tol,
        patience=patience,
        colocate=colocate,
        track_best=True,
        use_pallas=use_pallas,
        interpret=interpret,
        solver=solver,
        block_apps=block_apps,
    )


def solve_oneshot(
    problem: Problem,
    *,
    t_phi: int = 10,
    alpha: float = 0.5,
    use_pallas: bool = False,
    interpret: bool = False,
    solver: str = "neumann",
    block_apps: int = 1,
) -> Result:
    """One placement/forwarding round: isolates the value of alternation.

    The engine at m_max=1 with `track_best=False` (the final — i.e. only —
    iterate is returned, matching the historical OneShot semantics)."""
    return _engine_result(
        problem,
        "OneShot",
        m_max=1,
        t_phi=t_phi,
        alpha=alpha,
        tol=1e-3,
        patience=1,
        colocate=False,
        track_best=False,
        use_pallas=use_pallas,
        interpret=interpret,
        solver=solver,
        block_apps=block_apps,
    )


def linearize(problem: Problem) -> Problem:
    """The same problem under congestion-blind linear costs (D=F/mu, C=G/nu).

    Shared by the sequential and fleet CongUnaware baselines so their
    linearization can never diverge."""
    return Problem(
        net=problem.net,
        apps=problem.apps,
        cost=CostModel(
            kind="linear",
            rho_max=problem.cost.rho_max,
            w_comm=problem.cost.w_comm,
            w_comp=problem.cost.w_comp,
        ),
        hop_bound=problem.hop_bound,
    )


def solve_congunaware(
    problem: Problem,
    *,
    use_pallas: bool = False,
    interpret: bool = False,
    solver: str = "neumann",
) -> Result:
    """Shortest extended path under linear costs, evaluated with true costs.

    Implementation note: with linear costs the zero-load marginals ARE the
    link weights (D' = 1/mu, C' = 1/nu constants), so the extended-graph
    shortest path over the stage-copy / partition-transition chain reduces
    exactly to the structured initialization's stage DP under the linear
    cost model (any partition count — DESIGN.md section 13).
    """
    state = structured_init(
        linearize(problem), use_pallas=use_pallas, interpret=interpret
    )
    J, aux = objective(
        problem, state, solver=solver, use_pallas=use_pallas,
        interpret=interpret,
    )
    return _result(problem, state, aux, "CongUnaware", [], 0)


def solve_colocated(
    problem: Problem,
    *,
    m_max: int = 30,
    t_phi: int = 10,
    alpha: float = 0.5,
    tol: float = 1e-3,
    patience: int = 4,
    use_pallas: bool = False,
    interpret: bool = False,
    solver: str = "neumann",
    block_apps: int = 1,
) -> Result:
    """All partitions at a single node; forwarding still congestion-aware."""
    return solve_alt(
        problem,
        m_max=m_max,
        t_phi=t_phi,
        alpha=alpha,
        tol=tol,
        patience=patience,
        colocate=True,
        use_pallas=use_pallas,
        interpret=interpret,
        solver=solver,
        block_apps=block_apps,
        name="CoLocated",
    )


ALL_METHODS = {
    "ALT": solve_alt,
    "OneShot": solve_oneshot,
    "CongUnaware": solve_congunaware,
    "CoLocated": solve_colocated,
}

# The one shared source of truth for which solver kwargs each method accepts.
# `compare_all` and the fleet's `solve_sequential` both filter through this,
# so the sequential and fleet baselines cannot drift apart by hand-copied
# per-method defaults (the pre-PR-3 bug: `m_max` was forwarded to CoLocated
# but `tol`/`patience` were not).
METHOD_KWARGS = {
    "ALT": (
        "m_max", "t_phi", "alpha", "tol", "patience", "use_pallas",
        "interpret", "solver", "block_apps",
    ),
    "OneShot": (
        "t_phi", "alpha", "use_pallas", "interpret", "solver", "block_apps",
    ),
    # CongUnaware runs no placement sweep (structured init only), so the
    # sweep-schedule knob does not apply to it.
    "CongUnaware": ("use_pallas", "interpret", "solver"),
    "CoLocated": (
        "m_max", "t_phi", "alpha", "tol", "patience", "use_pallas",
        "interpret", "solver", "block_apps",
    ),
}


def validate_solver_kwargs(kw: dict) -> None:
    """Reject kwargs no method accepts — a typo must raise, never silently
    run with defaults."""
    unknown = set(kw) - set().union(*METHOD_KWARGS.values())
    if unknown:
        raise TypeError(f"unknown solver kwargs {sorted(unknown)}")


def method_kwargs(method: str, kw: dict) -> dict:
    """Restrict one shared (validated) kwargs dict to what `method` accepts."""
    validate_solver_kwargs(kw)
    return {k: v for k, v in kw.items() if k in METHOD_KWARGS[method]}


def compare_all(problem: Problem, **kw) -> dict:
    """Run all four methods on one shared kwargs dict.

    Unknown kwargs raise (they would previously have been silently dropped
    for every method but ALT)."""
    return {
        name: fn(problem, **method_kwargs(name, kw))
        for name, fn in ALL_METHODS.items()
    }
