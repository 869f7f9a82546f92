"""Gallager-style congestion-aware forwarding update (paper Eq. 11).

Each sweep moves forwarding mass at every (application, stage, node) away from
high-marginal-cost out-links toward the minimum-marginal-cost out-link j*.
The paper's Eq. (11) uses an absolute step alpha * (delta_ij - delta_min); in
the deeply congested regime the marginals are enormous (quadratic-extension
slopes), so any absolute step overshoots and flaps. We use the
scale-invariant relative form (the paper defers exact scheduling to [9],[11];
recorded in DESIGN.md section 8):

    rate_ij = alpha * (delta_ij - delta_min) / (|delta_min| + delta_ij - delta_min)
    phi_ij <- phi_ij * (1 - rate_ij)                      (j != j*)
    phi_ij* <- mass_i - sum_{j != j*} phi_ij

so at an equalized optimum (gap = 0 on active links) the update is a no-op,
and mass drains geometrically — no overshoot, no renormalization guard.

Loop-freedom ("node-blocking mechanism"): out-links with q_j >= q_i
("improper" links) are drained at the maximal rate alpha instead of receiving
the Eq.-11 step. Why this keeps the flow solve well-posed:

  * the argmin link always has q_{j*} < q_i (q_i is a phi-weighted average of
    delta_ij >= delta_min = L D'_{i j*} + q_{j*} > q_{j*} since D' > 0), so
    mass always has a proper link to go to;
  * any directed cycle in the phi-support must contain >= 1 improper link
    (q strictly decreases along proper links), and improper links shrink
    geometrically, so every cycle's gain stays < 1 and (I - Phi^T) remains
    invertible (Neumann series converges).

The whole sweep is dense and vectorized over (A, K, V) — the TPU-native
reshaping of the per-node distributed update (DESIGN.md section 3).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .marginals import link_marginals
from .structs import BIG_THRESHOLD, Problem, State, forwarding_mass

_PRUNE = 1e-9  # forwarding fractions below this are swept into j*


@functools.partial(
    jax.jit, static_argnames=("alpha", "solver", "use_pallas", "interpret")
)
def forwarding_sweep(
    problem: Problem,
    state: State,
    alpha: float = 0.5,
    *,
    solver: str = "neumann",
    use_pallas: bool = False,
    interpret: bool = False,
    mass: jax.Array | None = None,
) -> State:
    """One full congestion-aware forwarding sweep (all apps/stages/nodes).

    `mass` (the per-node emission totals, Eq. 2) depends only on the
    placement x and the destinations — both fixed across the T_phi inner
    sweeps — so `forwarding_update` computes it once and passes it in;
    standalone callers may omit it.
    """
    n = problem.net.n_nodes
    delta, aux = link_marginals(
        problem, state, solver=solver, use_pallas=use_pallas,
        interpret=interpret,
    )  # [A, K, V, V]
    q = aux["q"]

    if mass is None:
        mass = forwarding_mass(state, problem.apps, n)  # [A, K, V]

    delta_min = jnp.min(delta, axis=-1, keepdims=True)  # [A, K, V, 1]
    jstar = jnp.argmin(delta, axis=-1)  # [A, K, V]
    jstar_oh = jax.nn.one_hot(jstar, n, dtype=state.phi.dtype)

    edge = delta < BIG_THRESHOLD
    gap = jnp.where(edge, delta - delta_min, 0.0)
    rel = gap / (jnp.abs(delta_min) + gap + 1e-12)
    rate = alpha * rel

    # Blocking: improper links (q_j >= q_i) drain at the maximal rate.
    q_i = q[..., :, None]
    q_j = q[..., None, :]
    improper = ~(q_j < q_i)
    rate = jnp.where(improper, alpha, rate)

    phi = state.phi * (1.0 - rate)
    phi = jnp.where(phi < _PRUNE, 0.0, phi)

    # Re-assign the freed mass to j*.
    phi = phi * (1.0 - jstar_oh)
    others = jnp.sum(phi, axis=-1)
    phi = phi + jstar_oh * jnp.maximum(mass - others, 0.0)[..., None]

    return State(x=state.x, phi=phi)


@functools.partial(
    jax.jit,
    static_argnames=("t_phi", "alpha", "solver", "use_pallas", "interpret"),
)
def forwarding_update(
    problem: Problem,
    state: State,
    *,
    t_phi: int = 8,
    alpha: float = 0.5,
    solver: str = "neumann",
    use_pallas: bool = False,
    interpret: bool = False,
) -> State:
    """T_phi inner forwarding sweeps (the paper's forwarding subproblem 8).

    A fori_loop rather than a Python loop so the update stays a single XLA
    while-op when embedded in outer lax.scan bodies (the batched fleet
    solver traces this once per outer round, not t_phi times). The emission
    mass is hoisted out of the loop: it changes only when x or the absorbed
    (destination) mass changes, never across forwarding micro-steps.
    """
    mass = forwarding_mass(state, problem.apps, problem.net.n_nodes)

    def body(_, s):
        return forwarding_sweep(
            problem, s, alpha=alpha, solver=solver, use_pallas=use_pallas,
            interpret=interpret, mass=mass,
        )

    return jax.lax.fori_loop(0, t_phi, body, state)
