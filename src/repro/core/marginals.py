"""Downstream marginal costs q_i^{a,k} and link marginals delta (Eq. 10).

Gallager's cost-to-go q summarizes the marginal increase of the whole-system
cost per extra unit of stage-k traffic injected at node i, under the current
forwarding state. For the final stage (k = parts_a, toward the destination):

  q^{a,K-1}_i = sum_j phi^{a,K-1}_{ij} (L_{a,K-1} D'_{ij} + q^{a,K-1}_j)

and for every earlier stage, the partition-(k+1) host absorbs the stage,
pays the computation marginal kappa, and re-injects the next stage locally:

  q^{a,k}_i = sum_j phi^{a,k}_{ij} (L_{a,k} D'_{ij} + q^{a,k}_j)
              + x^{a,k+1}_i (kappa^{a,k+1}_i + q^{a,k+1}_i)

Each line is a linear fixed point (I - Phi) q = c, solved batched over
applications on the same propagation path as the traffic solve (DESIGN.md
sections 3 and 10; `solver="lu"` keeps the dense reference). The backward
chain is a *reversed* `lax.scan` over the stage axis — the mirror image of
flow.py's forward scan — so the partition count P stays per-`Problem` data
(DESIGN.md section 13). Phantom stages (k > parts) have phi = 0, kappa = 0
and gate 0, so their cost-to-go is exactly zero and the real stages see the
same recursion as an unpadded problem.

delta^{a,k}_{ij} = L_{a,k} D'_{ij}(F_{ij}) + q^{a,k}_j  is the per-link
forwarding marginal used by both the forwarding update and its blocking rule.

`round_eval` is the once-per-outer-round evaluation shared by the round's
objective read-out and the next placement sweep: both consume the identical
(q, dp, kappa, t, F, G) tuple, so the ALT loop no longer re-solves the
traffic fixed point separately for `objective` and `placement_update`
(the per-round dataflow restructure of DESIGN.md section 10).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from .flow import (
    loads,
    marginal_comp,
    marginal_link_weights,
    objective_from_loads,
    stage_solve,
    stage_traffic,
)
from .structs import BIG, Problem, State, partition_live_mask


@partial(jax.jit, static_argnames=("solver", "use_pallas", "interpret"))
def cost_to_go(
    problem: Problem,
    state: State,
    t: jax.Array | None = None,
    *,
    solver: str = "neumann",
    use_pallas: bool = False,
    interpret: bool = False,
):
    """Returns (q [A,K,V], dp [V,V], kappa [A,P,V], t [A,K,V], F, G)."""
    if t is None:
        t = stage_traffic(
            problem, state, solver=solver, use_pallas=use_pallas,
            interpret=interpret,
        )
    F, G = loads(problem, state, t)
    dp = marginal_link_weights(problem, F)  # BIG off-edges
    dp_edges = jnp.where(problem.net.adj > 0, dp, 0.0)  # safe for sums
    kappa = marginal_comp(problem, G)  # [A, P, V]
    apps = problem.apps
    L = apps.L  # [A, K]
    solve = partial(
        stage_solve, problem=problem, transpose=False, solver=solver,
        use_pallas=use_pallas, interpret=interpret,
    )

    def link_term(phi_k, Lk):
        # c_i = sum_j phi_{ij} * L_k * D'_{ij}
        return Lk * jnp.sum(phi_k * dp_edges[None, :, :], axis=-1)

    # Absorption gates / marginals of the *next* partition, stage-aligned:
    # stage k is absorbed by partition k+1 (gate x^{a,k+1}, cost kappa^{a,k+1})
    # for k < parts; the final and phantom stages have no absorption term.
    live = partition_live_mask(apps)[:, :, None]  # [A, P, 1]
    zeros_tail = jnp.zeros_like(state.x[:, :1])
    gates = jnp.moveaxis(
        jnp.concatenate([state.x * live, zeros_tail], axis=1), 1, 0
    )  # [K, A, V]
    kappas = jnp.moveaxis(
        jnp.concatenate([kappa * live, zeros_tail], axis=1), 1, 0
    )  # [K, A, V]
    phi_s = jnp.moveaxis(state.phi, 1, 0)  # [K, A, V, V]
    L_s = jnp.moveaxis(L, 1, 0)  # [K, A]

    def step(q_next, xs):
        phi_k, L_k, gate_k, kap_k = xs
        c = link_term(phi_k, L_k[:, None]) + gate_k * (kap_k + q_next)
        q_k = solve(phi_k, c)
        return q_k, q_k

    _, q_rev = jax.lax.scan(
        step,
        jnp.zeros_like(gates[0]),
        (phi_s, L_s, gates, kappas),
        reverse=True,
    )
    q = jnp.moveaxis(q_rev, 0, 1)  # [A, K, V]
    return q, dp, kappa, t, F, G


@partial(jax.jit, static_argnames=("solver", "use_pallas", "interpret"))
def round_eval(
    problem: Problem,
    state: State,
    *,
    solver: str = "neumann",
    use_pallas: bool = False,
    interpret: bool = False,
):
    """One full marginal evaluation of `state`: (J, aux).

    aux carries everything the round needs downstream — the objective
    breakdown for the history/stall logic AND the (q, dp, kappa, t, F, G)
    tuple the next placement sweep consumes — computed from a single
    traffic solve instead of one per consumer.
    """
    q, dp, kappa, t, F, G = cost_to_go(
        problem, state, solver=solver, use_pallas=use_pallas,
        interpret=interpret,
    )
    J, j_comm, j_comp = objective_from_loads(problem, F, G)
    aux = {
        "J": J,
        "J_comm": j_comm,
        "J_comp": j_comp,
        "ctg": (q, dp, kappa, t, F, G),
    }
    return J, aux


@partial(jax.jit, static_argnames=("solver", "use_pallas", "interpret"))
def link_marginals(
    problem: Problem,
    state: State,
    *,
    solver: str = "neumann",
    use_pallas: bool = False,
    interpret: bool = False,
):
    """delta^{a,k}_{ij} (Eq. 10), BIG on non-edges. Returns (delta, aux)."""
    q, dp, kappa, t, F, G = cost_to_go(
        problem, state, solver=solver, use_pallas=use_pallas,
        interpret=interpret,
    )
    L = problem.apps.L  # [A, K]
    # delta[a,k,i,j] = L[a,k] * dp[i,j] + q[a,k,j]
    delta = L[:, :, None, None] * dp[None, None, :, :] + q[:, :, None, :]
    delta = jnp.where(problem.net.adj[None, None] > 0, delta, BIG)
    return delta, {"q": q, "dp": dp, "kappa": kappa, "t": t, "F": F, "G": G}
