"""Stage-wise traffic flow, link/node loads, and the objective J (Eqs. 3-7).

The paper's recursion (3) defines, per application a and stage k, the node
traffic t_i^{a,k}. Under loop-free forwarding (guaranteed by the blocking rule
in forwarding.py and by the shortest-path-tree initialization/repair), the
forwarding matrix Phi^{a,k} is nilpotent, hence (I - Phi^T) is invertible and

    t^{a,k} = (I - (Phi^{a,k})^T)^{-1} b^{a,k}

with stage sources

    b^{a,0} = lambda_a e_{s_a}
    b^{a,k} = x^{a,k} .* t^{a,k-1}   for 1 <= k <= parts_a
              (the partition-k host converts stage k-1 -> k)
    b^{a,k} = 0                      for k > parts_a (phantom stages).

The chain is a `lax.scan` over the stage axis — one trace of the solve body
regardless of the partition count P, which is per-`Problem` data rather than
a structural constant (DESIGN.md section 13).

TPU adaptation (DESIGN.md sections 3 and 10): each fixed point is solved
batched over applications. The default `solver="neumann"` exploits the
nilpotency directly — a hop-capped propagation x <- b + Phi^T x (O(H V^2)
per solve, kernels/neumann) — while `solver="lu"` keeps the dense
O(V^3) `jnp.linalg.solve` as the exactness reference.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from . import costs
from ..kernels.neumann import effective_hops, neumann_solve
from .structs import Apps, Problem, State, one_hot, partition_live_mask

SOLVERS = ("neumann", "lu")
# fp32 products on the TPU too, where XLA's default rounds dot operands to
# bf16: the link and node loads feed J directly.
HIGHEST = jax.lax.Precision.HIGHEST


def stage_solve(
    phi_k: jax.Array,
    b: jax.Array,
    problem: Problem,
    *,
    transpose: bool,
    solver: str = "neumann",
    use_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Batched (I - Phi^T) t = b (transpose=True) or (I - Phi) q = c solve.

    phi_k: [..., V, V] stacked over apps (and fleet instances under vmap),
    b: [..., V]. The hop cap comes from the Problem-carried bound.

    `interpret=True` runs the Pallas kernel body under the interpreter
    (CPU tests only); a launch passes `--use-pallas` at the CLI and the
    pair flows down here unchanged.
    """
    if solver == "lu":
        n = phi_k.shape[-1]
        eye = jnp.eye(n, dtype=phi_k.dtype)
        a = eye - (jnp.swapaxes(phi_k, -1, -2) if transpose else phi_k)
        return jnp.linalg.solve(a, b[..., None])[..., 0]
    if solver != "neumann":
        raise ValueError(f"unknown solver {solver!r}; expected one of {SOLVERS}")
    m = jnp.swapaxes(phi_k, -1, -2) if transpose else phi_k
    hops = effective_hops(
        problem.hop_bound, problem.net.n_nodes, fixed_loop=use_pallas
    )
    return neumann_solve(
        m, b, hops=hops, use_pallas=use_pallas, interpret=interpret
    )


def _stage_gates(state: State, apps: Apps) -> jax.Array:
    """[K, A, V] conversion gate of each stage: gate_k = x^{a,k} for live
    partitions (stage k is re-injected by partition k's host), zero for
    stage 0 (exogenous source) and for phantom stages (k > parts)."""
    gated = state.x * partition_live_mask(apps)[:, :, None]  # [A, P, V]
    gates = jnp.concatenate(
        [jnp.zeros_like(gated[:, :1]), gated], axis=1
    )  # [A, K, V]
    return jnp.moveaxis(gates, 1, 0)


def _traffic_scan(problem, state, inject, *, solver, use_pallas, interpret=False):
    """Forward stage scan: t_k = solve(phi_k, inject_k + gate_k * t_{k-1})."""
    solve = partial(
        stage_solve, problem=problem, transpose=True, solver=solver,
        use_pallas=use_pallas, interpret=interpret,
    )
    phi_s = jnp.moveaxis(state.phi, 1, 0)  # [K, A, V, V]
    gates = _stage_gates(state, problem.apps)  # [K, A, V]

    def step(t_prev, xs):
        phi_k, inj_k, gate_k = xs
        t_k = solve(phi_k, inj_k + gate_k * t_prev)
        return t_k, t_k

    _, t = jax.lax.scan(step, jnp.zeros_like(inject[0]), (phi_s, inject, gates))
    return jnp.moveaxis(t, 0, 1)  # [A, K, V]


def _source_injection(problem: Problem) -> jax.Array:
    """[K, A, V] exogenous stage sources: lambda at s_a on stage 0, 0 after."""
    n = problem.net.n_nodes
    apps = problem.apps
    b0 = apps.lam[:, None] * one_hot(apps.src, n)  # [A, V]
    k = apps.L.shape[-1]
    return jnp.concatenate(
        [b0[None], jnp.zeros((k - 1,) + b0.shape, b0.dtype)], axis=0
    )


@partial(jax.jit, static_argnames=("solver", "use_pallas", "interpret"))
def stage_traffic(
    problem: Problem,
    state: State,
    *,
    solver: str = "neumann",
    use_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """[A, K, V] traffic rate t_i^{a,k} (requests/s)."""
    return _traffic_scan(
        problem, state, _source_injection(problem),
        solver=solver, use_pallas=use_pallas, interpret=interpret,
    )


@jax.jit
def loads(problem: Problem, state: State, t: jax.Array | None = None):
    """Link load F [V,V] (Eq. 5) and node computation load G [V] (Eq. 6).

    The stage/partition axis is accumulated by a sequential scan (one
    fixed-shape per-stage contraction per step), NOT one fused (a, k)
    einsum: a fused contraction's reduction pairing depends on the
    contracted extent, so the same real stages could round differently
    under different K envelopes. Sequential accumulation keeps the real
    prefix's float associativity independent of K — appended phantom
    stages are exact-zero addends — which is what makes stage padding
    *bitwise*-inert on J (DESIGN.md section 13).
    """
    if t is None:
        t = stage_traffic(problem, state)
    apps = problem.apps
    # f^{a,k}_{ij} = t^{a,k}_i phi^{a,k}_{ij}  (Eq. 4)
    f = t[..., :, None] * state.phi  # [A, K, V, V]

    def accum_f(acc, xs):
        L_k, f_k = xs  # [A], [A, V, V]
        return acc + jnp.einsum("a,aij->ij", L_k, f_k, precision=HIGHEST), None

    n = state.phi.shape[-1]
    F, _ = jax.lax.scan(
        accum_f,
        jnp.zeros((n, n), f.dtype),
        (jnp.moveaxis(apps.L, 1, 0), jnp.moveaxis(f, 1, 0)),
    )

    # G_i = sum_a sum_p w^{a,p} x^{a,p}_i t^{a,p-1}_i (phantom w = 0)
    def accum_g(acc, xs):
        w_p, x_p, t_p = xs  # [A], [A, V], [A, V]
        return acc + jnp.einsum(
            "a,av,av->v", w_p, x_p, t_p, precision=HIGHEST
        ), None

    G, _ = jax.lax.scan(
        accum_g,
        jnp.zeros((n,), f.dtype),
        (
            jnp.moveaxis(apps.w, 1, 0),
            jnp.moveaxis(state.x, 1, 0),
            jnp.moveaxis(t[:, :-1, :], 1, 0),
        ),
    )
    return F, G


@jax.jit
def objective_from_loads(problem: Problem, F: jax.Array, G: jax.Array):
    """J and its comm/comp split from already-computed loads (Eq. 7)."""
    net, cm = problem.net, problem.cost
    D = costs.link_cost(F, net.mu, cm) * net.adj
    C = costs.comp_cost(G, net.nu, cm)
    j_comm = jnp.sum(D)
    j_comp = jnp.sum(C)
    J = cm.w_comm * j_comm + cm.w_comp * j_comp
    return J, j_comm, j_comp


@partial(jax.jit, static_argnames=("solver", "use_pallas", "interpret"))
def objective(
    problem: Problem,
    state: State,
    *,
    solver: str = "neumann",
    use_pallas: bool = False,
    interpret: bool = False,
):
    """J(x, phi) plus a breakdown dict (Eq. 7 / the Fig-5 weighted variant)."""
    t = stage_traffic(
        problem, state, solver=solver, use_pallas=use_pallas, interpret=interpret
    )
    F, G = loads(problem, state, t)
    J, j_comm, j_comp = objective_from_loads(problem, F, G)
    return J, {"J": J, "J_comm": j_comm, "J_comp": j_comp, "F": F, "G": G, "t": t}


@jax.jit
def marginal_link_weights(problem: Problem, F: jax.Array) -> jax.Array:
    """w_comm * D'_ij(F_ij) on edges, BIG elsewhere: base weights for both the
    forwarding marginals (Eq. 10) and the placement surrogate (Eqs. 12-13)."""
    from .structs import BIG

    net, cm = problem.net, problem.cost
    dp = cm.w_comm * costs.link_cost_prime(F, net.mu, cm)
    return jnp.where(net.adj > 0, dp, BIG)


@jax.jit
def marginal_comp(problem: Problem, G: jax.Array) -> jax.Array:
    """kappa^{a,p}_i = w^{a,p} * w_comp * C'_i(G_i)   [A, P, V] (Eq. 12)."""
    cm = problem.cost
    cp = cm.w_comp * costs.comp_cost_prime(G, problem.net.nu, cm)  # [V]
    return problem.apps.w[:, :, None] * cp[None, None, :]


def objective_with_injection(
    problem: Problem,
    state: State,
    a: int,
    k: int,
    inj: jax.Array,
    *,
    solver: str = "neumann",
):
    """J when an extra exogenous stage-k source `inj` [V] is added for app a.

    Used to validate the marginal machinery: Gallager's identity says
    grad_inj J |_{inj=0} = q^{a,k} (the cost-to-go from marginals.py).
    Differentiating the neumann path goes through custom_linear_solve's
    implicit transpose solve, not the hop loop.
    """
    inject = _source_injection(problem).at[k, a].add(inj)
    t = _traffic_scan(problem, state, inject, solver=solver, use_pallas=False)
    F, G = loads(problem, state, t)
    J, _, _ = objective_from_loads(problem, F, G)
    return J


def total_absorbed(
    problem: Problem, state: State, *, solver: str = "neumann"
) -> jax.Array:
    """[A] sanity metric: final-stage traffic absorbed at each destination.

    Stage `parts_a` is app a's final stage (per-app split depths may differ
    inside one problem); its absorbed rate equals lambda_a when forwarding
    is consistent (conservation test)."""
    t = stage_traffic(problem, state, solver=solver)
    n = problem.net.n_nodes
    apps = problem.apps
    dst_oh = one_hot(apps.dst, n)
    t_fin = jnp.take_along_axis(t, apps.parts[:, None, None], axis=1)[:, 0, :]
    return jnp.sum(t_fin * dst_oh, axis=-1)
