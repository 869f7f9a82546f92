"""Marginal-cost placement reassignment (paper Eqs. 12-16) + phi repair.

Because the stage-k edge weight L_{a,k} D'_{ij}(F_{ij}) differs across stages
only by the positive scalar L_{a,k}, a single APSP under the base weight
D'_{ij}(F_{ij}) serves every (application, stage): Gamma^{a,k}_{uv} =
L_{a,k} * dist[u, v]  (the paper's section III-B observation). On TPU the APSP
is tropical matrix squaring (kernels/minplus), not Dijkstra — DESIGN.md 3.

Candidate score of partition p (0-based; upstream comm + local comp +
downstream comm), generic over the per-app partition count `parts`:

    S_{a,p}(i) = L_{a,p} dist[up_a, i] + kappa^{a,p}_i
                 + L_{a,p+1} dist[i, down_a]

where `up_a` is the *new* host of partition p-1 (the source s_a for p = 0)
and `down_a` is the *old* host of partition p+1 (the destination d_a for the
last live partition). Partitions are updated in order p = 0..P-1 — the
generalization of the paper's footnote 5 ("partition 1 first, then partition
2 with the new host of partition 1") to arbitrary split depths. Phantom
partitions (p >= parts) are frozen in place and carry zero load, so a
stage-padded instance sweeps bit-identically to its unpadded original
(DESIGN.md section 13).

Sweep schedules (`block_apps`, DESIGN.md section 18):

  * `block_apps=1` (default) — the paper's strictly sequential Gauss-Seidel
    scan over applications: each app removes its own loads from the
    incrementally maintained compute vector G, scores, moves, and commits
    before the next app is scored. This is the historical `lax.scan` path,
    kept verbatim.
  * `block_apps=k>1` (0 = all apps in one block) — the blocked sweep: apps
    are processed in blocks of static size k. Per block, everything that
    does not depend on in-block decisions is precomputed batched (the
    downstream score legs for all k apps at once, one dense `cprime(G)`
    base at the block-entry G); the decisions themselves stay a serial
    walk in app order (footnote-5 partition chain inside each app), with
    the compute marginal corrected incrementally on the <= 2P tracked
    slots an app's own removals/choices touch — never a dense per-app
    recompute. In-block conflicts are exact: each commit folds its delta
    into the carried cprime values at the <= 2P slots it touched, so every
    decision sees the same bits the sequential scan would, and the sweep's
    result is BITWISE-invariant to the block size (pinned at k in
    {1, 4, A} by tests/test_placement_sweep.py via
    `blocked_placement_update`). Block size trades batched precompute
    against per-block dense cprime evaluations; it never changes results.

After placement changes, stale forwarding would strand traffic (the old host
no longer absorbs), so per (app, stage) whose target host changed we rebuild
phi as the shortest-path next-hop tree toward the new host under the CURRENT
congested marginals — a congestion-aware warm restart that keeps (I - Phi^T)
invertible. Stages whose host did not change keep their refined multipath phi.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..kernels.minplus import apsp_with_nexthop
from .marginals import cost_to_go
from .structs import (
    Problem,
    State,
    app_live_mask,
    one_hot,
    partition_live_mask,
    stage_live_mask,
    stage_targets,
)


def _sp_tree_phi(nexthop_to: jax.Array, target: jax.Array, mass: jax.Array, n: int):
    """phi rows = one-hot(next hop toward `target`), scaled by row mass.

    nexthop_to: [V, V] next-hop table (column t = toward target t).
    """
    nh = nexthop_to[:, target]  # [V]
    rows = jax.nn.one_hot(nh, n, dtype=jnp.float32)  # [V, V]
    return rows * mass[:, None]


def zero_load_dp(problem: Problem) -> jax.Array:
    """[V, V] zero-load marginal link metric, gated to the live adjacency.

    The seed weight behind `structured_init` and `repair_placement`: the
    congestion-free shortest-path metric D'_{ij}(0), with non-edges (and
    every edge into/out of a pad-encoded dead node, which keeps adj = 0)
    priced at BIG. Depends only on (adj, mu, cost) — which is what makes
    the zero-load APSP cacheable across chaos epochs (chaos/repair.py
    `Apsp0Cache`); this single definition is shared by the cold and cached
    paths so parity is bitwise by construction.
    """
    from . import costs as _costs
    from .structs import BIG

    dp0 = problem.cost.w_comm * _costs.link_cost_prime(
        jnp.zeros_like(problem.net.mu), problem.net.mu, problem.cost
    )
    return jnp.where(problem.net.adj > 0, dp0, BIG)


def _sequential_sweep(problem, hosts, dist, G, cprime, *, colocate, move_margin):
    """The paper's sequential Gauss-Seidel app scan (the `block_apps=1` path).

    Kept verbatim from the pre-blocked implementation: each app removes its
    own loads from the incrementally maintained G (so kappa is the marginal
    of adding it), walks its partition chain in footnote-5 order, and
    commits its chosen hosts' loads before the next app is scored. Without
    the incremental G, every app would see the same cheapest node and
    stampede onto it (a placement 2-cycle).
    """
    n = problem.net.n_nodes
    apps = problem.apps
    n_parts = apps.n_parts
    p_idx = jnp.arange(n_parts)

    def body(Gv, inputs):
        (src_a, dst_a, h_old, lam_a, L_a, w_a, parts_a) = inputs
        loads_a = w_a * lam_a  # [P]
        live = p_idx < parts_a  # [P]
        # Remove this app's own loads so kappa is the marginal of adding it
        # (sequentially, in partition order — phantom loads are exact zeros).
        def remove(g, pin):
            h_p, load_p = pin
            return g - load_p * jax.nn.one_hot(h_p, n), None

        Gv, _ = jax.lax.scan(remove, Gv, (h_old, loads_a))

        def pick(S, h_prev):
            # Hysteresis: only move when the improvement beats move_margin
            # (damps host flapping between outer iterations).
            cand = jnp.argmin(S).astype(jnp.int32)
            better = S[cand] < (1.0 - move_margin) * S[h_prev]
            return jnp.where(better, cand, h_prev).astype(jnp.int32)

        if colocate:
            w_tot = jnp.sum(jnp.where(live, w_a, 0.0))
            load_tot = jnp.sum(jnp.where(live, loads_a, 0.0))
            L_fin = L_a[parts_a]
            S = (
                L_a[0] * dist[src_a, :]
                + w_tot * cprime(Gv)
                + L_fin * dist[:, dst_a]
            )
            h = pick(S, h_old[0])
            h_new = jnp.where(live, h, h_old)
            Gv = Gv + load_tot * jax.nn.one_hot(h, n)
            return Gv, h_new

        # Old downstream anchor of partition p: partition p+1's current host,
        # or the destination for the last live partition (and phantoms).
        down = jnp.where(
            p_idx + 1 < parts_a,
            jnp.concatenate([h_old[1:], dst_a[None]]),
            dst_a,
        )  # [P]

        def step(carry, pin):
            g, up = carry
            live_p, h_old_p, down_p, L_up, L_dn, w_p, load_p = pin
            S = L_up * dist[up, :] + w_p * cprime(g) + L_dn * dist[:, down_p]
            h = jnp.where(live_p, pick(S, h_old_p), h_old_p)
            g = g + jnp.where(live_p, load_p, 0.0) * jax.nn.one_hot(h, n)
            return (g, h), h

        (Gv, _), h_new = jax.lax.scan(
            step,
            (Gv, src_a),
            (live, h_old, down, L_a[:-1], L_a[1:], w_a, loads_a),
        )
        return Gv, h_new

    _, hosts_new = jax.lax.scan(
        body,
        G,
        (apps.src, apps.dst, hosts, apps.lam, apps.L, apps.w, apps.parts),
    )
    return hosts_new


def _blocked_sweep(
    problem, hosts, dist, G, cprime, cprime_at, *, colocate, move_margin, bk
):
    """Blocked placement sweep: batched score-row precompute, exact decisions.

    Per block of `bk` apps (static size; the app axis is padded to a block
    multiple with inert clamped repeats):

      1. PRECOMPUTE (batched): the parts of every app's candidate rows that
         do not depend on in-block decisions are built for the whole block
         at once — the downstream legs `L_dn * dist[:, down]` (old-host
         anchored, like the sequential scan) and one per-block dense
         `cprime(G)` base evaluated at the block-entry G.
      2. DECIDE + COMMIT (serial, conflict-exact): apps are walked in block
         order. App j's candidate row for partition p is assembled from the
         precomputed pieces in the sequential scan's exact operation order
         (`(L_up * dist[up, :] + w_p * cprime) + downstream`), with the
         compute marginal corrected ONLY on the <= 2P tracked slots the
         app's own removals/choices touch (`I` holds the P old hosts plus
         one slot per chain step; `gval` replays the scan's own-load op
         sequence on the gathered slots). Conflicts with apps 0..j-1 of the
         block are exact, not approximated: their committed deltas are
         folded into the carried cprime values at the <= 2P slots each
         commit touched, so every argmin + `move_margin` pick sees the same
         bits the sequential scan would. Duplicated indices in `I` always
         carry identical values, so the scatter-set is order-safe.

    Because step 2 reproduces the sequential decision sequence exactly, the
    sweep's result is BITWISE-invariant to `bk` — block size is a pure
    scheduling knob trading batched precompute against per-block dense
    cprime evaluations (A dense evaluations at bk = 1, A / bk at bk > 1,
    one at bk = 0). That is a deliberate design departure from scoring
    whole blocks against the block-entry G (Jacobi) with a revert-style
    acceptance pass: measured on the four paper topologies, Jacobi blocks
    steer the outer ALT loop to DIFFERENT local optima (end-of-solve J off
    by 0.9%-68% depending on block size), and an all-at-once acceptance
    pass livelocks when every app lands in one block. DESIGN.md section 18
    records both measurements.

    Returns (hosts_new [A, P], cert) where `cert` carries the decision
    certificates (old/final hosts, decision-context scores S_new/S_old,
    the per-partition moved mask, and the per-block entry G) for the
    monotonicity property in tests/test_placement_sweep.py. For `colocate`
    the per-app chain collapses to one joint host; cert score fields then
    have one column.
    """
    n = problem.net.n_nodes
    apps = problem.apps
    n_parts = apps.n_parts
    a_tot = apps.n_apps
    n_blocks = -(-a_tot // bk)
    a_pad = n_blocks * bk

    idx = jnp.minimum(jnp.arange(a_pad), a_tot - 1)
    valid = jnp.arange(a_pad) < a_tot  # [A_pad]
    take = lambda x: jnp.take(x, idx, axis=0)  # noqa: E731

    src = take(apps.src)
    dst = take(apps.dst)
    h_old_all = take(hosts)  # [A_pad, P]
    L = take(apps.L)  # [A_pad, P+1]
    w = take(apps.w)  # [A_pad, P]
    parts = take(apps.parts)
    p_idx = jnp.arange(n_parts)
    live_all = (p_idx[None, :] < parts[:, None]) & valid[:, None]
    # Removal amounts are the raw per-partition loads (phantom loads are
    # exact zeros, like the sequential scan); clamped pad repeats must not
    # double-remove the last real app's loads, so they are zeroed outright.
    rem_all = jnp.where(valid[:, None], w * take(apps.lam)[:, None], 0.0)
    add_all = jnp.where(live_all, rem_all, 0.0)
    down_all = jnp.where(
        p_idx[None, :] + 1 < parts[:, None],
        jnp.concatenate([h_old_all[:, 1:], dst[:, None]], axis=1),
        dst[:, None],
    )
    L_fin = jnp.take_along_axis(L, parts[:, None], axis=1)[:, 0]
    w_tot = jnp.sum(jnp.where(live_all, w, 0.0), axis=1)
    load_tot = jnp.sum(add_all, axis=1)

    blk = lambda x: x.reshape((n_blocks, bk) + x.shape[1:])  # noqa: E731
    xs = dict(
        src=blk(src), dst=blk(dst), h_old=blk(h_old_all), rem=blk(rem_all),
        add=blk(add_all), live=blk(live_all), down=blk(down_all),
        L_up=blk(L[:, :-1]), L_dn=blk(L[:, 1:]), w=blk(w), L0=blk(L[:, 0]),
        L_fin=blk(L_fin), w_tot=blk(w_tot), load_tot=blk(load_tot),
    )
    margin = 1.0 - move_margin

    def _app_chain(carry, xa):
        """Exact footnote-5 chain walk for one app (docstring step 2).

        Carry: (G, cpw) where `cpw` is the dense cprime-value vector kept
        current at every slot touched by committed apps. `DN` rides in `xa`
        precomputed (downstream legs are old-host anchored, never stale).
        """
        Gc, cpw = carry
        h_old_j = xa["h_old"]  # [P]
        I = jnp.concatenate([h_old_j, h_old_j])  # [2P] tracked slots
        gval = Gc[I]
        for p2 in range(n_parts):
            gval = gval - jnp.where(I == h_old_j[p2], xa["rem"][p2], 0.0)
        up = xa["src"]
        h_fins, s_news, s_olds = [], [], []
        for p in range(n_parts):
            h_old_p = h_old_j[p]
            # Same association as the sequential scan's dense S:
            # (upstream + compute) + downstream, compute corrected on I.
            T = xa["L_up"][p] * dist[up, :] + xa["w"][p] * cpw
            T = T.at[I].set(
                xa["L_up"][p] * dist[up, I]
                + xa["w"][p] * cprime_at(gval, I)
            )
            S = T + xa["DN"][p]
            cand = jnp.argmin(S).astype(jnp.int32)
            better = S[cand] < margin * S[h_old_p]
            h_p = jnp.where(
                xa["live"][p], jnp.where(better, cand, h_old_p), h_old_p
            ).astype(jnp.int32)
            # Retarget this step's slot to the chosen host: if already
            # tracked copy the (consistent) tracked value, else h_p is
            # untouched by the app's own ops and holds the carried G.
            match = I == h_p
            tracked = gval[jnp.argmax(match)]
            val_h = jnp.where(match.any(), tracked, Gc[h_p])
            I = I.at[n_parts + p].set(h_p)
            gval = gval.at[n_parts + p].set(val_h)
            gval = gval + jnp.where(I == h_p, xa["add"][p], 0.0)
            h_fins.append(h_p)
            s_news.append(jnp.where(xa["live"][p], S[h_p], S[h_old_p]))
            s_olds.append(S[h_old_p])
            up = h_p
        h_fin = jnp.stack(h_fins)
        # Commit: removals then additions, in partition order (the
        # sequential scan's exact scatter sequence), then refresh the
        # carried cprime values at the touched slots — which are exactly
        # the tracked I (old hosts in the first half, chosen in the second).
        for p2 in range(n_parts):
            Gc = Gc.at[h_old_j[p2]].add(-xa["rem"][p2])
        for p2 in range(n_parts):
            Gc = Gc.at[h_fin[p2]].add(xa["add"][p2])
        cpw = cpw.at[I].set(cprime_at(Gc[I], I))
        out = dict(
            h_fin=h_fin, S_new=jnp.stack(s_news), S_old=jnp.stack(s_olds)
        )
        return (Gc, cpw), out

    def _app_colo(carry, xa):
        """Exact joint-host decision for one app (colocate variant)."""
        Gc, cpw = carry
        h_old_j = xa["h_old"]  # [P]
        h_prev = h_old_j[0]
        gval = Gc[h_old_j]
        for p2 in range(n_parts):
            gval = gval - jnp.where(h_old_j == h_old_j[p2], xa["rem"][p2], 0.0)
        T = xa["L0"] * dist[xa["src"], :] + xa["w_tot"] * cpw
        T = T.at[h_old_j].set(
            xa["L0"] * dist[xa["src"], h_old_j]
            + xa["w_tot"] * cprime_at(gval, h_old_j)
        )
        S = T + xa["DN"]
        cand = jnp.argmin(S).astype(jnp.int32)
        better = S[cand] < margin * S[h_prev]
        h_1 = jnp.where(better, cand, h_prev).astype(jnp.int32)
        for p2 in range(n_parts):
            Gc = Gc.at[h_old_j[p2]].add(-xa["rem"][p2])
        Gc = Gc.at[h_1].add(xa["load_tot"])
        I_t = jnp.concatenate([h_old_j, h_1[None]])
        cpw = cpw.at[I_t].set(cprime_at(Gc[I_t], I_t))
        h_fin = jnp.where(xa["live"], h_1, h_old_j)  # [P]
        out = dict(h_fin=h_fin, S_new=S[h_1][None], S_old=S[h_prev][None])
        return (Gc, cpw), out

    def body(Gv, x):
        g_entry = Gv
        cpb = cprime(Gv)  # [V] per-block dense base (docstring step 1)
        if colocate:
            DN = x["L_fin"][:, None] * jnp.take(dist, x["dst"], axis=1).T
            xa = dict(
                src=x["src"], h_old=x["h_old"], rem=x["rem"],
                live=x["live"], L0=x["L0"], w_tot=x["w_tot"],
                load_tot=x["load_tot"], DN=DN,
            )
            (Gv, _), ys = jax.lax.scan(_app_colo, (Gv, cpb), xa)
        else:
            dcol = jnp.take(dist, x["down"].reshape(-1), axis=1)  # [V, bk*P]
            DN = x["L_dn"][:, :, None] * dcol.T.reshape(bk, n_parts, n)
            xa = dict(
                src=x["src"], h_old=x["h_old"], rem=x["rem"], add=x["add"],
                live=x["live"], L_up=x["L_up"], w=x["w"], DN=DN,
            )
            (Gv, _), ys = jax.lax.scan(_app_chain, (Gv, cpb), xa)
        ys["G_entry"] = g_entry
        return Gv, ys

    _, ys = jax.lax.scan(body, G, xs)

    unblk = lambda v: v.reshape((a_pad,) + v.shape[2:])[:a_tot]  # noqa: E731
    hosts_new = unblk(ys["h_fin"])
    cert = {
        "h_old": hosts,
        "h_fin": hosts_new,
        "moved": hosts_new != hosts,
        "S_new": unblk(ys["S_new"]),
        "S_old": unblk(ys["S_old"]),
        "G_entry": ys["G_entry"],  # [n_blocks, V]
        "block": jnp.int32(bk),
    }
    return hosts_new, cert


def _placement_update_impl(
    problem, state, ctg, *, colocate, use_pallas, interpret, move_margin,
    solver, block_apps, force_blocked,
):
    if block_apps < 0:
        raise ValueError(
            f"block_apps must be >= 0 (0 = all apps per block), "
            f"got {block_apps}"
        )
    n = problem.net.n_nodes
    apps = problem.apps
    if ctg is None:
        ctg = cost_to_go(
            problem, state, solver=solver, use_pallas=use_pallas,
            interpret=interpret,
        )
    q, dp, kappa, t, F, G = ctg
    dist, nexthop = apsp_with_nexthop(
        dp, use_pallas=use_pallas, interpret=interpret
    )
    hosts = state.hosts()  # [A, P]
    cm = problem.cost
    nu = problem.net.nu

    from . import costs as _costs

    def cprime(Gv):
        return cm.w_comp * _costs.comp_cost_prime(Gv, nu, cm)

    def cprime_at(g, idx):
        # Same elementwise marginal, evaluated at gathered node slots `idx`
        # (comp_cost_prime is elementwise in (G, nu), so gathering nu keeps
        # each slot's value bitwise-equal to the dense vector's entry).
        return cm.w_comp * _costs.comp_cost_prime(g, nu[idx], cm)

    a_tot = apps.n_apps
    bk = a_tot if (block_apps == 0 or block_apps >= a_tot) else block_apps
    cert = None
    if bk <= 1 and not force_blocked:
        hosts_new = _sequential_sweep(
            problem, hosts, dist, G, cprime,
            colocate=colocate, move_margin=move_margin,
        )
    else:
        hosts_new, cert = _blocked_sweep(
            problem, hosts, dist, G, cprime, cprime_at,
            colocate=colocate, move_margin=move_margin, bk=bk,
        )

    x_new = one_hot(hosts_new, n)  # [A, P, V]
    new_state = State(x=x_new, phi=state.phi)
    return repair_phi(problem, state, new_state, nexthop), cert


_PLACEMENT_STATICS = (
    "colocate", "use_pallas", "interpret", "move_margin", "solver",
    "block_apps",
)


@functools.partial(jax.jit, static_argnames=_PLACEMENT_STATICS)
def placement_update(
    problem: Problem,
    state: State,
    ctg=None,
    *,
    colocate: bool = False,
    use_pallas: bool = False,
    interpret: bool = False,
    move_margin: float = 0.02,
    solver: str = "neumann",
    block_apps: int = 1,
) -> State:
    """One placement reassignment sweep over all applications.

    `ctg` is an optional precomputed (q, dp, kappa, t, F, G) tuple from
    `marginals.cost_to_go` / `round_eval` evaluated at `state` — the ALT
    loop passes the round-final evaluation so placement never re-solves
    the traffic fixed point it was just measured with. Link marginals (the
    Gamma distances) stay fixed during the sweep, exactly as in the paper.

    `block_apps` selects the sweep schedule (module doc + DESIGN.md §18):
    1 = the paper's sequential Gauss-Seidel app scan (default; the
    historical path, kept verbatim), k > 1 = the blocked sweep (batched
    per-block score-row precompute around an exact serial decision core),
    0 = one block covering every app. The result is bitwise-invariant to
    `block_apps` — the knob only changes the work schedule
    (tests/test_placement_sweep.py pins bitwise equality at 1, 4 and A).
    """
    new_state, _ = _placement_update_impl(
        problem, state, ctg, colocate=colocate, use_pallas=use_pallas,
        interpret=interpret, move_margin=move_margin, solver=solver,
        block_apps=block_apps, force_blocked=False,
    )
    return new_state


@functools.partial(jax.jit, static_argnames=_PLACEMENT_STATICS)
def blocked_placement_update(
    problem: Problem,
    state: State,
    ctg=None,
    *,
    colocate: bool = False,
    use_pallas: bool = False,
    interpret: bool = False,
    move_margin: float = 0.02,
    solver: str = "neumann",
    block_apps: int = 1,
) -> State:
    """`placement_update` forced through the blocked sweep at ANY block size.

    The production entry dispatches `block_apps=1` to the sequential scan
    (it is cheaper to compile and trivially bitwise); this variant runs the
    blocked code path even at block size 1, which is what the bitwise pins
    in tests/test_placement_sweep.py actually exercise — the claim is that
    the blocked ALGORITHM reproduces the sequential scan bit-for-bit at
    EVERY block size, not that a dispatch branch picked the old code.
    """
    new_state, _ = _placement_update_impl(
        problem, state, ctg, colocate=colocate, use_pallas=use_pallas,
        interpret=interpret, move_margin=move_margin, solver=solver,
        block_apps=block_apps, force_blocked=True,
    )
    return new_state


@functools.partial(jax.jit, static_argnames=_PLACEMENT_STATICS)
def blocked_sweep_cert(
    problem: Problem,
    state: State,
    ctg=None,
    *,
    colocate: bool = False,
    use_pallas: bool = False,
    interpret: bool = False,
    move_margin: float = 0.02,
    solver: str = "neumann",
    block_apps: int = 1,
) -> dict:
    """Decision certificates of one blocked sweep (test/diagnostic entry).

    Returns the blocked sweep's internal evidence: old/final hosts, the
    decision-context scores S_new/S_old per (app, partition), the moved
    mask, and the per-block entry G. Every committed move carries
    `S_new < (1 - move_margin) * S_old` under its decision context — the
    certificate behind the "a blocked sweep never increases the
    placement-side objective" property in tests/test_placement_sweep.py.
    """
    _, cert = _placement_update_impl(
        problem, state, ctg, colocate=colocate, use_pallas=use_pallas,
        interpret=interpret, move_margin=move_margin, solver=solver,
        block_apps=block_apps, force_blocked=True,
    )
    return cert


@jax.jit
def repair_phi(
    problem: Problem,
    old: State,
    new: State,
    nexthop: jax.Array,
    force: jax.Array | None = None,
) -> State:
    """Rebuild phi for stages whose absorption target moved (see module doc).

    Generic over the stage axis: stage k targets the partition-(k+1) host
    for k < parts and the destination after that (`structs.stage_targets`),
    so the final stage — and every phantom stage — never triggers a rebuild,
    and phantom stages keep zero mass via `stage_live_mask`.

    `force` is an optional [A, K] bool mask requesting a rebuild even when
    the target did not move — the failure-repair path (`repair_placement`)
    uses it for stages whose refined multipath phi carries mass into a node
    that just died, which a target-only comparison cannot see."""
    n = problem.net.n_nodes
    apps = problem.apps
    old_t = stage_targets(apps, old.hosts())  # [A, K]
    new_t = stage_targets(apps, new.hosts())  # [A, K]
    live = stage_live_mask(apps)  # [A, K]
    if force is None:
        force = jnp.zeros(old_t.shape, bool)

    def per_stage(phi_k, ot, nt, lv, fc):
        m = (1.0 - jax.nn.one_hot(nt, n, dtype=jnp.float32)) * lv
        tree = _sp_tree_phi(nexthop, nt, m, n)
        return jnp.where((ot != nt) | fc, tree, phi_k)

    phi = jax.vmap(jax.vmap(per_stage, in_axes=(0, 0, 0, 0, 0)))(
        new.phi, old_t, new_t, live, force
    )
    phi = phi * app_live_mask(apps)[:, None, None, None]
    return State(x=new.x, phi=phi)


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def repair_placement(
    problem: Problem,
    state: State,
    node_mask: jax.Array,
    *,
    use_pallas: bool = False,
    interpret: bool = False,
    sp=None,
) -> State:
    """Evict partitions from masked-out hosts to the best live node.

    The failure-repair primitive (DESIGN.md section 15): `node_mask` is a
    [V] validity mask (1.0 = live) over a problem whose dead nodes already
    carry the pad encoding (adj = 0, mu = BIG, nu = NU_PAD — see
    chaos/events.py). Partitions hosted on dead nodes are rescored under
    the ZERO-LOAD marginals — the same metric as `structured_init`, because
    the post-fault congestion pattern is unknown until the next solve — and
    moved to the argmin live host, walking the partition chain in order so
    partition p sees the repaired host of p-1 (footnote-5 semantics, as in
    `placement_update`). Partitions on live hosts do not move: repair is a
    minimal eviction, not a re-optimization — the warm-started engine does
    the re-optimization afterwards.

    `sp` optionally injects a precomputed `(dist, nexthop)` pair for the
    zero-load metric `zero_load_dp(problem)` — the chaos controller's
    `Apsp0Cache` (chaos/repair.py) passes the cached APSP here so an
    epoch whose (adj, mu, cost) did not change skips the from-scratch
    `apsp_with_nexthop`. The cached arrays are produced by the identical
    computation on identical inputs, so parity with sp=None is bitwise
    (asserted per epoch by `launch.control --verify-apsp0` in CI).

    phi is then repaired by `repair_phi`, with a `force` rebuild for every
    stage whose current multipath phi carries mass INTO a dead node: once
    the node's links are BIG-rate, traffic routed there would otherwise be
    costed as if those links were free (zero incident traffic => zero D
    contribution), silently hiding an unservable route.

    Identity contract: with node_mask all-ones this returns `state`
    bitwise — no host is dead so no eviction happens, `one_hot(argmax(x))`
    round-trips the one-hot x exactly, and no stage is force-rebuilt.
    """
    n = problem.net.n_nodes
    apps = problem.apps
    n_parts = apps.n_parts
    from . import costs as _costs
    from .structs import BIG

    # Zero-load marginal link metric on the surviving subgraph. Dead nodes
    # keep adj = 0, so the `adj > 0` gate prices every edge into (or out of)
    # them at BIG and the SP trees route around the failure automatically.
    if sp is None:
        dist, nexthop = apsp_with_nexthop(
            zero_load_dp(problem), use_pallas=use_pallas, interpret=interpret
        )
    else:
        dist, nexthop = sp

    cp0 = problem.cost.w_comp * _costs.comp_cost_prime(
        jnp.zeros_like(problem.net.nu), problem.net.nu, problem.cost
    )
    # Hard eviction barrier: a dead candidate host scores BIG on top of its
    # already-prohibitive 1/NU_PAD compute marginal (belt and braces — the
    # braces matter when w_a,p is tiny).
    node_pen = jnp.where(node_mask > 0, 0.0, BIG)

    hosts = state.hosts()  # [A, P]
    p_idx = jnp.arange(n_parts)

    def per_app(src_a, dst_a, h_old, L_a, w_a, parts_a):
        live = p_idx < parts_a  # [P]
        dead_host = node_mask[h_old] <= 0  # [P]
        # Old downstream anchor: partition p+1's current host, or the
        # destination for the last live partition (and phantoms).
        down = jnp.where(
            p_idx + 1 < parts_a,
            jnp.concatenate([h_old[1:], dst_a[None]]),
            dst_a,
        )  # [P]

        def step(up, pin):
            live_p, h_old_p, down_p, L_up, L_dn, w_p, dead_p = pin
            S = (
                L_up * dist[up, :]
                + w_p * cp0
                + L_dn * dist[:, down_p]
                + node_pen
            )
            h = jnp.where(
                live_p & dead_p, jnp.argmin(S).astype(jnp.int32), h_old_p
            )
            return jnp.where(live_p, h, up), h

        _, h_new = jax.lax.scan(
            step,
            src_a,
            (live, h_old, down, L_a[:-1], L_a[1:], w_a, dead_host),
        )
        return h_new

    hosts_new = jax.vmap(per_app)(
        apps.src, apps.dst, hosts, apps.L, apps.w, apps.parts
    )

    # Stages whose refined phi still pushes mass into a dead node must be
    # rebuilt even if their absorption target did not move (docstring).
    dead = (node_mask <= 0).astype(state.phi.dtype)  # [V]
    force = jnp.einsum("akuv,v->ak", state.phi, dead) > 0  # [A, K]
    new_state = State(x=one_hot(hosts_new, n), phi=state.phi)
    return repair_phi(problem, state, new_state, nexthop, force)


@functools.partial(
    jax.jit, static_argnames=("colocate", "use_pallas", "interpret")
)
def structured_init(
    problem: Problem,
    *,
    colocate: bool = False,
    use_pallas: bool = False,
    interpret: bool = False,
    sp=None,
) -> State:
    """Feasible structured initialization (paper section IV, method a).

    Zero-load marginal weights D'_{ij}(0) give the uncongested shortest-path
    metric; the placement scores (14)-(15) under these weights pick initial
    hosts, and phi is initialized to the corresponding SP next-hop trees.
    `sp` optionally injects a precomputed `(dist, nexthop)` pair for the
    `zero_load_dp` metric (same contract as `repair_placement`); the engine's
    jitted init path passes None and fuses the APSP into its program.

    The joint host selection is an O(K V^2) Viterbi-style DP over the stage
    chain (cost-to-come M_p per candidate host, argmin backpointers, final
    leg to the destination) rather than the O(V^P) joint enumeration the
    P = 2 pair scan would become. At P = 2 the DP *is* the pair scan: the
    per-path float sums associate identically, and the final tie-break key
    (last backpointer, then host index) reproduces the row-major flat-argmin
    pair choice exactly. Phantom partitions (p >= parts) contribute identity
    transitions, so a stage-padded instance initializes bit-identically to
    its unpadded original (DESIGN.md section 13).
    """
    n = problem.net.n_nodes
    apps = problem.apps
    n_parts = apps.n_parts
    from . import costs as _costs

    if sp is None:
        dist, nexthop = apsp_with_nexthop(
            zero_load_dp(problem), use_pallas=use_pallas, interpret=interpret
        )
    else:
        dist, nexthop = sp

    cp0 = problem.cost.w_comp * _costs.comp_cost_prime(
        jnp.zeros_like(problem.net.nu), problem.net.nu, problem.cost
    )
    kappa0 = apps.w[:, :, None] * cp0[None, None, :]  # [A, P, V]

    L = apps.L
    dist_from_src = dist[apps.src, :]  # [A, V]
    dist_to_dst = dist[:, apps.dst].T  # [A, V]
    live = partition_live_mask(apps)  # [A, P]
    # L_{a, parts_a}: the packet size of each app's final (destination) leg.
    L_fin = jnp.take_along_axis(L, apps.parts[:, None], axis=1)[:, 0]  # [A]

    if colocate:
        S = L[:, 0][:, None] * dist_from_src
        for p in range(n_parts):
            S = S + kappa0[:, p, :] * live[:, p, None]
        S = S + L_fin[:, None] * dist_to_dst
        h = jnp.argmin(S, axis=-1).astype(jnp.int32)
        hosts = jnp.broadcast_to(h[:, None], (apps.n_apps, n_parts))
    else:
        # Forward DP over the partition chain: M_p(j) = cost-to-come of
        # hosting partition p at j, with smallest-index argmin backpointers.
        M = L[:, 0][:, None] * dist_from_src + kappa0[:, 0, :]  # [A, V]
        ptrs = []
        idx_j = jnp.arange(n, dtype=jnp.int32)[None, :]
        for p in range(1, n_parts):
            cand = M[:, :, None] + L[:, p][:, None, None] * dist[None]  # [A,V,V]
            ptr = jnp.argmin(cand, axis=1).astype(jnp.int32)  # [A, V]
            M_new = jnp.min(cand, axis=1) + kappa0[:, p, :]
            live_p = live[:, p] > 0  # [A]
            # Phantom transition: identity (cost-to-come and position pass
            # through unchanged), keeping the real chain's values bitwise.
            M = jnp.where(live_p[:, None], M_new, M)
            ptrs.append(jnp.where(live_p[:, None], ptr, idx_j))
        total = M + L_fin[:, None] * dist_to_dst  # [A, V]

        # Tie-break compatible with the historical P = 2 row-major flat
        # argmin over (h1, h2): among minimizing final hosts j, prefer the
        # one whose last *real* backpointer is smallest, then smallest j.
        m = jnp.min(total, axis=-1, keepdims=True)
        if ptrs:
            ptrs_arr = jnp.stack(ptrs, axis=1)  # [A, P-1, V]
            t_idx = jnp.clip(apps.parts - 2, 0, n_parts - 2)
            ptr_last = jnp.take_along_axis(
                ptrs_arr, t_idx[:, None, None], axis=1
            )[:, 0, :]
            ptr_last = jnp.where(apps.parts[:, None] >= 2, ptr_last, idx_j)
        else:
            ptr_last = jnp.broadcast_to(idx_j, total.shape)
        key = jnp.where(total == m, ptr_last * n + idx_j, n * n)
        h_last = jnp.argmin(key, axis=-1).astype(jnp.int32)

        hs = [None] * n_parts
        hs[n_parts - 1] = h_last
        for p in range(n_parts - 1, 0, -1):
            hs[p - 1] = jnp.take_along_axis(
                ptrs[p - 1], hs[p][:, None], axis=1
            )[:, 0]
        hosts = jnp.stack(hs, axis=1)  # [A, P]

    x = one_hot(hosts, n)  # [A, P, V]
    targets = stage_targets(apps, hosts)  # [A, K]
    stage_live = stage_live_mask(apps)  # [A, K]

    def per_stage(tgt, lv):
        m = (1.0 - jax.nn.one_hot(tgt, n, dtype=jnp.float32)) * lv
        return _sp_tree_phi(nexthop, tgt, m, n)

    phi = jax.vmap(jax.vmap(per_stage))(targets, stage_live)
    phi = phi * app_live_mask(apps)[:, None, None, None]
    return State(x=x, phi=phi)
