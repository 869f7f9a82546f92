"""Core data structures for the joint placement/routing problem (paper Eq. 1-7).

All structures are registered JAX pytrees so the whole optimizer state can be
jitted / vmapped / sharded. Shapes use the conventions:

    V  = number of nodes
    A  = number of applications (DNN inference services)
    P  = number of DNN partitions carried by the arrays (the *structural*
         partition axis; partition p consumes stage-p traffic, emits stage p+1)
    K  = P + 1 traffic stages (0: raw input, 1..P-1: intermediate features,
         and the final stage toward the destination)

The partition count is per-`Problem` DATA, not a structural constant: each
app carries its effective split depth in `Apps.parts` (1 <= parts <= P), and
partitions/stages past `parts` are inert phantoms (w = 0, L = 0, zero
forwarding mass — see DESIGN.md section 13). The paper's evaluation uses
P = 2 / K = 3 (`N_PARTS` / `K_STAGES` below record those defaults), but every
kernel in this package is generic over the stage axis.
"""
from __future__ import annotations

import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

# A large-but-finite stand-in for +inf: safe under addition in the tropical
# (min,+) semiring without producing inf-inf NaNs inside kernels.
BIG = jnp.float32(1e18)
# Threshold above which a distance is considered unreachable.
BIG_THRESHOLD = jnp.float32(1e17)

# The paper's evaluation defaults (section IV): two partitions, three stages.
# These are *defaults* for scenario construction, not structural invariants —
# the solver stack is generic over the stage axis (DESIGN.md section 13).
K_STAGES = 3
N_PARTS = 2


def _register(cls, data_fields, meta_fields=()):
    jax.tree_util.register_dataclass(
        cls, data_fields=list(data_fields), meta_fields=list(meta_fields)
    )
    return cls


@dataclasses.dataclass(frozen=True)
class Network:
    """Directed multi-hop edge network G = (V, E) with heterogeneous resources.

    adj : [V, V] float {0,1} adjacency (adj[i,j]=1 iff link (i,j) in E)
    mu  : [V, V] link service rate (bit/s);  BIG where no link (never used)
    nu  : [V]    node computation service rate
    """

    adj: jax.Array
    mu: jax.Array
    nu: jax.Array

    @property
    def n_nodes(self) -> int:
        return self.adj.shape[-1]


_register(Network, ["adj", "mu", "nu"])


@dataclasses.dataclass(frozen=True)
class Apps:
    """The set A of DNN inference services.

    src   : [A] int32  source node s_a
    dst   : [A] int32  destination node d_a (may equal src)
    lam   : [A] input request rate lambda_a (requests/s)
    L     : [A, K] packet size of stage k (bits/request); entries past an
            app's effective stage count (`parts` + 1) are 0
    w     : [A, P] per-request computation workload of partition p
            (node heterogeneity is carried by nu in C_i; DESIGN.md section 8)
    parts : [A] int32 effective partition count of each app (1 <= parts <= P).
            Stage `parts` is the app's final stage (absorbed at d_a); stages
            past it are phantom padding with zero forwarding mass. Defaults
            to the structural P = w.shape[-1] when omitted.
    """

    src: jax.Array
    dst: jax.Array
    lam: jax.Array
    L: jax.Array
    w: jax.Array
    parts: jax.Array | None = None

    def __post_init__(self):
        if self.parts is None:
            w = self.w
            object.__setattr__(
                self,
                "parts",
                jnp.full(w.shape[:-1], w.shape[-1], dtype=jnp.int32),
            )

    @property
    def n_apps(self) -> int:
        return self.src.shape[-1]

    @property
    def n_parts(self) -> int:
        """Structural partition-axis length P (>= every per-app `parts`)."""
        return self.w.shape[-1]

    @property
    def n_stages(self) -> int:
        """Structural stage-axis length K = P + 1."""
        return self.L.shape[-1]


_register(Apps, ["src", "dst", "lam", "L", "w", "parts"])


@dataclasses.dataclass(frozen=True)
class CostModel:
    """Congestion cost configuration.

    kind      : "mm1" (D(F)=F/(mu-F), C(G)=G/(nu-G)) or "linear" (F/mu, G/nu)
    rho_max   : load fraction beyond which the M/M/1 curve is continued by a
                C^1 quadratic extension (keeps J finite + differentiable for
                infeasible iterates; see DESIGN.md section 8)
    w_comm / w_comp : objective weights (eta, 1-eta) for the Fig-5 tradeoff;
                (1, 1) reproduces the paper's main unweighted objective.

    `rho_max` / `w_comm` / `w_comp` are pytree *data* leaves (scalars), so
    cost models may differ per instance inside a stacked fleet (e.g. the
    Fig-5 eta grid solved as one batch — see fleet/solve.py). Only `kind`
    is static metadata: it selects a code path, so a fleet must share it.
    """

    kind: str = "mm1"
    rho_max: float = 0.95
    w_comm: float = 1.0
    w_comp: float = 1.0


_register(CostModel, ["rho_max", "w_comm", "w_comp"], ["kind"])


@dataclasses.dataclass(frozen=True)
class Problem:
    """One placement/routing instance, plus solver-facing static metadata.

    hop_bound : static bound on the *typical* loop-free forwarding path
        (unweighted graph diameter + 2 host re-injections) — the expected
        early-exit point of the Neumann propagation solver's hop loop. The
        solver's hard cap floors this with the nilpotency-index bound V + 1
        (kernels/neumann.effective_hops), so refined multipath paths longer
        than the diameter stay exact. `None` means unknown (the floor alone
        applies). Static metadata (it sizes a loop), so fleets unify it to
        the batch max before stacking (fleet/pad.py).
    """

    net: Network
    apps: Apps
    cost: CostModel
    hop_bound: int | None = None


_register(Problem, ["net", "apps", "cost"], ["hop_bound"])


@dataclasses.dataclass(frozen=True)
class HopBoundCache:
    """Host-side snapshot of the unweighted distance closure behind one
    `infer_hop_bound` answer — NOT a pytree; it lives with the controller.

    adj       : [V, V] bool adjacency the closure was computed for
    dist      : [V, V] fp32 exact unweighted hop counts (integers below
                2^24, so every entry is exact in fp32; BIG where unreachable)
    hop_bound : the derived diameter + 2
    sweeps    : re-closure squaring sweeps the last refresh took
                (0 = adjacency unchanged, -1 = cold from-scratch solve) —
                the controller's `control.hop_bound.sweeps` metric
    """

    adj: "np.ndarray"
    dist: "np.ndarray"
    hop_bound: int
    sweeps: int = -1


def _unweighted_seed(adj: jax.Array) -> jax.Array:
    """[V, V] reflexive 1/BIG hop weights for the unweighted closure."""
    v = adj.shape[-1]
    w = jnp.where(adj > 0, 1.0, BIG)
    return jnp.where(jnp.eye(v, dtype=bool), 0.0, w)


def _hop_bound_of(dist: "np.ndarray") -> int:
    diam = float(np.max(np.where(dist < BIG_THRESHOLD, dist, 0.0)))
    return int(diam) + 2


def _warm_unweighted_closure(adj_new, cache: HopBoundCache, *, use_pallas, interpret):
    """Re-close the previous epoch's distances after a local adjacency change.

    Exactness argument (DESIGN.md section 16): let S be the touched nodes
    (any row/column of the adjacency delta). An old entry can only be wrong
    if its optimal path visited S, and every such pair satisfies
    `min_{s in S} d_old[i,s] + d_old[s,j] <= d_old[i,j]` — one masked
    (min,+) product finds them all. Those entries are invalidated to BIG;
    the surviving entries are still exact path lengths in the NEW graph
    (their paths avoid S entirely), so the seed `min(filtered, w_new)`
    contains every 1-hop edge and only valid upper bounds. Its transitive
    closure is therefore the from-scratch answer — and all values are exact
    fp32 integers, so the result is bitwise identical to a cold solve. The
    closure loop exits one sweep after the fixpoint; local perturbations
    typically re-close in 1-2 sweeps.
    """
    from ..kernels.minplus import minplus_matmul, squaring_bound

    changed = adj_new != cache.adj
    touched = jnp.asarray(changed.any(axis=0) | changed.any(axis=1))  # [V]
    d_old = jnp.asarray(cache.dist)
    cols = jnp.where(touched[None, :], d_old, BIG)  # keep d_old[i, s]
    rows = jnp.where(touched[:, None], d_old, BIG)  # keep d_old[s, j]
    via = minplus_matmul(cols, rows, use_pallas=use_pallas, interpret=interpret)
    stale = via <= d_old
    seed = jnp.minimum(
        jnp.where(stale, BIG, d_old), _unweighted_seed(jnp.asarray(adj_new))
    )
    sweeps = 0
    for _ in range(squaring_bound(seed.shape[-1])):
        nxt = jnp.minimum(
            seed,
            minplus_matmul(seed, seed, use_pallas=use_pallas, interpret=interpret),
        )
        sweeps += 1
        closed = bool(jnp.all(nxt == seed))
        seed = nxt
        if closed:
            break
    return seed, sweeps


def hop_bound_cache(
    net: Network,
    cache: HopBoundCache | None = None,
    *,
    use_pallas: bool = False,
    interpret: bool = False,
) -> HopBoundCache:
    """Compute (or incrementally refresh) the unweighted closure behind
    `infer_hop_bound`.

    With a `cache` from a previous round/epoch the refresh is warm-started
    from the cached distances — bitwise identical to a cold solve (see
    `_warm_unweighted_closure`) but one or two squaring sweeps instead of a
    full APSP. An unchanged adjacency returns immediately.
    """
    adj = np.asarray(net.adj) > 0
    if cache is not None and cache.adj.shape == adj.shape:
        if np.array_equal(cache.adj, adj):
            return dataclasses.replace(cache, sweeps=0)
        d, sweeps = _warm_unweighted_closure(
            adj, cache, use_pallas=use_pallas, interpret=interpret
        )
    else:
        from ..kernels.minplus import apsp

        d = apsp(
            _unweighted_seed(jnp.asarray(adj)),
            use_pallas=use_pallas,
            interpret=interpret,
        )
        sweeps = -1
    dist = np.asarray(d)
    return HopBoundCache(
        adj=adj, dist=dist, hop_bound=_hop_bound_of(dist), sweeps=sweeps
    )


def infer_hop_bound(
    net: Network,
    cache: HopBoundCache | None = None,
    *,
    use_pallas: bool = False,
    interpret: bool = False,
) -> int:
    """Unweighted graph diameter plus 2, covering one host re-injection per
    stage hand-off.

    Concrete (Python-int) by construction: call at problem build time, not
    inside traced code. Pass the previous round's `HopBoundCache` (see
    `hop_bound_cache`) to warm-start the closure after a local topology
    change."""
    return hop_bound_cache(
        net, cache, use_pallas=use_pallas, interpret=interpret
    ).hop_bound


def with_hop_bound(problem: Problem, cache: HopBoundCache | None = None) -> Problem:
    """Attach the inferred hop bound (no-op if already carried)."""
    if problem.hop_bound is not None:
        return problem
    return dataclasses.replace(problem, hop_bound=infer_hop_bound(problem.net, cache))


@dataclasses.dataclass(frozen=True)
class State:
    """Decision variables of problem (7).

    x   : [A, P, V] one-hot placement (x[a, p, i] = 1 iff partition p+1 at i)
    phi : [A, K, V, V] forwarding fractions phi_{ij}^{a,k}
    """

    x: jax.Array
    phi: jax.Array

    def hosts(self) -> jax.Array:
        """[A, P] int32 host node of each partition (phantom partitions
        carry a harmless real-node index; see DESIGN.md section 13)."""
        return jnp.argmax(self.x, axis=-1)


_register(State, ["x", "phi"])


def one_hot(idx: jax.Array, n: int) -> jax.Array:
    return jax.nn.one_hot(idx, n, dtype=jnp.float32)


def app_live_mask(apps: Apps) -> jax.Array:
    """[A] 1.0 for apps with positive arrival rate, else 0.0.

    Zero-rate apps route nothing, so they carry zero forwarding mass
    (phi = 0, hence (I - Phi^T) = I on their stages). This is what keeps
    fleet padding inert: a padded phantom app must never accumulate a
    cyclic phi-support that would make the flow solve singular
    (DESIGN.md section 9)."""
    return (apps.lam > 0).astype(jnp.float32)


def partition_live_mask(apps: Apps) -> jax.Array:
    """[A, P] 1.0 where partition p is within the app's effective split
    depth (`p < parts`), 0.0 on phantom partitions."""
    p = jnp.arange(apps.w.shape[-1])
    return (p[None, :] < apps.parts[..., None]).astype(jnp.float32)


def stage_live_mask(apps: Apps) -> jax.Array:
    """[A, K] 1.0 where stage k exists for the app (`k <= parts`; stage
    `parts` is the final leg toward d_a), 0.0 on phantom stages."""
    k = jnp.arange(apps.L.shape[-1])
    return (k[None, :] <= apps.parts[..., None]).astype(jnp.float32)


def stage_targets(apps: Apps, hosts: jax.Array) -> jax.Array:
    """[A, K] int32 absorption target of each stage given partition `hosts`
    [A, P]: the partition-(k+1) host for k < parts, the destination for every
    later stage (phantom stages carry zero mass; their target only gives the
    repair logic a stable, never-changing anchor)."""
    k = jnp.arange(apps.L.shape[-1])
    hosts_pad = jnp.concatenate([hosts, hosts[..., -1:]], axis=-1)  # [A, K]
    return jnp.where(
        k[None, :] < apps.parts[..., None], hosts_pad, apps.dst[..., None]
    ).astype(jnp.int32)


@partial(jax.jit, static_argnames=("n",))
def forwarding_mass(state: State, apps: Apps, n: int) -> jax.Array:
    """[A, K, V] total forwarding fraction each node must emit per stage.

    Eq. (2a): sum_j phi^{a,k}_{ij} = 1 - x^{a,k+1}_i for k < parts
              (the partition-(k+1) host absorbs the stage)
    Eq. (2b): sum_j phi^{a,parts}_{ij} = 0 at d_a else 1 (final stage).
    Phantom stages (k > parts) and apps with lambda_a = 0 carry zero mass
    (see app_live_mask / stage_live_mask)."""
    dst_oh = one_hot(apps.dst, n)  # [A, V]
    k = jnp.arange(state.phi.shape[-3])[None, :, None]  # [1, K, 1]
    parts = apps.parts[:, None, None]  # [A, 1, 1]
    x_pad = jnp.concatenate(
        [state.x, jnp.zeros_like(state.x[:, :1])], axis=1
    )  # [A, K, V]
    m = jnp.where(
        k < parts,
        1.0 - x_pad,
        jnp.where(k == parts, 1.0 - dst_oh[:, None, :], 0.0),
    )
    return m * app_live_mask(apps)[:, None, None]
