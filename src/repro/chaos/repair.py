"""Fleet-level failure repair: one vmapped `repair_placement` per epoch.

Bridges the per-instance repair primitive (core/placement.py) to the fleet
envelope the controller actually carries: the perturbed problems are padded
and stacked exactly like `solve_fleet` would stack them, the per-instance
live masks are extended with zeros over the pad tail (padded nodes ARE dead
nodes under the shared encoding), and `repair_placement` runs vmapped over
the instance axis. The result is a stacked `State` ready to hand to
`solve_fleet(warm_start=...)`.

Identity contract (inherited from `repair_placement`): with every mask
all-ones the returned State is bitwise the input — the empty-fault-trace
stability the tests pin.

`Apsp0Cache` caches the zero-load APSP behind that repair across control
epochs: the metric `zero_load_dp` depends only on (adj, mu, cost), and most
chaos epochs perturb none of them (flash crowds scale lam; event-free
epochs change nothing), so the [B, V, V] (dist, nexthop) pair from the
previous epoch can be reused by value-equality of the inputs — the same
controller-owned-snapshot pattern as `core.structs.HopBoundCache`. A hit
injects the cached pair through `repair_placement(sp=...)`; because the
cold path and the cache both evaluate the identical `zero_load_dp` program
on bitwise-identical inputs, reuse is exact, and
`launch.control --verify-apsp0` asserts that bitwise parity per epoch in
the chaos CI job.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from ..core.placement import repair_placement, zero_load_dp
from ..core.structs import State
from ..fleet.pad import stack_problems
from ..kernels.minplus import apsp_with_nexthop


@dataclasses.dataclass
class Apsp0Cache:
    """Host-side snapshot of one fleet's zero-load APSP — NOT a pytree.

    key     : np leaves of the stacked (adj, mu, cost) the pair was computed
              from (plus the static cost kind) — the full input closure of
              `zero_load_dp`, compared by VALUE each refresh
    dist    : [B, V, V] fp32 zero-load all-pairs distances
    nexthop : [B, V, V] int32 SP next hops
    reused  : whether the last refresh was a hit (feeds `control.apsp0.*`)
    hits / misses : lifetime refresh counters
    """

    key: tuple
    dist: "np.ndarray"
    nexthop: "np.ndarray"
    reused: bool = False
    hits: int = 0
    misses: int = 0

    def sp(self):
        """The `(dist, nexthop)` pair in `repair_placement(sp=...)` form."""
        return jnp.asarray(self.dist), jnp.asarray(self.nexthop)


def _apsp0_key(stacked) -> tuple:
    """Value key over everything `zero_load_dp` reads (kind is static)."""
    leaves = jax.tree_util.tree_leaves(
        (stacked.net.adj, stacked.net.mu, stacked.cost)
    )
    return (stacked.cost.kind,) + tuple(np.asarray(x) for x in leaves)


def _apsp0_key_equal(a: tuple, b: tuple) -> bool:
    if len(a) != len(b) or a[0] != b[0]:
        return False
    return all(
        x.shape == y.shape and x.dtype == y.dtype and np.array_equal(x, y)
        for x, y in zip(a[1:], b[1:])
    )


def refresh_apsp0(
    problems,
    cache: Apsp0Cache | None,
    *,
    round_to: int = 1,
    envelope=None,
    hop_bound=None,
    n_parts=None,
    use_pallas: bool = False,
    interpret: bool = False,
) -> Apsp0Cache:
    """Return a cache valid for this epoch's problems (hit or recompute).

    The envelope arguments must match what `repair_fleet` / `solve_fleet`
    use so the [B, V, V] shapes line up. On a hit the returned cache is the
    old one with `reused=True`; on a miss the APSP is recomputed from
    scratch (one jitted vmapped `apsp_with_nexthop` over `zero_load_dp`) —
    the exact computation the sp=None path of `repair_placement` would fuse,
    on the exact stacked inputs, which is what makes reuse bitwise-exact.
    """
    stacked, _ = stack_problems(
        problems, round_to=round_to, envelope=envelope, hop_bound=hop_bound,
        n_parts=n_parts,
    )
    key = _apsp0_key(stacked)
    if cache is not None and _apsp0_key_equal(cache.key, key):
        cache.reused = True
        cache.hits += 1
        return cache
    dist, nexthop = jax.jit(
        jax.vmap(
            lambda p: apsp_with_nexthop(
                zero_load_dp(p), use_pallas=use_pallas, interpret=interpret
            )
        )
    )(stacked)
    return Apsp0Cache(
        key=key,
        dist=np.asarray(dist),
        nexthop=np.asarray(nexthop),
        reused=False,
        hits=cache.hits if cache is not None else 0,
        misses=(cache.misses if cache is not None else 0) + 1,
    )


def repair_fleet(
    problems,
    state: State,
    live_masks,
    *,
    round_to: int = 1,
    envelope=None,
    hop_bound=None,
    n_parts=None,
    use_pallas: bool = False,
    interpret: bool = False,
    apsp0: Apsp0Cache | None = None,
) -> State:
    """Evict every dead-hosted partition across a fleet in one vmapped call.

    problems   : the PERTURBED problems (dead nodes already pad-encoded)
    state      : stacked [B, ...] State over the fleet envelope — typically
                 `FleetResult.state` from the previous epoch's
                 `solve_fleet(..., keep_state=True)`
    live_masks : per-instance [V_i] masks from `chaos.apply_health`
                 (1.0 = live); shorter than the envelope is fine, the pad
                 tail is dead by definition
    round_to / envelope / hop_bound / n_parts : must match what the solves
                 use, so the stacked envelope — and therefore the state
                 shape — agrees epoch over epoch
    apsp0      : a `refresh_apsp0` cache covering THIS epoch's problems;
                 its (dist, nexthop) pair is injected into every lane's
                 `repair_placement` (bitwise-identical to the fused sp=None
                 path). None keeps the APSP inside the vmapped program.
    """
    stacked, _ = stack_problems(
        problems, round_to=round_to, envelope=envelope, hop_bound=hop_bound,
        n_parts=n_parts,
    )
    b = len(problems)
    v_env = int(stacked.net.adj.shape[-1])
    exp = (b,) + tuple(stacked.apps.w.shape[1:]) + (v_env,)
    if tuple(state.x.shape) != exp:
        raise ValueError(
            f"repair_fleet: state placement shape {tuple(state.x.shape)} "
            f"does not match the fleet envelope {exp} — the envelope "
            "drifted since the state was produced; re-solve cold"
        )
    masks = np.zeros((b, v_env), np.float32)
    for i, m in enumerate(live_masks):
        m = np.asarray(m, dtype=np.float32)
        masks[i, : m.size] = m
    fn = functools.partial(
        repair_placement, use_pallas=use_pallas, interpret=interpret
    )
    if apsp0 is None:
        return jax.vmap(fn)(stacked, state, jnp.asarray(masks))
    return jax.vmap(lambda p, s, m, sp: fn(p, s, m, sp=sp))(
        stacked, state, jnp.asarray(masks), apsp0.sp()
    )
