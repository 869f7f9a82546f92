"""Jitted public wrappers around the min-plus kernel.

`use_pallas` selects the Pallas kernel, compiled for the TPU; CPU tests pass
`interpret=True` to run the kernel body under the Pallas interpreter. The
default pure-jnp path is the k-blocked streaming matmul (peak memory
O(V * block_k * V)); a launch enables the kernel path with `--use-pallas`
(see launch/fleet.py).

APSP has two strategies:

  * the jnp default is one exact Floyd-Warshall pass — V rank-1 relaxations
    `d <- min(d, d[:, k] + d[k, :])` that XLA fuses into a single streaming
    update per step, no O(V^3) candidate tensor and no log(V) sweep factor
    (~36x over the old one-broadcast squaring at V=512 on one CPU core);
  * the Pallas path (and any `n_iter`/warm-start caller) squares to a
    transitive fixpoint via `minplus_closure`, with an early exit: most
    topologies close in far fewer than the ceil(log2(V-1)) worst-case
    sweeps, and an extra squaring of a closed matrix is a bitwise no-op, so
    the early exit never changes the result — it only skips sweeps that
    would not have changed it.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from .kernel import minplus_matmul_argmin_pallas, minplus_matmul_pallas
from .ref import minplus_matmul_blocked, minplus_matmul_ref  # noqa: F401

BIG = 1e18
BIG_THRESHOLD = 1e17

# Target-column block width for the next-hop fallback: the per-block carries
# ([V, block] value + index) stay cache-resident on the CPU path.
_NEXTHOP_BLOCK_T = 128


def minplus_matmul(a, b, *, use_pallas: bool = False, interpret: bool = False):
    if use_pallas:
        return minplus_matmul_pallas(a, b, interpret=interpret)
    return minplus_matmul_blocked(a, b)


def squaring_bound(n: int) -> int:
    """Sweeps that provably close any [n, n] seed: paths double per sweep."""
    return max(1, math.ceil(math.log2(max(n - 1, 2))))


def minplus_closure(
    d: jax.Array,
    *,
    n_iter: int | None = None,
    early_exit: bool = True,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    """Close `d` to its transitive (min,+) fixpoint by repeated squaring.

    `d` must be reflexive (zero diagonal) so squaring only ever shortens:
    d <- min(d, d (x) d). With `early_exit` the loop stops one sweep after
    the matrix stops changing (a fixpoint stays fixed, so the skipped sweeps
    are bitwise no-ops); `n_iter` overrides the worst-case sweep cap.
    Also the warm-start re-closure primitive for incremental hop bounds
    (core/structs.hop_bound_cache): a seed that already contains every
    1-hop edge closes under the same doubling argument.
    """
    n = d.shape[-1]
    sweeps = squaring_bound(n) if n_iter is None else max(1, int(n_iter))

    def sweep(x):
        return jnp.minimum(
            x, minplus_matmul(x, x, use_pallas=use_pallas, interpret=interpret)
        )

    if not early_exit:
        for _ in range(sweeps):
            d = sweep(d)
        return d

    def cond(carry):
        _, i, changed = carry
        return jnp.logical_and(i < sweeps, changed)

    def body(carry):
        x, i, _ = carry
        x_new = sweep(x)
        return x_new, i + 1, jnp.any(x_new != x)

    d, _, _ = jax.lax.while_loop(cond, body, (d, jnp.int32(0), jnp.bool_(True)))
    return d


def _apsp_fw(d: jax.Array) -> jax.Array:
    """One exact Floyd-Warshall pass: V fused rank-1 (min,+) relaxations."""
    v = d.shape[-1]

    def body(k, d):
        row = jax.lax.dynamic_slice_in_dim(d, k, 1, axis=0)  # [1, V]
        col = jax.lax.dynamic_slice_in_dim(d, k, 1, axis=1)  # [V, 1]
        return jnp.minimum(d, col + row)

    return jax.lax.fori_loop(0, v, body, d)


@functools.partial(
    jax.jit, static_argnames=("n_iter", "early_exit", "use_pallas", "interpret")
)
def apsp(
    w: jax.Array,
    *,
    n_iter: int | None = None,
    early_exit: bool = True,
    use_pallas: bool = False,
    interpret: bool = False,
):
    """All-pairs shortest-path distances.

    w: [V, V] nonnegative marginal link weights, BIG on non-edges. The
    diagonal is forced to 0 (paths may stay put). Returns [V, V] distances
    (BIG-ish where unreachable).

    The jnp default runs Floyd-Warshall (exact, single pass, O(V^2) memory).
    `use_pallas` — or an explicit `n_iter` sweep override — selects the
    tropical-squaring closure instead (the blocked Pallas kernel's native
    shape); `early_exit` then stops squaring once the matrix is closed.
    """
    n = w.shape[-1]
    d = jnp.where(jnp.eye(n, dtype=bool), 0.0, w.astype(jnp.float32))
    if not use_pallas and n_iter is None:
        return _apsp_fw(d)
    return minplus_closure(
        d,
        n_iter=n_iter,
        early_exit=early_exit,
        use_pallas=use_pallas,
        interpret=interpret,
    )


def _nexthop_blocked(w: jax.Array, dist: jax.Array) -> jax.Array:
    """argmin_j w[i, j] + dist[j, t] without the [V, V, V] candidate tensor.

    Target columns are scanned in `_NEXTHOP_BLOCK_T`-wide blocks; within a
    block, j advances as V fused rank-1 relaxations carrying (best, idx) —
    no argmin reduction ever runs, only elementwise compare/select on
    cache-resident [V, block] carries (the reduce-based argmin is ~4x
    slower on CPU). Strict `<` with ascending j reproduces the full-tensor
    `jnp.argmin` first-minimum tie-break exactly. Peak memory O(V^2).
    """
    v = w.shape[-1]
    bt = min(v, _NEXTHOP_BLOCK_T)
    pad = (-v) % bt
    nb = (v + pad) // bt
    d_cols = jnp.pad(dist, ((0, 0), (0, pad)), constant_values=jnp.inf)
    d3 = jnp.moveaxis(d_cols.reshape(v, nb, bt), 1, 0)  # [nb, V, bt]

    def block(_, d_b):  # d_b = dist[:, t0:t0+bt]
        def body(j, carry):
            best, idx = carry
            cand = jax.lax.dynamic_slice_in_dim(
                w, j, 1, axis=1
            ) + jax.lax.dynamic_slice_in_dim(d_b, j, 1, axis=0)
            upd = cand < best
            return jnp.where(upd, cand, best), jnp.where(upd, j, idx)

        best0 = jnp.full((v, bt), jnp.inf, jnp.float32)
        idx0 = jnp.zeros((v, bt), jnp.int32)
        _, idx = jax.lax.fori_loop(0, v, body, (best0, idx0))
        return None, idx

    _, nh = jax.lax.scan(block, None, d3)  # [nb, V, bt]
    nh = jnp.moveaxis(nh, 0, 1).reshape(v, nb * bt)
    return nh[:, :v]


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def apsp_with_nexthop(w: jax.Array, *, use_pallas: bool = False, interpret: bool = False):
    """APSP distances + next-hop table.

    nexthop[i, t] = argmin_j  w[i, j] + dist[j, t]   (j over out-links of i)

    Following next-hops strictly decreases dist[., t], so the induced
    forwarding is loop-free by construction (used for phi repair/init).
    On the Pallas path the table comes from the fused min+argmin kernel
    (kernel.py); the fallback scans target-column blocks. Both paths are
    O(V^2) peak memory and share the first-minimum tie-break.
    """
    dist = apsp(w, use_pallas=use_pallas, interpret=interpret)
    if use_pallas:
        _, nexthop = minplus_matmul_argmin_pallas(w, dist, interpret=interpret)
    else:
        nexthop = _nexthop_blocked(w, dist)
    return dist, nexthop
