"""Public wrappers around the Neumann propagation solve.

`neumann_solve(m, b)` solves (I - m) x = b for stacked batches of
propagation operators (m: [..., V, V], b: [..., V]) by the truncated Neumann
recurrence x <- b + m x, wrapped in `jax.lax.custom_linear_solve` so that

  * reverse-mode differentiation works without unrolling the hop loop
    (the cotangent solve is itself a Neumann solve on m^T, via
    `transpose_solve`), keeping Gallager's identity test (grad == q) on the
    propagation path;
  * the forward pass is free to use a genuine early-exit `while_loop`
    (not reverse-differentiable on its own) or the fused Pallas kernel.

Hop budget: the exact part of the series is bounded by the longest
forwarding path (<= graph diameter + 2 host re-injections for loop-free
phi; `Problem.hop_bound` carries that). Mid-refinement, the blocking rule
tolerates transient cycles whose gain shrinks geometrically (DESIGN.md
section 10), so `effective_hops` adds a fixed slack that the early-exit
check makes free whenever phi is already nilpotent.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ...obs.trace import engine_scope
from .kernel import HIGHEST, neumann_solve_pallas

# Extra hops past the nilpotent bound, absorbing the geometric tail of
# blocking-rule transient cycles (gain <= 1 - alpha per sweep; see
# DESIGN.md section 10 for the derivation). Early exit makes the slack
# cost nothing once phi is loop-free.
NEUMANN_SLACK = 32

# Early-exit threshold: consecutive iterates agreeing to this relative
# tolerance terminate the hop loop (fp32 headroom below the 1e-5 parity
# contract with the LU path).
DEFAULT_TOL = 1e-6


def effective_hops(
    hop_bound: int | None, n_nodes: int, fixed_loop: bool = False
) -> int:
    """Hop cap for one solve.

    With `fixed_loop=False` (the XLA while_loop path) the floor is the
    nilpotency-index bound V + 1 — refined multipath forwarding may route
    along loop-free paths longer than the diameter, so the Problem-carried
    bound alone is the *expected* exit point (where the early-exit check
    typically fires), not a hard guarantee. Maxing with V + 1 makes the cap
    exact for every truly nilpotent phi, and costs nothing: the while_loop
    exits on the residual. The slack then only has to absorb the geometric
    tail of transient blocking-rule cycles (gain <= 1 - alpha per sweep —
    at very small alpha that tail thins slowly and the cap can truncate;
    parity with LU is then governed by the residual tolerance, see
    DESIGN.md section 10).

    With `fixed_loop=True` (the fused Pallas kernel, whose fori_loop always
    executes every hop — 'done' only freezes the carry) the V + 1 floor
    would cost O(V^3) wasted matvecs, so the cap is hop_bound + slack: the
    kernel trades exactness on longer-than-diameter multipath chains for
    the O(V/H) roofline advantage it exists for."""
    base = int(hop_bound) if hop_bound is not None else n_nodes + 1
    if not fixed_loop:
        base = max(base, n_nodes + 1)
    return base + NEUMANN_SLACK


def _bmv(m: jax.Array, x: jax.Array) -> jax.Array:
    """Batched matvec (m x) over arbitrary shared leading dims.

    HIGHEST keeps the products fp32 on the TPU, where XLA's default rounds
    dot operands to bf16 and would move the fixed point by ~1e-3 relative;
    the matvec is memory-bound, so the extra MXU passes are free.
    """
    return jnp.einsum("...ij,...j->...i", m, x, precision=HIGHEST)


def _propagate_xla(m: jax.Array, b: jax.Array, hops: int, tol: float) -> jax.Array:
    """Early-exit propagation: x <- b + m x until every iterate settles.

    One while_loop drives the whole stacked solve — each hop is a single
    batched matvec (BLAS-3 shaped on TPU, one fused einsum on CPU). The
    convergence test is PER batch element (residual vs that element's own
    magnitude): a batch-global relative residual would let a large
    fast-converging element mask a small slow-converging one and truncate
    its series arbitrarily early. The loop runs until the slowest element
    converges; already-settled elements keep iterating but their iterates
    are fixed points, so extra hops leave them bitwise unchanged.
    """

    def cond(carry):
        _, k, unconverged = carry
        return jnp.logical_and(k < hops, unconverged)

    def body(carry):
        x, k, _ = carry
        x_new = b + _bmv(m, x)
        resid = jnp.max(jnp.abs(x_new - x), axis=-1)   # [...batch]
        scale = jnp.max(jnp.abs(x_new), axis=-1)       # [...batch]
        unconverged = jnp.any(resid > tol * scale + 1e-30)
        return x_new, k + 1, unconverged

    init = (b, jnp.int32(0), jnp.bool_(True))
    x, _, _ = jax.lax.while_loop(cond, body, init)
    return x


def _propagate_pallas(
    m: jax.Array, b: jax.Array, hops: int, tol: float, interpret: bool,
    block_k: int | None = None, operand_dtype=None,
) -> jax.Array:
    """Flatten leading batch dims and run the fused kernel."""
    batch_shape = b.shape[:-1]
    v = b.shape[-1]
    m2 = m.reshape((-1, v, v))
    b2 = b.reshape((-1, v))
    out = neumann_solve_pallas(
        m2, b2, hops=hops, tol=tol, interpret=interpret,
        block_k=block_k, operand_dtype=operand_dtype,
    )
    return out.reshape(batch_shape + (v,))


def neumann_solve(
    m: jax.Array,
    b: jax.Array,
    *,
    hops: int,
    tol: float = DEFAULT_TOL,
    use_pallas: bool = False,
    interpret: bool = False,
    block_k: int | None = None,
    operand_dtype=None,
) -> jax.Array:
    """Solve (I - m) x = b by truncated Neumann propagation.

    m: [..., V, V] propagation operator (pass phi^T for the traffic fixed
    point (I - Phi^T) t = b, phi for the cost-to-go (I - Phi) q = c);
    b: [..., V] with matching batch dims. Differentiable in both m and b.

    `block_k` / `operand_dtype` select the K-tiled Pallas kernel explicitly
    (V > MAX_VMEM_V auto-tiles); `operand_dtype=jnp.bfloat16` streams the
    operator in bf16 with fp32 accumulation (kernel.py). Both are ignored
    on the XLA path.
    """

    def run(op, rhs):
        if use_pallas:
            return _propagate_pallas(
                op, rhs, hops, tol, interpret,
                block_k=block_k, operand_dtype=operand_dtype,
            )
        return _propagate_xla(op, rhs, hops, tol)

    with engine_scope("neumann"):
        mt = jnp.swapaxes(m, -1, -2)
        return jax.lax.custom_linear_solve(
            lambda x: x - _bmv(m, x),
            b,
            solve=lambda _, rhs: run(m, rhs),
            transpose_solve=lambda _, rhs: run(mt, rhs),
        )
