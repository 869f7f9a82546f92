"""Pallas TPU kernel: fused batched Neumann propagation hops.

Solves (I - M) x = b for a batch of independent [V, V] operators by keeping
each operator resident in VMEM and iterating the propagation recurrence

    x <- b + M x

for a fixed (Problem-derived) hop cap, with an early-exit residual check
folded into the loop: once two consecutive iterates agree to `tol`
(relative), the carry freezes and the remaining hops are no-ops. On the LU
path every solve re-factorizes a [V, V] matrix from HBM at O(V^3) MXU-hostile
work; here the operator is loaded once and each hop is a single [1, Vp] x
[Vp, Vp] MXU matvec at O(V^2), so a hop cap H gives an O(V/H) flop advantage
and a single-load memory profile (the roofline argument in DESIGN.md
section 10).

Layout: the caller passes the *transposed* operator W = M^T so the iterate
can live as a row vector — x_new = b + x @ W — which keeps the V axis on the
lane dimension (128-aligned) and the matvec on the MXU. Batch is the grid's
only dimension; each grid step owns one operator.

VMEM budget per grid step (fp32): W tile Vp^2 * 4 B + three [1, Vp] rows.
Vp = 512 -> 1 MiB, Vp = 1024 -> 4 MiB. Past `MAX_VMEM_V` the operator no
longer fits VMEM whole, so the wrapper switches to the K-TILED kernel: the
grid grows (hops, k_tiles) axes, W streams through VMEM as [block_k, Vp]
row tiles, and the iterate x plus the hop accumulator live in VMEM scratch
that persists across the sequential grid steps (the standard Pallas-TPU
revisiting pattern — scratch carries state between grid iterations of the
same batch element). An opt-in `operand_dtype=jnp.bfloat16` streams W (and
feeds the MXU) in bf16 while the accumulator, iterate, and residual check
stay fp32 — the mixed-precision contract of DESIGN.md section 16.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANE = 128
# fp32 products in every fp32 dot: the TPU's default contraction precision
# may round fp32 operands to bf16. (Mosaic refuses it on bf16 operands, whose
# products are exact anyway.)
HIGHEST = jax.lax.Precision.HIGHEST
MAX_VMEM_V = 1024
# Contraction-axis tile of the K-tiled kernel: [block_k, Vp] W row tiles.
# 512 keeps the streamed tile at Vp = 2048 under 4 MiB in fp32.
DEFAULT_BLOCK_K = 512


def _neumann_kernel(w_ref, b_ref, o_ref, *, hops: int, tol: float):
    """One grid step: propagate one batch element's RHS through W = M^T."""
    b = b_ref[...]  # [1, Vp]
    w = w_ref[...]  # [Vp, Vp]

    def body(_, carry):
        x, done = carry
        x_new = b + jnp.dot(
            x, w, precision=HIGHEST, preferred_element_type=jnp.float32
        )
        resid = jnp.max(jnp.abs(x_new - x))
        scale = jnp.max(jnp.abs(x_new)) + 1e-30
        done_new = jnp.logical_or(done, resid <= tol * scale)
        return jnp.where(done, x, x_new), done_new

    x, _ = jax.lax.fori_loop(0, hops, body, (b, jnp.bool_(False)))
    o_ref[...] = x


def _neumann_tiled_kernel(
    w_ref, b_ref, o_ref, x_ref, acc_ref, done_ref, *,
    hops: int, tol: float, nk: int, bk: int,
):
    """K-tiled grid step: one [block_k, Vp] W row tile of one hop.

    Grid (batch, hops, k_tiles), K innermost. Scratch persists across the
    sequential (hops, k_tiles) steps of one batch element:

      x_ref    [1, Vp] fp32 VMEM — the current iterate
      acc_ref  [1, Vp] fp32 VMEM — this hop's b + x @ W partial sum
      done_ref [1] int32 SMEM    — the residual-freeze flag

    The hop closes on the last K tile with the exact done-before-freeze
    semantics of `_neumann_kernel`: the converging iteration's x_new IS
    applied, later hops keep the frozen carry. When W streams in bf16 the
    x chunk is cast to match, but the dot always accumulates fp32
    (`preferred_element_type`) and the residual test runs on the fp32
    scratch values.
    """
    h = pl.program_id(1)
    kk = pl.program_id(2)

    @pl.when(jnp.logical_and(h == 0, kk == 0))
    def _init():
        x_ref[...] = b_ref[...]
        done_ref[0] = 0

    @pl.when(kk == 0)
    def _reset():
        acc_ref[...] = b_ref[...]

    x_chunk = x_ref[:, pl.ds(kk * bk, bk)]  # [1, bk]
    w_tile = w_ref[...]  # [bk, Vp], possibly bf16
    acc_ref[...] += jnp.dot(
        x_chunk.astype(w_tile.dtype), w_tile,
        precision=HIGHEST if w_tile.dtype == jnp.float32 else None,
        preferred_element_type=jnp.float32,
    )

    @pl.when(kk == nk - 1)
    def _finish():
        x_old = x_ref[...]
        x_new = acc_ref[...]
        resid = jnp.max(jnp.abs(x_new - x_old))
        scale = jnp.max(jnp.abs(x_new)) + 1e-30
        done = done_ref[0] > 0
        x_ref[...] = jnp.where(done, x_old, x_new)
        done_ref[0] = jnp.logical_or(done, resid <= tol * scale).astype(
            jnp.int32
        )

    @pl.when(jnp.logical_and(h == hops - 1, kk == nk - 1))
    def _out():
        o_ref[...] = x_ref[...]


def _rows(b: jax.Array, pad_v: int) -> jax.Array:
    """[N, V] -> zero-padded [N, 1, Vp]: one row-vector block per grid step.

    A [1, Vp] block of an [N, Vp] array is refused by Mosaic (the sublane
    block dim must be a multiple of 8 or the whole axis); as the trailing
    [1, Vp] of an [N, 1, Vp] array it is the whole of both minor axes.
    """
    return jnp.pad(b, ((0, 0), (0, pad_v)))[:, None, :]


@functools.partial(
    jax.jit,
    static_argnames=("hops", "tol", "interpret", "block_k", "operand_dtype"),
)
def neumann_solve_pallas(
    m: jax.Array,
    b: jax.Array,
    *,
    hops: int,
    tol: float = 1e-6,
    interpret: bool = False,
    block_k: int | None = None,
    operand_dtype=None,
) -> jax.Array:
    """x = (I - m)^{-1} b (truncated Neumann) for m: [N, V, V], b: [N, V].

    The V axis is zero-padded to a lane multiple; padded coordinates carry
    zero source and zero coupling, so they stay exactly zero through every
    hop and never contaminate the valid region (bf16 casts preserve exact
    zeros, so the invariant survives mixed precision too).

    Dispatch: V <= MAX_VMEM_V with default precision keeps the original
    single-tile kernel (operator resident in VMEM, fori_loop over hops).
    Larger V — or an explicit `block_k` / `operand_dtype` — selects the
    K-tiled kernel: grid (batch, hops, k_tiles) with W streamed as
    [block_k, Vp] row tiles and the iterate carried in VMEM scratch.
    `operand_dtype=jnp.bfloat16` halves the streamed W traffic; the
    accumulator and residual check stay fp32.
    """
    n_batch, v, v2 = m.shape
    assert v == v2 and b.shape == (n_batch, v), (m.shape, b.shape)
    assert hops >= 1, hops
    m = m.astype(jnp.float32)
    b = b.astype(jnp.float32)

    if v <= MAX_VMEM_V and block_k is None and operand_dtype is None:
        pad_v = (-v) % LANE
        vp = v + pad_v
        w = jnp.pad(
            jnp.swapaxes(m, -1, -2), ((0, 0), (0, pad_v), (0, pad_v))
        )
        b_p = _rows(b, pad_v)
        out = pl.pallas_call(
            functools.partial(_neumann_kernel, hops=hops, tol=tol),
            grid=(n_batch,),
            in_specs=[
                pl.BlockSpec((None, vp, vp), lambda i: (i, 0, 0)),
                pl.BlockSpec((None, 1, vp), lambda i: (i, 0, 0)),
            ],
            out_specs=pl.BlockSpec((None, 1, vp), lambda i: (i, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((n_batch, 1, vp), jnp.float32),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",)
            ),
            interpret=interpret,
        )(w, b_p)
        return out[:, 0, :v]

    bk = DEFAULT_BLOCK_K if block_k is None else int(block_k)
    if bk % LANE:
        raise ValueError(f"block_k must be a multiple of {LANE}, got {bk}")
    bk = min(bk, -(-v // LANE) * LANE)
    vp = -(-v // bk) * bk  # pad V to a whole number of K tiles
    nk = vp // bk
    pad_v = vp - v
    w = jnp.pad(jnp.swapaxes(m, -1, -2), ((0, 0), (0, pad_v), (0, pad_v)))
    if operand_dtype is not None:
        w = w.astype(operand_dtype)
    b_p = _rows(b, pad_v)

    out = pl.pallas_call(
        functools.partial(
            _neumann_tiled_kernel, hops=hops, tol=tol, nk=nk, bk=bk
        ),
        grid=(n_batch, hops, nk),
        in_specs=[
            pl.BlockSpec((None, bk, vp), lambda i, h, k: (i, k, 0)),
            pl.BlockSpec((None, 1, vp), lambda i, h, k: (i, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, 1, vp), lambda i, h, k: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((n_batch, 1, vp), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((1, vp), jnp.float32),
            pltpu.VMEM((1, vp), jnp.float32),
            pltpu.SMEM((1,), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary")
        ),
        interpret=interpret,
    )(w, b_p)
    return out[:, 0, :v]
