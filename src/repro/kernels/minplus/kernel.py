"""Pallas TPU kernel: tropical (min,+) matmul, the APSP inner loop.

TPU adaptation of the paper's Dijkstra-based placement step (DESIGN.md
section 3): shortest paths under the marginal link weights D'_ij(F_ij) are
computed by tropical matrix squaring. The (min,+) semiring has no MXU support
(the systolic array is multiply-accumulate only), so the kernel targets the
VPU: each grid step loads MXU-aligned 128x128 tiles of A^T and B into VMEM
and walks the K tile in KINNER-row chunks, each chunk a run of rank-1
updates acc <- min(acc, a[:, k] + b[k, :]) on [bm, bn] vregs. A is passed
transposed so that every dynamic slice runs along the sublane axis at a
multiple of KINNER (Mosaic cannot slice the lane axis at such offsets).

Grid: (M/bm, N/bn, K/bk) with K innermost ("arbitrary") so the output tile is
revisited and used as the running-min accumulator — the standard Pallas
matmul accumulation pattern, with (+, *) replaced by (min, +).

VMEM budget per grid step (fp32, bm=bn=bk=128, KINNER=8):
    A tile 64 KiB + B tile 64 KiB + out tile 64 KiB + [bm, bn] accumulator
    64 KiB, well under the ~16 MiB VMEM of a TPU core.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BIG = 1e18
DEFAULT_BLOCK = 128
KINNER = 8


def _chunk(at_ref, b_ref, c):
    """KINNER k-slices of one K tile: A columns [bm, KINNER], B rows [KINNER, bn].

    Both slices run along the sublane axis (A arrives transposed), at an
    offset that is a multiple of KINNER, which is what Mosaic can slice
    dynamically; the [KINNER, bm] A slab is then transposed in VMEM.
    """
    k0 = pl.multiple_of(c * KINNER, KINNER)
    a_cols = at_ref[pl.ds(k0, KINNER), :].T
    b_rows = b_ref[pl.ds(k0, KINNER), :]
    return k0, a_cols, b_rows


def _minplus_kernel(at_ref, b_ref, o_ref, *, bk: int):
    """One (i, j, k) grid step: o[i,j] = min(o[i,j], min_k a[i,k]+b[k,j])."""

    # +inf (not BIG) is the accumulator identity: BIG-padding chunks then
    # contribute BIG+BIG candidates, exactly what the pure-jnp oracle
    # computes for all-non-edge rows, so kernel == oracle bitwise.
    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, jnp.inf)

    def body(c, acc):
        _, a_cols, b_rows = _chunk(at_ref, b_ref, c)
        for j in range(KINNER):  # rank-1 (min,+) updates, [bm, 1] + [1, bn]
            acc = jnp.minimum(acc, a_cols[:, j : j + 1] + b_rows[j : j + 1, :])
        return acc

    acc = jnp.full(o_ref.shape, jnp.inf, jnp.float32)
    acc = jax.lax.fori_loop(0, bk // KINNER, body, acc)
    o_ref[...] = jnp.minimum(o_ref[...], acc)


def _minplus_argmin_kernel(at_ref, b_ref, o_ref, ix_ref, *, bk: int):
    """Fused (min, argmin_k) grid step for the next-hop table.

    Tie-break contract: FIRST minimizing k, matching `jnp.argmin` on the
    full candidate tensor. k advances monotonically (rank-1 updates in
    order within a chunk, chunks in order within a tile, K the innermost
    "arbitrary" grid dim), so the strict `<` update keeps the earliest.
    """
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _init():
        o_ref[...] = jnp.full_like(o_ref, jnp.inf)
        ix_ref[...] = jnp.zeros_like(ix_ref)

    def body(c, carry):
        acc, idx = carry
        k0, a_cols, b_rows = _chunk(at_ref, b_ref, c)
        for j in range(KINNER):
            cand = a_cols[:, j : j + 1] + b_rows[j : j + 1, :]  # [bm, bn]
            upd = cand < acc
            acc = jnp.where(upd, cand, acc)
            idx = jnp.where(upd, k0 + j, idx)
        return acc, idx

    acc = jnp.full(o_ref.shape, jnp.inf, jnp.float32)
    idx = jnp.zeros(ix_ref.shape, jnp.int32)
    acc, idx = jax.lax.fori_loop(0, bk // KINNER, body, (acc, idx))
    upd = acc < o_ref[...]
    o_ref[...] = jnp.where(upd, acc, o_ref[...])
    ix_ref[...] = jnp.where(upd, idx + kk * bk, ix_ref[...])


def _padded(a, b, block):
    """BIG-pad to block multiples; A is returned transposed ([K, M])."""
    (m, k), (k2, n) = a.shape, b.shape
    assert k == k2, (a.shape, b.shape)
    pad_m, pad_k, pad_n = (-m) % block, (-k) % block, (-n) % block
    a_p = jnp.pad(a.astype(jnp.float32), ((0, pad_m), (0, pad_k)),
                  constant_values=BIG)
    b_p = jnp.pad(b.astype(jnp.float32), ((0, pad_k), (0, pad_n)),
                  constant_values=BIG)
    grid = ((m + pad_m) // block, (n + pad_n) // block, (k + pad_k) // block)
    out_shape = (m + pad_m, n + pad_n)
    return a_p.T, b_p, grid, out_shape


def _specs(block):
    return [
        pl.BlockSpec((block, block), lambda i, j, kk: (kk, i)),
        pl.BlockSpec((block, block), lambda i, j, kk: (kk, j)),
    ]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def minplus_matmul_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
) -> jax.Array:
    """Tropical matmul C[i,j] = min_k A[i,k] + B[k,j] via pallas_call.

    Inputs are padded with BIG (the (min,+) identity) to block multiples, so
    padding never affects the valid region.
    """
    m, n = a.shape[0], b.shape[1]
    at_p, b_p, grid, out_shape = _padded(a, b, block)
    out = pl.pallas_call(
        functools.partial(_minplus_kernel, bk=block),
        grid=grid,
        in_specs=_specs(block),
        out_specs=pl.BlockSpec((block, block), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(at_p, b_p)
    return out[:m, :n]


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def minplus_matmul_argmin_pallas(
    a: jax.Array,
    b: jax.Array,
    *,
    block: int = DEFAULT_BLOCK,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Fused tropical matmul + argmin: (min_k A[i,k]+B[k,j], argmin_k ...).

    This is the next-hop table of `apsp_with_nexthop` computed tile-resident:
    the [M, K, N] candidate tensor never exists, only one [bm, bn]
    candidate tile at a time in VMEM. Padding uses BIG so padded k indices
    lose every strict-< update against real candidates (and on all-non-edge
    ties the first — real — index wins, matching `jnp.argmin`).
    """
    m, n = a.shape[0], b.shape[1]
    at_p, b_p, grid, out_shape = _padded(a, b, block)
    val, idx = pl.pallas_call(
        functools.partial(_minplus_argmin_kernel, bk=block),
        grid=grid,
        in_specs=_specs(block),
        out_specs=[
            pl.BlockSpec((block, block), lambda i, j, kk: (i, j)),
            pl.BlockSpec((block, block), lambda i, j, kk: (i, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(out_shape, jnp.float32),
            jax.ShapeDtypeStruct(out_shape, jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(at_p, b_p)
    return val[:m, :n], idx[:m, :n]
