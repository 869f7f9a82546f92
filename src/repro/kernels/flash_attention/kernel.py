"""Pallas TPU kernel: blockwise (flash) attention, GQA / causal / SWA.

The model zoo's dominant compute op. Streaming softmax over KV blocks with
running (max, denominator, accumulator) state held in VMEM scratch — the
FlashAttention recurrence laid out for the TPU memory hierarchy:

  grid = (B * H, Sq / bq, Sk / bk), KV innermost ("arbitrary"), so the
  (bq, d) accumulator tile is revisited across KV steps while q/k/v tiles
  stream HBM -> VMEM. The two matmuls per step ([bq,d]x[d,bk] and
  [bq,bk]x[bk,d]) hit the MXU with 128-aligned dims.

GQA is handled in the BlockSpec index maps: the kv-head index is derived
arithmetically from the q-head grid coordinate (kvh = h // group), so no
KV replication is materialized.

VMEM per step (fp32, bq=bk=128, d<=256):
  q/k/v tiles 3 * 128 KiB + acc 128 KiB + scores 64 KiB  << 16 MiB.

Numerics: masked scores use NEG = -1e30 with the running max initialized to
M_INIT = -1e29 > NEG, so fully-masked blocks contribute exp(NEG - M_INIT) ~ 0
rather than exp(0) = 1, and rows that never see a valid key produce zeros.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30
M_INIT = -1e29


def _flash_kernel(
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    scale: float,
    causal: bool,
    window: int | None,
    bq: int,
    bk: int,
    nk: int,
    q_offset: int,
    kv_len: int,
):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, M_INIT)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # Block-level mask bounds: skip fully-masked KV blocks entirely (the
    # causal upper triangle / outside the sliding-window band / padding).
    # Halves causal-attention work and makes SWA cost O(window), at runtime,
    # with no change to the streamed-softmax state.
    q_lo = q_offset + iq * bq
    q_hi = q_lo + bq - 1
    k_lo = ik * bk
    k_hi = k_lo + bk - 1
    live = k_lo < kv_len
    if causal:
        live &= k_lo <= q_hi
    if window is not None:
        live &= k_hi > q_lo - window

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32)  # [bq, d]
        k = k_ref[0].astype(jnp.float32)  # [bk, d]
        v = v_ref[0].astype(jnp.float32)  # [bk, d]

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale  # [bq, bk]

        q_pos = q_offset + iq * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        k_pos = ik * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = k_pos < kv_len  # KV padding
        if causal:
            mask &= k_pos <= q_pos
        if window is not None:
            mask &= k_pos > q_pos - window
        s_m = jnp.where(mask, s, NEG)

        m_prev = m_ref[...]
        l_prev = l_ref[...]
        m_cur = jnp.maximum(m_prev, jnp.max(s_m, axis=-1))
        corr = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s_m - m_cur[:, None])
        l_ref[...] = l_prev * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_ref[...] = m_cur

    @pl.when(ik == nk - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)[:, None]
        o_ref[0] = (acc_ref[...] / denom).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "q_offset", "bq", "bk", "interpret"),
)
def flash_attention_pallas(
    q: jax.Array,  # [B, H, Sq, D]
    k: jax.Array,  # [B, Kv, Sk, D]
    v: jax.Array,  # [B, Kv, Sk, D]
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    bq: int = 128,
    bk: int = 128,
    interpret: bool = False,
) -> jax.Array:
    b, h, sq, d = q.shape
    _, kv, sk, _ = k.shape
    assert h % kv == 0, (h, kv)
    group = h // kv
    scale = 1.0 / float(d) ** 0.5

    bq_ = min(bq, max(8, sq))
    bk_ = min(bk, max(128, 1))
    pad_q = (-sq) % bq_
    pad_k = (-sk) % bk_
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad_q), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k), (0, 0)))
    sqp, skp = sq + pad_q, sk + pad_k

    qp = qp.reshape(b * h, sqp, d)
    kp = kp.reshape(b * kv, skp, d)
    vp = vp.reshape(b * kv, skp, d)

    nq = sqp // bq_
    nk = skp // bk_
    grid = (b * h, nq, nk)

    def q_index(bh, iq, ik):
        return (bh, iq, 0)

    def kv_index(bh, iq, ik):
        batch = bh // h
        head = bh % h
        return (batch * kv + head // group, ik, 0)

    out = pl.pallas_call(
        functools.partial(
            _flash_kernel,
            scale=scale,
            causal=causal,
            window=window,
            bq=bq_,
            bk=bk_,
            nk=nk,
            q_offset=q_offset,
            kv_len=sk,
        ),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bq_, d), q_index),
            pl.BlockSpec((1, bk_, d), kv_index),
            pl.BlockSpec((1, bk_, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq_, d), q_index),
        out_shape=jax.ShapeDtypeStruct((b * h, sqp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_,), jnp.float32),
            pltpu.VMEM((bq_, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")
        ),
        interpret=interpret,
    )(qp, kp, vp)
    return out.reshape(b, h, sqp, d)[:, :, :sq, :]
