"""Public attention op: dispatches between the Pallas flash kernel and the
jnp oracle.

The models call `flash_attention(...)`; the `use_pallas` flag comes from the
model config (default False on this CPU container — the dry-run lowers the
jnp path; the kernel is validated in interpret mode by tests/test_kernels.py
and is the intended TPU path)."""
from __future__ import annotations

import jax

from .kernel import flash_attention_pallas
from .ref import attention_chunked, attention_ref

# Above this q*kv sequence product, the jnp path streams over chunks
# (the [B, H, Sq, Sk] score tensor would not fit HBM).
_CHUNKED_THRESHOLD = 2048 * 2048


def flash_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    use_pallas: bool = False,
    interpret: bool = False,
) -> jax.Array:
    if use_pallas:
        return flash_attention_pallas(
            q, k, v, causal=causal, window=window, q_offset=q_offset, interpret=interpret
        )
    if q.shape[2] * k.shape[2] > _CHUNKED_THRESHOLD:
        return attention_chunked(
            q, k, v, causal=causal, window=window, q_offset=q_offset
        )
    return attention_ref(q, k, v, causal=causal, window=window, q_offset=q_offset)
