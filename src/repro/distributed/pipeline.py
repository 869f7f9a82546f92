"""GPipe-style pipeline parallelism over a mesh axis (the multi-pod story).

Across pods the DCN is ~10x slower than ICI, so all-reducing gradients every
step (pure cross-pod DP, the dry-run default) pays a large collective. The
alternative at 1000+-node scale is to map PIPELINE STAGES onto the pod axis:
each pod holds a contiguous block of layers and only ships microbatch
activations (B_mb x S x d) to its successor — point-to-point, overlappable.

Implementation: shard_map over the chosen axis; the classic skewed schedule
runs M + P - 1 ticks; at tick t, stage s processes microbatch (t - s), and
activations move one hop per tick via collective-permute:

    tick:       0    1    2    3    4   (M=3, P=3)
    stage 0:   mb0  mb1  mb2   -    -
    stage 1:    -   mb0  mb1  mb2   -
    stage 2:    -    -   mb0  mb1  mb2

The wrapper is model-agnostic: `stage_fn(stage_params, x) -> x` applies one
stage's layer block (e.g. a scan over L/P layers). Tested against the
sequential application of all stages (tests/test_pipeline.py)."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn, stage_params, x_microbatches, *, mesh: Mesh,
                   axis: str = "pod"):
    """Run a P-stage pipeline over `axis`.

    stage_params: pytree with leading dim P (stage-major), sharded over axis.
    x_microbatches: [M, mb, ...] microbatched inputs (replicated).
    Returns [M, mb, ...] outputs of the last stage.
    """
    n_stages = mesh.shape[axis]
    m = x_microbatches.shape[0]
    ticks = m + n_stages - 1

    def per_stage(params_local, xs):
        # params_local: [1, ...] this stage's slice; xs: [M, mb, ...] (full).
        stage = jax.lax.axis_index(axis)
        p_local = jax.tree.map(lambda a: a[0], params_local)
        mb_shape = xs.shape[1:]
        carry_in = jnp.zeros(mb_shape, xs.dtype)
        outputs = jnp.zeros_like(xs)

        def tick(state, t):
            carry, outputs = state
            # Stage 0 ingests microbatch t; others consume the carry.
            mb_idx = jnp.clip(t, 0, m - 1)
            x_in = jnp.where(stage == 0, xs[mb_idx], carry)
            y = stage_fn(p_local, x_in)
            # Valid iff this stage holds microbatch (t - stage) in [0, M).
            active = (t - stage >= 0) & (t - stage < m)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # Last stage records its finished microbatch.
            out_idx = jnp.clip(t - (n_stages - 1), 0, m - 1)
            record = active & (stage == n_stages - 1)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(record, y, outputs[out_idx]),
                out_idx,
                axis=0,
            )
            # Ship activations one stage forward (ring permute; the wrap-
            # around value into stage 0 is ignored).
            carry = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return (carry, outputs), None

        (carry, outputs), _ = jax.lax.scan(
            tick, (carry_in, outputs), jnp.arange(ticks)
        )
        # Only the last stage holds real outputs (others are zeros); the
        # psum broadcasts them so the replicated out_spec is truthful.
        return jax.lax.psum(outputs, axis)

    spec_params = jax.tree.map(lambda _: P(axis), stage_params)
    fn = jax.shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(spec_params, P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, x_microbatches)
