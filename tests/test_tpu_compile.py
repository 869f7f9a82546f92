"""Compile rehearsals: the control plane's Pallas kernels, compiled by the
TPU compiler for a described (not attached) v5e chip at the cells' widths.

Interpret mode accepts kernels that Mosaic refuses (unaligned dynamic
slices, block shapes off the (8, 128) tiling, VMEM overruns); these tests
catch such refusals without a chip. Nothing runs: results are checked by
the interpret-mode tests in test_kernels.py and test_neumann.py.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports every test file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.minplus.ops import apsp_with_nexthop
from repro.kernels.neumann.kernel import MAX_VMEM_V, neumann_solve_pallas
from repro.kernels.neumann.ops import DEFAULT_TOL


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *specs):
    compiled = jax.jit(fn).lower(*specs).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel, not a fallback
    return compiled


@pytest.mark.parametrize("v", [128, 512, 1024])
def test_apsp_with_nexthop_compiles(one_chip, v):
    """Min-plus squaring kernel and fused min+argmin next-hop kernel."""
    w = jax.ShapeDtypeStruct((v, v), jnp.float32, sharding=one_chip)
    _compile(
        lambda w: apsp_with_nexthop(w, use_pallas=True, interpret=False), w
    )


@pytest.mark.parametrize(
    "v, n_batch, operand_dtype",
    [
        (256, 4, None),  # VMEM-resident operator
        (MAX_VMEM_V, 2, None),  # the largest VMEM-resident operator
        (2048, 2, None),  # K-tiled: W streamed as [block_k, Vp] row tiles
        (2048, 2, jnp.bfloat16),  # K-tiled, bf16 operand stream
    ],
)
def test_neumann_compiles(one_chip, v, n_batch, operand_dtype):
    m = jax.ShapeDtypeStruct((n_batch, v, v), jnp.float32, sharding=one_chip)
    b = jax.ShapeDtypeStruct((n_batch, v), jnp.float32, sharding=one_chip)
    _compile(
        lambda m, b: neumann_solve_pallas(
            m, b, hops=40, tol=DEFAULT_TOL, interpret=False,
            operand_dtype=operand_dtype,
        ),
        m, b,
    )
