"""Kernel micro-benchmarks: jnp oracle paths timed on CPU; Pallas kernels
validated in interpret mode (wall-clock on CPU interpret is meaningless —
the TPU perf argument lives in the roofline analysis).

`benchmarks.run --use-pallas [--interpret]` routes the apsp section (and
the fleet benches) through the Pallas kernels instead — see _knobs.py."""
from __future__ import annotations

import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks._knobs import pallas_knobs
from repro.kernels.flash_attention.ref import attention_chunked, attention_ref
from repro.kernels.flash_attention.kernel import flash_attention_pallas
from repro.kernels.minplus.kernel import minplus_matmul_pallas
from repro.kernels.minplus.ops import apsp, apsp_with_nexthop
from repro.kernels.minplus.ref import apsp_ref
from repro.kernels.neumann import lu_solve_ref, neumann_solve
from repro.kernels.neumann.kernel import neumann_solve_pallas


def _time(fn, *args, reps=5):
    fn(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    out.block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6  # us


def _bench_apsp(out, print_fn, knobs) -> None:
    """APSP — the placement step's inner loop and PR 8's scaling cliff.

    Default path vs the dense one-broadcast squaring (`apsp_ref`): the dense
    path materializes a [V, V, V] candidate tensor per squaring, 512 MiB at
    V=512 and 4 GiB at V=1024 — which is why V=1024 only runs the O(V^2)
    paths, and why this section exists. `apsp_*_us` keys are trend-linted
    (lower is better); the `_speedup` ratios are the portable claim.
    """
    small = bool(os.environ.get("SCALE_SMALL"))
    rng = np.random.RandomState(0)
    sizes = (32, 128, 256) if small else (32, 128, 512, 1024)
    dense_cap = 256 if small else 512
    for v in sizes:
        w = rng.uniform(0.1, 5.0, (v, v)).astype(np.float32)
        w[rng.rand(v, v) < 0.7] = 1e18
        wj = jnp.asarray(w)
        reps = 2 if v >= 512 else 5
        us = _time(jax.jit(lambda x: apsp(x, **knobs)), wj, reps=reps)
        out[f"apsp_v{v}_us"] = us
        line = f"kernel,apsp v={v:4d}  {us:10.1f} us/call"
        if v >= 128:
            us_nh = _time(
                jax.jit(lambda x: apsp_with_nexthop(x, **knobs)[1]),
                wj,
                reps=reps,
            )
            out[f"apsp_nexthop_v{v}_us"] = us_nh
            line += f"  nexthop {us_nh:10.1f} us"
        if 128 <= v <= dense_cap:
            d0 = jnp.where(jnp.eye(v, dtype=bool), 0.0, wj)
            us_dense = _time(jax.jit(apsp_ref), d0, reps=2)
            out[f"apsp_dense_v{v}_us"] = us_dense
            out[f"apsp_v{v}_speedup"] = us_dense / us
            line += f"  dense {us_dense:10.1f} us ({us_dense / us:.1f}x)"
        elif v > dense_cap:
            line += "  dense skipped (O(V^3) broadcast)"
        print_fn(line)


def _bench_fleet_round(out, print_fn, knobs) -> None:
    """End-to-end ALT round wall-clock across V — the ROADMAP success
    metric behind PR 8: a V=1024 round on the O(V^2) APSP paths vs the
    small-V rounds the dense path used to cap the stack at."""
    from repro.core import random_connected, solve_alt

    small = bool(os.environ.get("SCALE_SMALL"))
    sizes = ((64, 3), (256, 4)) if small else ((256, 4), (1024, 4))
    ms = {}
    for v, a in sizes:
        p = random_connected(v, a, seed=1)
        kw = dict(m_max=1, t_phi=2, **knobs)
        float(solve_alt(p, **kw).J)  # compile + warm
        reps = 2
        t0 = time.perf_counter()
        for _ in range(reps):
            float(solve_alt(p, **kw).J)
        ms[v] = (time.perf_counter() - t0) / reps * 1e3
        out[f"fleet_round_v{v}_ms"] = ms[v]
        print_fn(f"kernel,fleet_round v={v:4d}  {ms[v]:8.1f} ms/round")
    lo, hi = min(ms), max(ms)
    out["fleet_round_small_over_big_ratio"] = ms[lo] / ms[hi]


def run(print_fn=print) -> dict:
    out = {}
    rng = np.random.RandomState(0)
    knobs = pallas_knobs()

    _bench_apsp(out, print_fn, knobs)
    _bench_fleet_round(out, print_fn, knobs)

    # neumann propagation solve vs dense LU — the ALT hot-loop fixed point.
    # Workload shape: [A, V, V] nilpotent operators (SP-tree-like support,
    # longest chain ~ diameter), one RHS per app.
    for v in (64, 128):
        a_apps, hops = 12, 10
        m = np.triu(rng.uniform(0.0, 1.0, (a_apps, v, v)).astype(np.float32), 1)
        m *= rng.rand(a_apps, v, v) < (2.0 / v)  # sparse loop-free support
        rhs = rng.uniform(0.0, 2.0, (a_apps, v)).astype(np.float32)
        m_j, rhs_j = jnp.asarray(m), jnp.asarray(rhs)
        ne = jax.jit(lambda mm, bb: neumann_solve(mm, bb, hops=hops))
        lu = jax.jit(lu_solve_ref)
        us_ne = _time(ne, m_j, rhs_j)
        us_lu = _time(lu, m_j, rhs_j)
        err = float(jnp.max(jnp.abs(ne(m_j, rhs_j) - lu(m_j, rhs_j))))
        out[f"neumann_v{v}_us"] = us_ne
        out[f"lu_v{v}_us"] = us_lu
        out[f"neumann_v{v}_speedup"] = us_lu / us_ne
        print_fn(
            f"kernel,neumann v={v:4d} A={a_apps} hops<={hops}  "
            f"neumann={us_ne:8.1f}us lu={us_lu:8.1f}us "
            f"speedup={us_lu / us_ne:.2f}x err={err:.2e}"
        )
        assert err < 1e-3

    # neumann Pallas (interpret) vs LU oracle: correctness of the fused hops.
    m = np.triu(rng.uniform(0.0, 1.0, (4, 48, 48)).astype(np.float32), 1)
    m *= rng.rand(4, 48, 48) < 0.2
    rhs = rng.uniform(0.0, 2.0, (4, 48)).astype(np.float32)
    got = neumann_solve_pallas(jnp.asarray(m), jnp.asarray(rhs), hops=49, interpret=True)
    want = lu_solve_ref(jnp.asarray(m), jnp.asarray(rhs))
    err = float(jnp.max(jnp.abs(got - want)) / (jnp.max(jnp.abs(want)) + 1e-30))
    out["neumann_interpret_err"] = err
    print_fn(f"kernel,neumann_pallas interpret rel err={err:.2e}")
    assert err < 1e-5

    # minplus Pallas (interpret) vs oracle: correctness + relative cost.
    a = jnp.asarray(rng.uniform(0, 5, (256, 256)).astype(np.float32))
    got = minplus_matmul_pallas(a, a, interpret=True)
    from repro.kernels.minplus.ref import minplus_matmul_ref

    err = float(jnp.max(jnp.abs(got - minplus_matmul_ref(a, a))))
    out["minplus_interpret_err"] = err
    print_fn(f"kernel,minplus_pallas interpret err={err:.2e}")

    # attention: chunked-flash jnp vs naive ref (the memory-bound fix).
    q = jnp.asarray(rng.randn(1, 8, 1024, 64), jnp.float32)
    k = jnp.asarray(rng.randn(1, 2, 1024, 64), jnp.float32)
    v_ = jnp.asarray(rng.randn(1, 2, 1024, 64), jnp.float32)
    us_ref = _time(jax.jit(lambda *x: attention_ref(*x)), q, k, v_)
    us_chk = _time(jax.jit(lambda *x: attention_chunked(*x)), q, k, v_)
    out["attn_ref_us"] = us_ref
    out["attn_chunked_us"] = us_chk
    print_fn(f"kernel,attention S=1024 ref={us_ref:.0f}us chunked={us_chk:.0f}us")

    got = flash_attention_pallas(q, k, v_, interpret=True)
    err = float(
        jnp.max(jnp.abs(got - attention_ref(q, k, v_)))
    )
    out["flash_interpret_err"] = err
    print_fn(f"kernel,flash_pallas interpret err={err:.2e}")
    assert err < 5e-3
    return out


if __name__ == "__main__":
    run()
