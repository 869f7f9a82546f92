#!/usr/bin/env python3
"""Bring-up smoke test: the main path of the system on one TPU.

Runs, in this one process, the control plane (placement and routing) and the
partitioned data plane through the entry points a user calls, at real widths,
with every input made from a seed:

  kernels    Pallas min-plus APSP (+ next hop) at V = 128/512/1024 and the
             Neumann propagation kernel at V = 256/1024 (VMEM-resident) and
             2048 (K-tiled), compiled, against the pure-XLA path on the chip;
  fleet      `solve_fleet` on 12 sampled instances (V = 64, A = 24, all four
             generator families), XLA path against the Pallas path;
  scenarios  the paper's four topologies at loads 0.6 and 1.0: one batched
             fleet, sequential `solve_alt`, and `solve_alt` on the host CPU
             backend as the device-independent reference;
  control    the chaos control loop (`repro.launch.control`, 8 instances,
             10 epochs, Pallas kernels); no solve may raise;
  dataplane  qwen1.5-0.5b at full width, split in two partitions, answering
             4 requests of 128 tokens; partitioned logits against the
             monolithic model.

`--chips 4` runs only the path that spans chips: `solve_fleet(shard=True)`
over the instance axis of a 16-instance fleet, against the same fleet solved
unsharded on device 0.

Each phase prints one JSON line of its own numbers. The last line is
`{"ok": true, "device": {...}}` only if every phase passed. Without a TPU, or
without the program beside this file, it exits nonzero and prints no result.

    python chip_smoke.py            # one chip
    python chip_smoke.py --chips 4  # the sharded fleet on a 2x2 host
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

SEED = 2026
FAMILY_KW = dict(n_range=(64, 64), apps_range=(24, 24))
FLEET_RTOL = 1e-3  # Pallas vs XLA, batched vs sequential (tests/test_fleet.py)
SHARD_RTOL = 1e-5
LOGITS_ATOL = 1e-2  # partitioned vs monolithic (examples/edge_serving.py)
APSP_V = (128, 512, 1024)
NEUMANN_V_BATCH = ((256, 4), (1024, 2), (2048, 1))  # 2048 > MAX_VMEM_V: tiled


def _timed(fn, *args, **kw):
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn(*args, **kw))
    return out, time.perf_counter() - t0


def _max_rel(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want) / np.maximum(np.abs(want), 1e-30)))


def _integer_weights(rng, v):
    """Sparse directed graph, integer link weights, BIG off the edges.

    Integer weights keep both APSP strategies exact, so distances and the
    first-minimum next hops of the two paths agree bitwise."""
    import numpy as np

    w = np.full((v, v), 1e18, np.float32)
    n_edges = 8 * v
    u, t = rng.randint(0, v, n_edges), rng.randint(0, v, n_edges)
    w[u, t] = rng.randint(1, 5, n_edges).astype(np.float32)
    np.fill_diagonal(w, 1e18)
    return w


def _substochastic(rng, n_batch, v, rho=0.9):
    """Strictly upper-triangular operators with row sums rho: nilpotent and
    contractive (tests/test_neumann.py)."""
    import numpy as np

    m = rng.uniform(0.0, 1.0, (n_batch, v, v)).astype(np.float32)
    m *= np.triu(np.ones((v, v), np.float32), 1)
    m *= rho / np.maximum(m.sum(-1, keepdims=True), 1e-9)
    return m


def phase_kernels() -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.minplus.ops import apsp_with_nexthop
    from repro.kernels.neumann.kernel import neumann_solve_pallas
    from repro.kernels.neumann.ops import DEFAULT_TOL, neumann_solve

    rng = np.random.RandomState(SEED)
    out = {}
    for v in APSP_V:
        w = jnp.asarray(_integer_weights(rng, v))
        t0 = time.perf_counter()
        kern = apsp_with_nexthop.lower(
            w, use_pallas=True, interpret=False
        ).compile()
        compile_s = time.perf_counter() - t0
        (d_pl, nh_pl), run_s = _timed(kern, w)
        (d_x, nh_x), _ = _timed(apsp_with_nexthop, w)
        rel = _max_rel(d_pl, d_x)
        same_nh = bool(np.array_equal(np.asarray(nh_pl), np.asarray(nh_x)))
        out[f"apsp_{v}"] = dict(
            compile_s=compile_s, run_s=run_s, max_rel_err=rel,
            nexthop_equal=same_nh,
        )
        if not (rel <= 1e-6 and same_nh):
            raise AssertionError(f"apsp V={v}: rel {rel}, nexthop {same_nh}")
    hops = 40
    xla_solve = jax.jit(neumann_solve, static_argnames=("hops",))
    for v, n_batch in NEUMANN_V_BATCH:
        m = jnp.asarray(_substochastic(rng, n_batch, v))
        b = jnp.asarray(rng.uniform(0.0, 2.0, (n_batch, v)).astype(np.float32))
        t0 = time.perf_counter()
        kern = neumann_solve_pallas.lower(
            m, b, hops=hops, tol=DEFAULT_TOL, interpret=False
        ).compile()
        compile_s = time.perf_counter() - t0
        x_pl, run_s = _timed(kern, m, b)
        x_x, _ = _timed(xla_solve, m, b, hops=hops)
        scale = float(jnp.max(jnp.abs(x_x))) + 1e-30
        err = float(jnp.max(jnp.abs(x_pl - x_x))) / scale
        out[f"neumann_{v}"] = dict(
            compile_s=compile_s, run_s=run_s, max_scaled_err=err,
        )
        if not err < 1e-5:
            raise AssertionError(f"neumann V={v}: scaled error {err}")
    return out


def _solve_twice(fleet, **kw):
    """Cold solve (compile + run), then the same solve warm."""
    from repro.fleet import solve_fleet

    cold, cold_s = _timed(solve_fleet, fleet, method="ALT", **kw)
    warm, warm_s = _timed(solve_fleet, fleet, method="ALT", **kw)
    return warm, cold_s, warm_s


def phase_fleet() -> dict:
    import numpy as np

    from repro.fleet import FAMILIES, sample_fleet

    fleet = sample_fleet(12, seed=SEED, families=list(FAMILIES), **FAMILY_KW)
    out = {}
    res = {}
    for name, kw in (
        ("xla", {}),
        ("pallas", dict(use_pallas=True, interpret=False)),
    ):
        r, cold_s, warm_s = _solve_twice(fleet, **kw)
        if not np.isfinite(r.J).all():
            raise AssertionError(f"fleet {name}: non-finite J {r.J}")
        res[name] = r
        out[name] = dict(
            rounds=int(r.rounds), compile_s=cold_s - warm_s, warm_s=warm_s,
            inst_per_s=len(fleet) / warm_s, J=[float(j) for j in r.J],
        )
    out["max_rel_err"] = _max_rel(res["pallas"].J, res["xla"].J)
    if not out["max_rel_err"] <= FLEET_RTOL:
        raise AssertionError(f"fleet: pallas vs xla rel {out['max_rel_err']}")
    return out


def _scenario_fleet():
    from repro.core import SCENARIOS
    from repro.fleet import load_grid

    names = ("iot", "mesh", "smallworld", "geant")
    return [p for n in names for p in load_grid(SCENARIOS[n], (0.6, 1.0))]


def phase_scenarios() -> dict:
    import jax
    import numpy as np

    from repro.core import solve_alt

    fleet = _scenario_fleet()
    batched, cold_s, warm_s = _solve_twice(fleet)
    t0 = time.perf_counter()
    seq = np.array([solve_alt(p).J for p in fleet])
    seq_s = time.perf_counter() - t0
    with jax.default_device(jax.devices("cpu")[0]):
        t0 = time.perf_counter()
        ref = np.array([solve_alt(p).J for p in _scenario_fleet()])
        cpu_s = time.perf_counter() - t0
    out = dict(
        compile_s=cold_s - warm_s, warm_s=warm_s, sequential_s=seq_s,
        cpu_reference_s=cpu_s, J_batched=[float(j) for j in batched.J],
        J_sequential=seq.tolist(), J_cpu=ref.tolist(),
        rel_batched_vs_seq=_max_rel(batched.J, seq),
        rel_batched_vs_cpu=_max_rel(batched.J, ref),
        rel_seq_vs_cpu=_max_rel(seq, ref),
    )
    worst = max(out["rel_batched_vs_seq"], out["rel_batched_vs_cpu"],
                out["rel_seq_vs_cpu"])
    if not (np.isfinite(batched.J).all() and worst <= FLEET_RTOL):
        raise AssertionError(f"scenarios: worst rel {worst}")
    return out


def phase_control() -> dict:
    from repro.launch import control

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = control.main([
            "--instances", "8", "--epochs", "10", "--use-pallas",
            "--assert-feasible",
        ])
    payload, _ = json.JSONDecoder().raw_decode(buf.getvalue())
    s = payload["summary"]
    out = dict(
        rc=rc, wall_s=s["wall_s"], epochs=s["epochs"],
        solve_errors=s["solve_errors"],
        feasible_fraction=s["feasible_fraction"],
        nonfinite_epochs=s["nonfinite_epochs"],
        fallback_epochs=s["fallback_epochs"],
        p50_recovery_latency_s=s["p50_recovery_latency_s"],
        J_median=[r["J_median"] for r in payload["epochs"]],
    )
    if rc != 0 or s["solve_errors"] != 0 or s["epochs"] != 10:
        raise AssertionError(f"control: rc {rc}, summary {s}")
    return out


def phase_dataplane() -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.models import init_params, logits_fn
    from repro.partition import profile_arch, run_partition, split_params

    cfg = get_config("qwen1.5-0.5b")
    n_req, seq = 4, 128
    prof = profile_arch(cfg, seq_len=seq)
    k = prof.split_layer
    params = init_params(cfg, jax.random.PRNGKey(0))
    p1, p2 = split_params(cfg, params, k)
    batch = {
        "tokens": jax.random.randint(
            jax.random.PRNGKey(1), (n_req, seq), 0, cfg.vocab
        )
    }
    part1 = jax.jit(lambda p, b: run_partition(cfg, p, b, part=1, k=k))
    part2 = jax.jit(lambda p, a: run_partition(cfg, p, a, part=2, k=k))
    mono = jax.jit(lambda p, b: logits_fn(cfg, p, b))

    act, cold1 = _timed(part1, p1, batch)
    logits, cold2 = _timed(part2, p2, act)
    want, cold_m = _timed(mono, params, batch)
    act, warm1 = _timed(part1, p1, batch)
    logits, warm2 = _timed(part2, p2, act)
    _, warm_m = _timed(mono, params, batch)

    diff = jnp.abs(logits.astype(jnp.float32) - want.astype(jnp.float32))
    err = float(jnp.max(diff))
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    out = dict(
        arch=cfg.name, n_layers=cfg.n_layers, d_model=cfg.d_model,
        vocab=cfg.vocab, split_layer=k, requests=n_req, seq_len=seq,
        logits_shape=list(logits.shape),
        stage1_bytes_per_request=act.size * act.dtype.itemsize // n_req,
        profile_L1_bytes=float(prof.L1_bytes),
        compile_s=(cold1 + cold2 + cold_m) - (warm1 + warm2 + warm_m),
        partitioned_s=warm1 + warm2, monolithic_s=warm_m,
        max_abs_err=err, max_rel_err=err / max(scale, 1e-30),
    )
    if not (bool(jnp.isfinite(logits).all()) and err < LOGITS_ATOL):
        raise AssertionError(f"dataplane: max abs err {err}")
    return out


def phase_sharded() -> dict:
    import jax
    import numpy as np

    from repro.fleet import FAMILIES, sample_fleet, solve_fleet

    n_dev = len(jax.devices())
    if n_dev != 4:
        raise AssertionError(f"--chips 4 needs 4 devices, found {n_dev}")
    fleet = sample_fleet(16, seed=SEED, families=list(FAMILIES), **FAMILY_KW)
    with jax.default_device(jax.devices()[0]):
        ref, ref_cold = _timed(solve_fleet, fleet, method="ALT")
    sh, cold_s = _timed(solve_fleet, fleet, method="ALT", shard=True,
                        keep_state=True)
    _, warm_s = _timed(solve_fleet, fleet, method="ALT", shard=True)
    spans = len(sh.state.phi.sharding.device_set)
    out = dict(
        reason=sh.shard.reason, n_devices=sh.shard.n_devices,
        output_sharded=sh.shard.output_sharded, state_devices=spans,
        rounds=int(sh.rounds), warm_s=warm_s, inst_per_s=len(fleet) / warm_s,
        unsharded_cold_s=ref_cold, max_rel_err=_max_rel(sh.J, ref.J),
        J=[float(j) for j in sh.J],
    )
    if not (
        sh.shard.reason == "sharded"
        and sh.shard.output_sharded
        and spans == 4
        and np.isfinite(sh.J).all()
        and out["max_rel_err"] <= SHARD_RTOL
    ):
        raise AssertionError(f"sharded fleet: {out}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4 runs only the instance-sharded fleet on a four-chip host",
    )
    args = ap.parse_args(argv)

    try:
        from repro.launch.compile_cache import configure_compile_cache
    except ImportError as e:
        print(f"chip_smoke: the program is not beside this script ({e})",
              file=sys.stderr)
        return 2
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (device 0 is {dev.platform!r}); "
              "this check never falls back to the CPU", file=sys.stderr)
        return 2
    cache_dir = configure_compile_cache()
    print(json.dumps({"compile_cache": cache_dir}), flush=True)

    if args.chips == 4:
        phases = [("sharded_fleet", phase_sharded)]
    else:
        phases = [
            ("kernels", phase_kernels),
            ("fleet", phase_fleet),
            ("scenarios", phase_scenarios),
            ("control", phase_control),
            ("dataplane", phase_dataplane),
        ]
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            numbers, ok = fn(), True
        except Exception as e:  # report every phase, then fail the run
            traceback.print_exc()
            numbers, ok = {"error": f"{type(e).__name__}: {e}"[:2000]}, False
            failed.append(name)
        line = {"phase": name, "ok": ok,
                "wall_s": time.perf_counter() - t0, **numbers}
        print(json.dumps(line), flush=True)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
