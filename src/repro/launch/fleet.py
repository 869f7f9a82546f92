"""Fleet control-plane CLI: batched re-optimization of scenario ensembles.

The serving-side counterpart of `launch/serve.py`: where serve.py executes
one node's DNN partition, this entry point is the *control plane* that
(re)places partitions and routes for a whole fleet of edge deployments in
one batched solve (DESIGN.md section 9).

Examples (CPU):
  PYTHONPATH=src python -m repro.launch.fleet --families erdos_renyi,iot_hierarchy \
      --instances 16 --seed 7 --m-max 8
  PYTHONPATH=src python -m repro.launch.fleet --scenario iot --load-grid 0.4,0.8,1.2
  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      PYTHONPATH=src python -m repro.launch.fleet --instances 10 --shard

Observability: `--trace-out spans.jsonl` (or REPRO_TRACE=spans.jsonl)
records the host span trace — a Chrome trace_event twin lands next to it —
and the emitted JSON carries a "metrics" snapshot plus the engine's
round-trace summary under "trace" (DESIGN.md section 14).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

from repro.core import SCENARIOS
from repro.fleet import FAMILIES, load_grid, sample_fleet, solve_fleet
from repro.launch.compile_cache import configure_compile_cache
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument(
        "--families",
        default=None,
        help=f"comma-separated generator families ({','.join(FAMILIES)})",
    )
    ap.add_argument("--instances", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--partitions",
        default=None,
        help="comma-separated DNN split depths (e.g. 1,2,3) cycled across "
        "instances — heterogeneous P is padded to one K envelope with inert "
        "phantom stages and solved as a single batch; with --scenario the "
        "first value sets the whole grid's depth. Default: the paper's P=2",
    )
    ap.add_argument(
        "--scenario",
        choices=list(SCENARIOS),
        default=None,
        help="use one paper scenario instead of sampled families",
    )
    ap.add_argument(
        "--load-grid",
        default=None,
        help="comma-separated load scales applied to --scenario",
    )
    from repro.fleet import METHODS

    ap.add_argument("--method", choices=list(METHODS), default="ALT")
    ap.add_argument("--m-max", type=int, default=30)
    ap.add_argument("--t-phi", type=int, default=10)
    ap.add_argument("--round-to", type=int, default=8)
    ap.add_argument(
        "--shard",
        action="store_true",
        help="run the engine with the instance axis committed over a 1-D "
        "fleet mesh of local devices (non-divisible batches are padded with "
        "inert repeats and trimmed)",
    )
    ap.add_argument(
        "--devices",
        type=int,
        default=None,
        help="cap the fleet mesh to the first N local devices "
        "(requires --shard)",
    )
    ap.add_argument(
        "--envelope-cap-gb",
        type=float,
        default=None,
        help="bound the per-device footprint of the [B, A, K, V, V] engine "
        "buffers by auto-capping the chunk size for this (V, A) tier",
    )
    ap.add_argument(
        "--solver",
        choices=("neumann", "lu"),
        default="neumann",
        help="linear fixed-point path: hop-capped Neumann propagation "
        "(default) or dense LU reference",
    )
    ap.add_argument(
        "--use-pallas",
        action="store_true",
        help="route the min-plus APSP and Neumann propagation through the "
        "Pallas kernels instead of the pure-XLA paths",
    )
    ap.add_argument(
        "--interpret",
        action=argparse.BooleanOptionalAction,
        default=False,
        help="with --use-pallas, run the kernel bodies under the Pallas "
        "interpreter (CPU tests only; off by default)",
    )
    ap.add_argument(
        "--chunk-size",
        type=int,
        default=None,
        help="split fleets larger than this into fixed-B chunks sharing one "
        "compiled (V, A, B) program",
    )
    ap.add_argument(
        "--block-apps",
        type=int,
        default=1,
        help="placement sweep schedule: 1 = the paper's sequential per-app "
        "scan (default), k > 1 = blocked sweep with size-k batched "
        "precompute, 0 = one block over all apps. Results are "
        "bitwise-identical across block sizes",
    )
    ap.add_argument(
        "--lane-chunk",
        type=int,
        default=None,
        help="round-body layout over the instance axis: 0 = fused vmap (the "
        "only layout compatible with --shard), k >= 1 = lax.map over k-lane "
        "chunks (faster warm on a single host). Default: auto (chunked when "
        "unsharded, vmap when a mesh is committed); bitwise-identical "
        "results either way",
    )
    ap.add_argument(
        "--trace-out",
        default=None,
        help="write the host span trace to this JSONL path (a Chrome "
        "trace_event file lands next to it); REPRO_TRACE=path does the same",
    )
    args = ap.parse_args(argv)

    if args.trace_out:
        obs_trace.configure(
            enabled=True,
            jsonl_path=args.trace_out,
            chrome_path=obs_trace.chrome_path_for(args.trace_out),
        )
    else:
        obs_trace.maybe_configure_from_env()

    partitions = (
        [int(x) for x in args.partitions.split(",")] if args.partitions else None
    )
    with obs_trace.span("launch.fleet.build", instances=args.instances):
        if args.scenario:
            scales = (
                [float(s) for s in args.load_grid.split(",")]
                if args.load_grid
                else [1.0] * args.instances
            )
            grid_kw = {"n_parts": partitions[0]} if partitions else {}
            fleet = load_grid(SCENARIOS[args.scenario], scales, **grid_kw)
        else:
            families = args.families.split(",") if args.families else None
            fleet = sample_fleet(
                args.instances, families=families, seed=args.seed,
                partitions=partitions,
            )

    t0 = time.time()
    with obs_trace.span(
        "launch.fleet.solve", method=args.method, instances=len(fleet)
    ):
        res = solve_fleet(
            fleet,
            method=args.method,
            m_max=args.m_max,
            t_phi=args.t_phi,
            round_to=args.round_to,
            shard=args.shard,
            devices=args.devices,
            solver=args.solver,
            use_pallas=args.use_pallas,
            interpret=args.interpret,
            chunk_size=args.chunk_size,
            envelope_cap_gb=args.envelope_cap_gb,
            block_apps=args.block_apps,
            lane_chunk=args.lane_chunk,
        )
    dt = time.time() - t0
    print(
        json.dumps(
            {
                "method": res.method,
                "solver": args.solver,
                "use_pallas": args.use_pallas,
                "interpret": args.interpret,
                "block_apps": args.block_apps,
                "lane_chunk": args.lane_chunk,
                "instances": res.n_instances,
                # split depths in the batch (per-instance P also appears in
                # each per_instance row as "partitions")
                "partition_mix": sorted(
                    {int(p) for p in res.parts[res.app_mask > 0]}
                ),
                "wall_s": round(dt, 2),
                "inst_per_s": round(res.n_instances / dt, 3),
                # while_loop trips actually executed: < m_max means the whole
                # batch converged and the engine exited early
                "rounds": res.rounds,
                "m_max": args.m_max,
                # the instance-axis layout decision: sharded or not, why, and
                # how many inert pad lanes were run and trimmed
                "shard": dataclasses.asdict(res.shard),
                "summary": res.summary(),
                # obs layer 3: the process-local metrics this solve produced
                "metrics": obs_metrics.registry.snapshot(),
                # obs layer 1: host summary of the engine's round trace
                "trace": None if res.trace is None else res.trace.to_dict(),
                "per_instance": res.per_instance(),
            },
            indent=1,
        ),
        flush=True,
    )
    obs_trace.flush()
    return 0


if __name__ == "__main__":
    configure_compile_cache()
    raise SystemExit(main())
