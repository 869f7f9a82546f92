import os
import sys

# The 512-device CPU mesh must be requested before jax initialises its
# backend. Append to the caller's XLA_FLAGS, and leave them alone when jax
# is already imported: a late flag change would silently not apply.
if "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=512"
        ).strip()

"""Multi-pod dry-run: prove the distribution config is coherent without
hardware.

For every (architecture x input-shape) cell and each production mesh
(single-pod 16x16, multi-pod 2x16x16), lower + compile the appropriate step
function with ShapeDtypeStruct stand-ins, then record:

  * memory_analysis()  — proves the cell fits per-device HBM
  * cost_analysis()    — HLO FLOPs / bytes for the roofline terms
  * collective bytes   — parsed from the post-SPMD optimized HLO
    (all-gather / all-reduce / reduce-scatter / all-to-all /
     collective-permute operand sizes)

Results are written as JSON under results/dryrun/ and consumed by
benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch gemma-2b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--debug-mesh]
  python -m repro.launch.dryrun --all --both-meshes
"""
import argparse
import json
import re
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS, get_config
from repro.distributed.act import activation_sharding
from repro.distributed.sharding import (
    ShardingRules,
    batch_pspec,
    batch_pspec_for,
    cache_pspecs,
    param_pspecs,
    to_named_shardings,
)
from repro.launch.mesh import make_debug_mesh, make_production_mesh
from repro.launch import hlo_cost
from repro.launch import steps as St
from repro.models.config import SHAPES, shape_applicable
from jax.sharding import NamedSharding, PartitionSpec as P

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVE_RE = re.compile(
    r"=\s+(\w+)\[([\d,]*)\][^=]*?"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)\("
)


def parse_collective_bytes(hlo_text: str) -> dict:
    """Sum result-shape bytes of every collective op in the optimized HLO.

    Returns {op_kind: bytes} per device per step. (For all-reduce the wire
    cost is ~2x the operand under a ring schedule; the roofline applies
    per-kind factors — see benchmarks/roofline.py.)"""
    out: dict[str, float] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        nbytes = _DTYPE_BYTES.get(dtype, 4)
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        out[kind] = out.get(kind, 0.0) + float(n * nbytes)
    return out


def _loop_trip_counts(hlo_text: str) -> list[int]:
    """Trip counts of while loops (scan-over-layers shows up here)."""
    return [int(x) for x in re.findall(r'known_trip_count[^0-9]*?(\d+)', hlo_text)][:20]


def build_cell(arch: str, shape_name: str, mesh, rules: ShardingRules,
               microbatches: int = 1, vocab_chunks: int = 0,
               cache_layout: str | None = None, moe_groups: int = -1,
               seq_shard: bool = False, remat: bool | None = None,
               no_fsdp: bool = False, quant_int8: bool = False):
    """Returns (step_fn, in_args_specs, in_shardings, donate) for a cell."""
    import dataclasses
    from repro.distributed.sharding import batch_axes_size

    cfg = get_config(arch)
    if vocab_chunks:
        cfg = dataclasses.replace(cfg, vocab_chunking=vocab_chunks)
    if moe_groups < 0:  # auto: one dispatch group per data shard
        moe_groups = batch_axes_size(mesh, rules)
    cfg = dataclasses.replace(cfg, moe_groups=moe_groups)
    if remat is not None:
        cfg = dataclasses.replace(cfg, remat=remat)
    if seq_shard:
        rules = dataclasses.replace(rules, seq_shard_residual=True)
    if no_fsdp:
        rules = dataclasses.replace(rules, fsdp=False)
    shape = SHAPES[shape_name]

    def _wrap(fn):
        def wrapped(*a):
            with __import__("repro.distributed.act", fromlist=["activation_sharding"]).activation_sharding(mesh, rules):
                return fn(*a)
        return wrapped

    if shape.kind == "train":
        step = _wrap(St.make_train_step(cfg, microbatches=microbatches))
        p_specs = St.param_specs(cfg)
        o_specs = St.opt_specs(cfg)
        b_specs = St.batch_specs(cfg, shape)
        p_sh = to_named_shardings(mesh, param_pspecs(cfg, p_specs, mesh, rules))
        o_sh = {
            "mu": to_named_shardings(mesh, param_pspecs(cfg, p_specs, mesh, rules)),
            "nu": to_named_shardings(mesh, param_pspecs(cfg, p_specs, mesh, rules)),
            "step": NamedSharding(mesh, P()),
        }
        b_sh = jax.tree.map(
            lambda _: NamedSharding(mesh, batch_pspec_for(mesh, rules, shape.global_batch)),
            b_specs,
        )
        return step, (p_specs, o_specs, b_specs), (p_sh, o_sh, b_sh), (0, 1)

    scfg = St.serve_config(cfg)
    if quant_int8:
        scfg = dataclasses.replace(scfg, quantize_int8=True)
    if cache_layout:
        rules = dataclasses.replace(rules, cache_layout=cache_layout)
    if shape.kind == "prefill":
        step = _wrap(St.make_prefill_step(scfg, shape.seq_len))
        p_specs = St.param_specs(scfg)
        b_specs = St.batch_specs(scfg, shape)
        p_sh = to_named_shardings(mesh, param_pspecs(scfg, p_specs, mesh, rules))
        b_sh = jax.tree.map(
            lambda _: NamedSharding(mesh, batch_pspec_for(mesh, rules, shape.global_batch)),
            b_specs,
        )
        return step, (p_specs, b_specs), (p_sh, b_sh), ()

    # decode
    step = _wrap(St.make_serve_step(scfg))
    p_specs = St.param_specs(scfg)
    c_specs = St.cache_specs(scfg, shape)
    t_spec = jax.ShapeDtypeStruct((shape.global_batch, 1), jnp.int32)
    pos_spec = jax.ShapeDtypeStruct((), jnp.int32)
    p_sh = to_named_shardings(mesh, param_pspecs(scfg, p_specs, mesh, rules))
    c_sh = to_named_shardings(mesh, cache_pspecs(scfg, c_specs, mesh, rules))
    t_sh = NamedSharding(mesh, batch_pspec_for(mesh, rules, shape.global_batch))
    pos_sh = NamedSharding(mesh, P())
    return step, (p_specs, c_specs, t_spec, pos_spec), (p_sh, c_sh, t_sh, pos_sh), (1,)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             debug_mesh: bool = False, microbatches: int = 1,
             vocab_chunks: int = 0, cache_layout: str | None = None,
             moe_groups: int = -1, seq_shard: bool = False,
             remat: bool | None = None, no_fsdp: bool = False,
             quant_int8: bool = False,
             save: bool = True, tag: str = "") -> dict:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, reason = shape_applicable(cfg, shape)
    mesh_name = "2x16x16" if multi_pod else "16x16"
    record = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "multi_pod": multi_pod, "tag": tag,
        "microbatches": microbatches, "vocab_chunks": vocab_chunks,
        "cache_layout": cache_layout,
    }
    if not ok:
        record.update(status="skipped", reason=reason)
        if save:
            _save(record)
        return record

    mesh = (
        make_debug_mesh(multi_pod=multi_pod)
        if debug_mesh
        else make_production_mesh(multi_pod=multi_pod)
    )
    rules = ShardingRules(pod_axis="pod" if multi_pod else None)
    t0 = time.time()
    try:
        step, arg_specs, in_sh, donate = build_cell(
            arch, shape_name, mesh, rules, microbatches=microbatches,
            vocab_chunks=vocab_chunks, cache_layout=cache_layout,
            moe_groups=moe_groups, seq_shard=seq_shard, remat=remat,
            no_fsdp=no_fsdp, quant_int8=quant_int8,
        )
        with mesh:
            jitted = jax.jit(step, in_shardings=in_sh, donate_argnums=donate)
            lowered = jitted.lower(*arg_specs)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

            cost = dict(compiled.cost_analysis() or {})
            cost = {k: float(v) for k, v in cost.items() if np.isscalar(v)}
            try:
                ma = compiled.memory_analysis()
                mem = {
                    "argument_bytes": float(getattr(ma, "argument_size_in_bytes", 0)),
                    "output_bytes": float(getattr(ma, "output_size_in_bytes", 0)),
                    "temp_bytes": float(getattr(ma, "temp_size_in_bytes", 0)),
                    "alias_bytes": float(getattr(ma, "alias_size_in_bytes", 0)),
                    "generated_code_bytes": float(
                        getattr(ma, "generated_code_size_in_bytes", 0)
                    ),
                }
            except Exception as e:  # pragma: no cover
                mem = {"error": str(e)}
            hlo = compiled.as_text()
            analysis = hlo_cost.analyze(hlo)
            coll = analysis["collectives"]
            trips = _loop_trip_counts(hlo)
        # Per-device argument bytes (params+opt+caches) from specs+shardings.
        arg_bytes = _sharded_arg_bytes(arg_specs, in_sh, mesh)
        record.update(
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            cost_analysis=cost,
            hlo_flops=analysis["flops"],
            hlo_bytes_accessed=analysis["bytes"],
            hlo_warnings=analysis["warnings"],
            memory=mem,
            collective_bytes=coll,
            loop_trip_counts=trips,
            per_device_argument_gib=round(arg_bytes / 2**30, 3),
            n_devices=int(np.prod(list(mesh.shape.values()))),
            hlo_bytes=len(hlo),
        )
    except Exception as e:
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      trace=traceback.format_exc()[-2000:])
    if save:
        _save(record)
    return record


def _sharded_arg_bytes(arg_specs, in_sh, mesh) -> float:
    """Per-device bytes of all inputs under their shardings."""
    total = 0.0
    flat_specs = jax.tree_util.tree_leaves(arg_specs)
    flat_sh = jax.tree_util.tree_leaves(
        in_sh, is_leaf=lambda x: isinstance(x, NamedSharding)
    )
    for spec, sh in zip(flat_specs, flat_sh):
        if not hasattr(spec, "shape"):
            continue
        n = int(np.prod(spec.shape)) if spec.shape else 1
        nbytes = n * spec.dtype.itemsize
        shards = 1
        if isinstance(sh, NamedSharding):
            for axis in jax.tree_util.tree_leaves(tuple(sh.spec)):
                if axis is not None:
                    shards *= mesh.shape[axis]
        total += nbytes / shards
    return total


def _save(record: dict):
    RESULTS_DIR.mkdir(parents=True, exist_ok=True)
    tag = f"-{record['tag']}" if record.get("tag") else ""
    name = f"{record['arch']}__{record['shape']}__{record['mesh']}{tag}.json"
    (RESULTS_DIR / name).write_text(json.dumps(record, indent=1))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=list(ARCHS))
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--debug-mesh", action="store_true")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--vocab-chunks", type=int, default=0)
    ap.add_argument("--cache-layout", choices=["seq", "heads"], default=None)
    ap.add_argument("--moe-groups", type=int, default=-1)
    ap.add_argument("--seq-shard", action="store_true")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--no-fsdp", action="store_true")
    ap.add_argument("--quant-int8", action="store_true")
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCHS:
            for shape in SHAPES:
                cells.append((arch, shape))
    elif args.arch and not args.shape:
        cells = [(args.arch, shape) for shape in SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all required"
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    n_ok = n_skip = n_err = 0
    for arch, shape in cells:
        for mp in meshes:
            r = run_cell(
                arch, shape, multi_pod=mp, debug_mesh=args.debug_mesh,
                microbatches=args.microbatches, vocab_chunks=args.vocab_chunks,
                cache_layout=args.cache_layout, moe_groups=args.moe_groups,
                seq_shard=args.seq_shard, no_fsdp=args.no_fsdp,
                quant_int8=args.quant_int8,
                remat=(False if args.no_remat else None), tag=args.tag,
            )
            status = r["status"]
            n_ok += status == "ok"
            n_skip += status == "skipped"
            n_err += status == "error"
            if status == "ok":
                fl = r.get("hlo_flops", 0)
                print(
                    f"OK    {arch:22s} {shape:12s} {r['mesh']:8s} "
                    f"compile={r['compile_s']:7.1f}s flops={fl:.3e} "
                    f"args/dev={r['per_device_argument_gib']:.2f}GiB "
                    f"coll={ {k: f'{v:.2e}' for k, v in r['collective_bytes'].items()} }",
                    flush=True,
                )
            elif status == "skipped":
                print(f"SKIP  {arch:22s} {shape:12s} {r['mesh']:8s} {r['reason'][:60]}", flush=True)
            else:
                print(f"ERROR {arch:22s} {shape:12s} {r['mesh']:8s} {r['error'][:200]}", flush=True)
    print(f"\ndry-run complete: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    return 0 if n_err == 0 else 1


if __name__ == "__main__":
    raise SystemExit(main())
