"""Training entry point: sharded train loop + fault tolerance.

Production behaviors implemented and tested:
  * pjit-sharded step over the ambient mesh (rules from distributed/sharding)
  * checkpoint every --ckpt-every steps (atomic, keep-K), --resume restarts
    from the latest checkpoint including the data-stream position
  * SIGTERM/SIGINT (preemption) triggers a final checkpoint before exit
  * elastic restore: checkpoints are mesh-shape-agnostic (see checkpoint/)
  * metrics JSONL for monitoring

Example (CPU smoke):
  PYTHONPATH=src python -m repro.launch.train --arch qwen1.5-0.5b --reduced \
      --steps 20 --global-batch 8 --seq-len 128 --ckpt-dir /tmp/ckpt
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import signal
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.configs import get_config, reduced_config
from repro.data import DataConfig, make_pipeline
from repro.distributed.act import activation_sharding
from repro.distributed.sharding import (
    ShardingRules,
    batch_pspec_for,
    param_pspecs,
    to_named_shardings,
)
from repro.launch.compile_cache import configure_compile_cache
from repro.launch import steps as St
from repro.models import init_params
from repro.optim import adamw_init
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P


def build_mesh(model_parallel: int):
    """(data, model) mesh with automatic axes.

    The model lays out activations by sharding constraints and leaves the
    rest to the partitioner, so its axes are Auto: under the Explicit axes
    that `jax.make_mesh` gives by default, every gather from the sharded
    embedding and every contraction over a sharded dim would need its
    output sharding spelled out.
    """
    n = len(jax.devices())
    mp = max(1, min(model_parallel, n))
    return jax.make_mesh(
        (n // mp, mp), ("data", "model"), axis_types=(AxisType.Auto,) * 2
    )


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--metrics", default=None)
    ap.add_argument("--log-every", type=int, default=1)
    args = ap.parse_args(argv)

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    if cfg.frontend != "none" or cfg.family == "encdec":
        # LM-style driver trains token-only families; frontend archs are
        # exercised by the partitioned-serving example instead.
        cfg = dataclasses.replace(cfg, frontend="none", frontend_dim=0)

    mesh = build_mesh(args.model_parallel)
    rules = ShardingRules()

    params = init_params(cfg, jax.random.PRNGKey(args.seed))
    opt = adamw_init(params)
    p_sh = to_named_shardings(mesh, param_pspecs(cfg, params, mesh, rules))
    o_sh = {"mu": p_sh, "nu": p_sh, "step": NamedSharding(mesh, P())}
    b_spec = batch_pspec_for(mesh, rules, args.global_batch)
    params = jax.device_put(params, p_sh)
    opt = jax.device_put(opt, o_sh)

    data_cfg = DataConfig(
        vocab=cfg.vocab, seq_len=args.seq_len, global_batch=args.global_batch,
        seed=1234 + args.seed,
    )
    pipeline = make_pipeline(data_cfg)

    ckpt = CheckpointManager(args.ckpt_dir) if args.ckpt_dir else None
    start_step = 0
    if ckpt and args.resume and ckpt.latest_step() is not None:
        (params, opt), extra, start_step = ckpt.restore(
            None, (params, opt), shardings=(p_sh, o_sh)
        )
        pipeline = make_pipeline(data_cfg, extra["data"])
        print(f"resumed from step {start_step}", flush=True)

    base_step = St.make_train_step(cfg, lr=args.lr, microbatches=args.microbatches)

    def step_fn(p, o, batch):
        with activation_sharding(mesh, rules):
            return base_step(p, o, batch)

    jit_step = jax.jit(
        step_fn,
        in_shardings=(p_sh, o_sh, {"tokens": NamedSharding(mesh, b_spec)}),
        donate_argnums=(0, 1),
    )

    metrics_path = Path(args.metrics) if args.metrics else None
    stop = {"now": False}

    def _preempt(signum, frame):
        stop["now"] = True

    signal.signal(signal.SIGTERM, _preempt)
    signal.signal(signal.SIGINT, _preempt)

    def save(step):
        if ckpt:
            ckpt.save(step, (params, opt), extra={"data": pipeline.state()})

    losses = []
    t_start = time.time()
    step = start_step
    with mesh:
        for step in range(start_step, args.steps):
            batch = {"tokens": jnp.asarray(pipeline.next_batch())}
            params, opt, m = jit_step(params, opt, batch)
            loss = float(m["loss"])
            losses.append(loss)
            if step % args.log_every == 0:
                line = {
                    "step": step, "loss": round(loss, 4),
                    "grad_norm": round(float(m["grad_norm"]), 4),
                    "elapsed_s": round(time.time() - t_start, 1),
                }
                print(json.dumps(line), flush=True)
                if metrics_path:
                    with open(metrics_path, "a") as f:
                        f.write(json.dumps(line) + "\n")
            if ckpt and (step + 1) % args.ckpt_every == 0:
                save(step + 1)
            if stop["now"]:
                print("preemption signal: checkpointing and exiting", flush=True)
                save(step + 1)
                return 0
    if ckpt:
        save(args.steps)
    n = max(1, len(losses) // 5)
    print(
        f"done: first-5-avg={np.mean(losses[:n]):.4f} "
        f"last-5-avg={np.mean(losses[-n:]):.4f}",
        flush=True,
    )
    return 0


if __name__ == "__main__":
    configure_compile_cache()
    raise SystemExit(main())
