"""Serving entry point: batched prefill + decode with slot-based continuous
batching (vLLM-style, simplified to synchronous steps).

A fixed pool of B slots runs lockstep decode; finished sequences free their
slot and the scheduler admits queued requests via a fresh prefill. Straggler/
hot-node mitigation at the cluster level is the paper's own contribution —
see examples/edge_serving.py where repro.core re-routes around degraded
nodes; this module is the per-node execution engine.

CPU smoke:
  PYTHONPATH=src python -m repro.launch.serve --arch qwen1.5-0.5b --reduced \
      --requests 12 --batch-slots 4 --prompt-len 32 --max-new 16

The JSON summary carries serving SLO telemetry through the obs metrics
registry (DESIGN.md section 14): p50/p95 end-to-end latency, p50/p95
time-to-first-token, and decode throughput, plus the raw registry snapshot
under "metrics". REPRO_TRACE=path additionally records host spans around
the prefill/decode loop.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, reduced_config
from repro.models import decode_step, init_caches, init_params, prefill
from repro.launch.compile_cache import configure_compile_cache
from repro.launch.steps import serve_config
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace


@dataclasses.dataclass
class Request:
    rid: int
    prompt: np.ndarray  # [prompt_len]
    max_new: int
    arrived: float
    started: float | None = None
    tokens: list | None = None
    finished: float | None = None
    # Wall time the first generated token landed (set once; survives the
    # re-prefill hack because dataclasses.replace copies it).
    first_token: float | None = None


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=12)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    obs_trace.maybe_configure_from_env()
    registry = obs_metrics.registry

    cfg = reduced_config(args.arch) if args.reduced else get_config(args.arch)
    cfg = serve_config(cfg)
    if cfg.frontend != "none" or cfg.family == "encdec":
        cfg = dataclasses.replace(cfg, frontend="none", frontend_dim=0)
    params = init_params(cfg, jax.random.PRNGKey(args.seed))

    rng = np.random.RandomState(args.seed)
    queue = [
        Request(
            rid=i,
            prompt=rng.randint(0, cfg.vocab, size=args.prompt_len).astype(np.int32),
            max_new=args.max_new,
            arrived=time.time(),
        )
        for i in range(args.requests)
    ]

    b = args.batch_slots
    jit_prefill = jax.jit(
        lambda p, batch: prefill(cfg, p, batch, args.max_seq)
    )
    jit_decode = jax.jit(
        lambda p, caches, tok, pos: decode_step(cfg, p, caches, tok, pos)
    )

    # Slot state (lockstep positions; per-slot remaining budget).
    active: list[Request | None] = [None] * b
    caches = None
    cur_tokens = np.zeros((b, 1), np.int32)
    pos = args.prompt_len
    done: list[Request] = []
    decode_steps = 0
    t0 = time.time()

    def admit():
        nonlocal caches, cur_tokens, pos
        free = [i for i, r in enumerate(active) if r is None]
        if not free or not queue:
            return
        # Lockstep batch: admit up to all free slots at once with a batched
        # prefill (empty slots run a dummy prompt).
        prompts = np.zeros((b, args.prompt_len), np.int32)
        for i in range(b):
            if active[i] is not None and active[i].tokens:
                continue
        batchful = []
        for i in free:
            if queue:
                r = queue.pop(0)
                r.started = time.time()
                r.tokens = []
                active[i] = r
                batchful.append(i)
        prompts = np.stack(
            [
                active[i].prompt if active[i] is not None
                else np.zeros(args.prompt_len, np.int32)
                for i in range(b)
            ]
        )
        with obs_trace.span("serve.prefill", admitted=len(batchful)):
            new_caches, logits = jit_prefill(
                params, {"tokens": jnp.asarray(prompts)}
            )
        caches = new_caches
        nxt = np.asarray(jnp.argmax(logits[:, -1, :], axis=-1)).astype(np.int32)
        cur_tokens = nxt[:, None]
        pos = args.prompt_len

    with obs_trace.span("serve.run", requests=args.requests, slots=b):
        admit()
        while any(r is not None for r in active) or queue:
            logits, caches = jit_decode(
                params, caches, jnp.asarray(cur_tokens), jnp.int32(pos)
            )
            decode_steps += 1
            nxt = np.asarray(jnp.argmax(logits[:, 0, :], axis=-1)).astype(np.int32)
            pos += 1
            finished_any = False
            for i, r in enumerate(active):
                if r is None:
                    continue
                r.tokens.append(int(nxt[i]))
                if r.first_token is None:
                    r.first_token = time.time()
                if len(r.tokens) >= r.max_new or pos >= args.max_seq - 1:
                    r.finished = time.time()
                    done.append(r)
                    active[i] = None
                    finished_any = True
            cur_tokens = nxt[:, None]
            if finished_any and queue:
                # Simplification: re-prefill the whole batch when slots free
                # up (a real engine would use paged attention to splice
                # requests).
                for i, r in enumerate(active):
                    if r is not None:
                        queue.insert(0, dataclasses.replace(r))
                        active[i] = None
                admit()

    dt = time.time() - t0
    total_tokens = sum(len(r.tokens) for r in done)

    # Serving SLOs through the obs registry (layer 3): the percentiles the
    # JSON reports are computed FROM the histogram snapshot, so the CLI and
    # any metrics consumer can never disagree.
    lat_hist = registry.histogram("serve.latency_s")
    ttft_hist = registry.histogram("serve.ttft_s")
    for r in done:
        lat_hist.observe(r.finished - r.arrived)
        if r.first_token is not None:
            ttft_hist.observe(r.first_token - r.arrived)
    registry.counter("serve.requests").inc(len(done))
    decode_tps = total_tokens / dt
    registry.gauge("serve.decode_tokens_per_s").set(decode_tps)
    lat_snap = lat_hist.snapshot()
    ttft_snap = ttft_hist.snapshot()
    print(
        json.dumps(
            {
                "requests": len(done),
                "decode_steps": decode_steps,
                "generated_tokens": total_tokens,
                "tokens_per_s": round(total_tokens / dt, 2),
                "decode_tokens_per_s": round(decode_tps, 2),
                "mean_latency_s": round(lat_snap["mean"], 3),
                "p50_latency_s": round(lat_snap["p50"], 3),
                "p95_latency_s": round(lat_snap["p95"], 3),
                "p50_ttft_s": round(ttft_snap["p50"], 3),
                "p95_ttft_s": round(ttft_snap["p95"], 3),
                "metrics": {
                    k: (
                        {kk: round(vv, 4) for kk, vv in v.items()}
                        if isinstance(v, dict)
                        else round(v, 4) if isinstance(v, float) else v
                    )
                    for k, v in registry.snapshot().items()
                },
            }
        ),
        flush=True,
    )
    obs_trace.flush()
    return 0


if __name__ == "__main__":
    configure_compile_cache()
    raise SystemExit(main())
