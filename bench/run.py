#!/usr/bin/env python3
"""Run one benchmark cell once, on the TPU it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Sets the cell up from its seed (inputs generated, every program the window
uses compiled or loaded from the compile cache), measures for `--seconds`,
then checks a seeded sample of the window's answers against the plain
reference in bench/harness/reference.py. The last line of standard output
is one JSON object: correct, attempted, failed, metrics (the cell's
end-to-end metrics, or with --trace 1 its per-layer metrics), device and,
last, the numbers compared with their limits. The same numbers end standard
error. Without a TPU, or with fewer chips than the cell asks for, it exits
nonzero and prints no result; there is no CPU fallback.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE_DIR = ROOT / ".bench_cache" / "jax"


def fail(msg: str) -> int:
    print(f"bench: {msg}", file=sys.stderr, flush=True)
    return 2


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    try:
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        cell = {w["name"]: w for w in bench["workloads"]}[args.workload]
        peaks = json.loads((BENCH / "peaks.json").read_text())
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot find the cell {args.workload!r}: {e!r}")
    if not (ROOT / "src" / "repro").is_dir():
        return fail("the program (src/repro) is not in this checkout")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU (device 0 is {devices[0].platform!r})")
    if len(devices) < int(cell["chips"]):
        return fail(f"cell needs {cell['chips']} chips, found {len(devices)}")
    kind = devices[0].device_kind
    if kind not in peaks["devices"]:
        return fail(f"device kind {kind!r} is not in bench/peaks.json")

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from harness.cell import run_cell

    out = run_cell(args.workload, args.seed, args.seconds, bool(args.trace),
                   root=ROOT, t_process=T_PROCESS)
    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(devices),
        "memory_peak_bytes": out.pop("memory_peak_bytes"),
    }
    if args.trace:
        device["busy_s"] = out.pop("busy_s", None)
        device["window_s"] = out.pop("window_s", None)
    checks = out.pop("checks")
    out["device"] = device
    out["checks"] = checks
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
