#!/usr/bin/env python3
"""The readings that the limits of a cell are set from (not run by the
benchmark's own runs).

    python3 bench/readings.py --workload <cell> --seeds 1,2,3 --seconds 10 \\
        [--faults unchanged,one_round,two_rounds,half_batch,altered]

In one process, for each seed: the cell's set-up, a window with the
program as it is (its numbers: the lower readings), the same answers
replaced by the control's (the reference in bfloat16 in the program's
place: the upper readings), and a window for each planted fault
(bench/harness/faults.py). One JSON line per seed and reading. Runs on
the TPU only, like bench/run.py.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--faults", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(BENCH))
    import jax

    if jax.devices()[0].platform != "tpu":
        print("readings: no TPU", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".bench_cache" / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)

    from harness import checks, faults
    from harness.cell import Window, lookup
    from repro.fleet import solve_fleet

    _, config, traffic, _, driver, _, _ = lookup(args.workload, ROOT)
    kinds = [f for f in args.faults.split(",") if f]

    def emit(seed, what, numbers, **extra):
        line = {"workload": args.workload, "seed": seed, "reading": what,
                **{k: numbers[k] for k in sorted(numbers)}, **extra}
        print(json.dumps(line, default=float), flush=True)

    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        ctx = driver.setup(config, traffic, seed, log=lambda *a: None)
        setup_s = time.perf_counter() - t0
        for kind in [None] + kinds:
            solve = solve_fleet if kind is None else faults.FAULTS[kind](solve_fleet)
            win = Window(args.seconds)
            win.start()
            driver.window(ctx, win, solve=solve)
            win.finish()
            attempted, failed = driver.counts(ctx)
            got, missing = driver.answers(ctx)
            numbers = checks.numbers(got)
            numbers["unanswered"] = missing
            if kind is not None:
                emit(seed, kind, numbers, attempted=attempted, failed=failed)
                continue
            emit(seed, "program", numbers, attempted=attempted, failed=failed,
                 setup_s=setup_s, e2e=driver.end_to_end(ctx))
            emit(seed, "control", checks.numbers(got, control=True))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
