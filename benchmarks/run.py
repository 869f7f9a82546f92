"""Benchmark harness entry point: one benchmark per paper table/figure plus
the framework-level benches. Prints `name,<payload>` lines and exits nonzero
if any paper claim fails.

    PYTHONPATH=src python -m benchmarks.run [--only fig2,fig4,...] [--json-out]
        [--check-trend] [--trend-tol 0.2] [--trend-metrics all|ratios]

`--json-out` persists each bench's result dict as `BENCH_<name>.json` at the
repo root (commit hash + dirty-worktree flag + timings + speedups), so the
perf trajectory is tracked PR-over-PR and CI can upload the files as
artifacts. Under
SCALE_SMALL=1 the file is `BENCH_<name>.small.json` instead: small-tier
smoke numbers must never overwrite (or be compared against) the full-scale
trajectory.

`--check-trend` is the trend-lint: it compares the fresh result against the
committed baseline JSON for the same scale tier and exits nonzero on a
>`--trend-tol` (default 20%) regression of any per-round timing (lower is
better) or speedup/ratio metric (higher is better). `--trend-metrics ratios`
restricts the check to machine-portable metrics — what CI uses, since raw
per-round milliseconds are only comparable on similar hardware. Portable
metrics are the speedups/ratios plus the solver-telemetry counts
(`rounds_executed`, `pad_overhead`): more rounds to hit the same tolerance
is a convergence regression no matter the machine. A baseline section
recorded as `{"skipped": true}` is REFUSED when the fresh run produced
numbers for it (a partial baseline lints nothing, forever), and a baseline
whose commit is not an ancestor of HEAD — or that was measured from a
dirty worktree — is warned about.

When REPRO_FLEET_SECTIONS explicitly requests the fleet bench's
`shard_axis` section, the harness sets
`XLA_FLAGS=--xla_force_host_platform_device_count=8` BEFORE importing jax,
so a single-device host that asks for the mesh section actually gets a
mesh. The default run leaves XLA_FLAGS alone — forcing the split shifts
every other section's warm timings, so committed baselines stay measured
on the native topology.

Observability (DESIGN.md section 14): each bench runs inside a host span
and with a cleared metrics registry; whatever the instrumented solvers
record lands in the bench result under "metrics", so the committed BENCH
files carry telemetry alongside timings. REPRO_TRACE=path.jsonl records
the span trace across the whole run.
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time
import traceback


def _fleet_shard_requested() -> bool:
    """Was the fleet bench's shard-axis section EXPLICITLY requested?

    Explicit means REPRO_FLEET_SECTIONS names `shard_axis` (and --only does
    not exclude the fleet bench). The default run deliberately does NOT
    count: forcing the simulated 8-device mesh reshapes the host's XLA
    device topology, which shifts every section's warm timings (measured:
    the batched engine loses ~30% warm throughput under the split), so the
    committed baselines must be measured without it and the shard section
    reports itself skipped on single-device hosts instead.
    """
    only = None
    for i, a in enumerate(sys.argv):
        if a == "--only" and i + 1 < len(sys.argv):
            only = sys.argv[i + 1]
        elif a.startswith("--only="):
            only = a.split("=", 1)[1]
    if only is not None and "fleet" not in only.split(","):
        return False
    sections = os.environ.get("REPRO_FLEET_SECTIONS")
    if not sections:
        return False
    return "shard_axis" in [s.strip() for s in sections.split(",")]


# Must run BEFORE anything imports jax: the XLA platform reads XLA_FLAGS at
# backend initialization, so a single-device host can only present the
# simulated 8-CPU mesh the shard-axis section needs if the flag is already
# set here. If jax snuck in first (run.py imported from another script),
# leave the environment alone — a flag change would silently not apply.
if _fleet_shard_requested() and "jax" not in sys.modules:
    _flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in _flags:
        os.environ["XLA_FLAGS"] = (
            _flags + " --xla_force_host_platform_device_count=8"
        ).strip()

from benchmarks import (  # noqa: E402
    fig2_scenarios,
    fig4_load_sweep,
    fig5_tradeoff,
    fleet_bench,
    kernel_bench,
    pareto_bench,
    roofline,
    scale_control_plane,
    serve_bench,
    table1_topologies,
)
from repro.obs import metrics as obs_metrics  # noqa: E402
from repro.obs import trace as obs_trace  # noqa: E402

# Every benchmarks/*.py module (except this harness) is registered here, so
# --only accepts each by name and the table is the complete inventory.
BENCHES = {
    "table1": table1_topologies.run,   # Table I scenario configs
    "fig2": fig2_scenarios.run,        # scenarios x methods (headline)
    "fig4": fig4_load_sweep.run,       # load sweep (batched fleet)
    "fig5": fig5_tradeoff.run,         # comm/comp tradeoff (batched fleet)
    "kernels": kernel_bench.run,       # Pallas kernels vs oracles
    "scale": scale_control_plane.run,  # beyond-paper: fleet-scale control
    "fleet": fleet_bench.run,          # batched-vs-sequential + solver axis
    "pareto": pareto_bench.run,        # split-point Pareto search (DESIGN 17)
    "serve": serve_bench.run,          # chaos control loop (epochs/sec, p95)
    "roofline": roofline.run,          # informational; needs dry-run artifacts
}

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _scale_tier() -> str:
    return "small" if os.environ.get("SCALE_SMALL") else "full"


def bench_json_path(name: str) -> pathlib.Path:
    """Per-tier result file: small-tier smoke runs get their own baseline."""
    suffix = "" if _scale_tier() == "full" else ".small"
    return REPO_ROOT / f"BENCH_{name}{suffix}.json"


def _git(*argv: str) -> subprocess.CompletedProcess:
    return subprocess.run(
        ["git", *argv], cwd=REPO_ROOT, capture_output=True, text=True
    )


def _commit_hash() -> str:
    try:
        r = _git("rev-parse", "HEAD")
        return r.stdout.strip() if r.returncode == 0 else "unknown"
    except Exception:
        return "unknown"


def _worktree_dirty() -> bool | None:
    """Uncommitted changes in tracked files (None if git is unavailable)."""
    try:
        r = _git("status", "--porcelain", "--untracked-files=no")
        return bool(r.stdout.strip()) if r.returncode == 0 else None
    except Exception:
        return None


def _baseline_commit_is_ancestor(commit: str) -> bool | None:
    """Whether `commit` is an ancestor of HEAD (None = undecidable)."""
    if not commit or commit == "unknown":
        return None
    try:
        r = _git("merge-base", "--is-ancestor", commit, "HEAD")
    except Exception:
        return None
    if r.returncode == 0:
        return True
    if r.returncode == 1:
        return False
    return None  # unknown object (shallow clone, foreign repo), can't say


def write_json(name: str, payload, elapsed_s: float) -> pathlib.Path:
    """Persist one bench result as BENCH_<name>[.small].json at the repo root."""
    path = bench_json_path(name)
    record = {
        "bench": name,
        "commit": _commit_hash(),
        # Provenance: a baseline measured from an uncommitted tree is not
        # reproducible from its recorded commit — flag it in the file so a
        # trend comparison (and a reviewer) can see it.
        "dirty": _worktree_dirty(),
        "timestamp": time.strftime("%Y-%m-%dT%H:%M:%SZ", time.gmtime()),
        "scale": _scale_tier(),
        "elapsed_s": round(elapsed_s, 2),
        "result": payload,
    }
    path.write_text(json.dumps(record, indent=1, default=str) + "\n")
    return path


# ---------------------------------------------------------------------------
# Trend lint: fresh timings vs the committed BENCH_<name>.json baseline
# ---------------------------------------------------------------------------
def trend_metrics(result, prefix: str = "") -> dict:
    """Extract comparable leaves: {dotted.path: (value, direction, portable)}.

    direction "lower" — per-round / per-op timings (path contains
    "per_round", or the key is a microsecond/millisecond reading); raw
    end-to-end seconds are deliberately excluded as too noisy. Also the
    solver-telemetry counts (`rounds_executed`, `pad_overhead`): more
    rounds — or more inert pad lanes — at the same tolerance is a
    convergence/layout regression.
    direction "higher" — speedups and ratios.

    portable=True marks metrics comparable across machines (speedups,
    ratios, and the telemetry counts — round counts don't depend on the
    hardware clock); --trend-metrics ratios keeps only those.
    """
    out = {}
    if isinstance(result, dict):
        for k, v in result.items():
            out.update(trend_metrics(v, f"{prefix}{k}."))
        return out
    if not isinstance(result, (int, float)) or isinstance(result, bool):
        return out
    path = prefix.rstrip(".")
    key = path.rsplit(".", 1)[-1]
    if "speedup" in key or "ratio" in key:
        out[path] = (float(result), "higher", True)
    elif "rounds_executed" in key or "pad_overhead" in key:
        out[path] = (float(result), "lower", True)
    elif "per_round" in path or key.endswith(("_ms", "_us")):
        out[path] = (float(result), "lower", False)
    elif key.endswith("_per_s"):
        # Throughput rates (candidates/sec, epochs/sec): higher is better,
        # but absolute rates are hardware-bound — compared only under
        # --trend-metrics all (the pareto CI job uses a generous tol).
        out[path] = (float(result), "higher", False)
    return out


def skipped_sections(result, prefix: str = "") -> list[str]:
    """Dotted paths of every `{"skipped": true}` marker in a result dict."""
    out = []
    if isinstance(result, dict):
        if result.get("skipped") is True:
            out.append(prefix.rstrip("."))
        for k, v in result.items():
            out.extend(skipped_sections(v, f"{prefix}{k}."))
    return out


def check_trend(
    name: str, fresh, baseline_record, *, tol: float, ratios_only: bool
) -> list[str]:
    """Compare one fresh result dict to its committed baseline record.

    Returns human-readable regression strings (empty = clean)."""
    regressions = []
    # Provenance guards. (1) A baseline whose section never ran has no
    # numbers to compare — linting "against" it silently passes forever, so
    # a section the fresh run DID produce numbers for refuses the partial
    # baseline outright. (2) A baseline from a commit that is not an
    # ancestor of HEAD (rebased away, or measured on another branch) is
    # only warned about: the numbers may still be comparable, but the
    # reader should know the trajectory has a seam.
    base_skipped = set(skipped_sections(baseline_record.get("result", {})))
    fresh_skipped = set(skipped_sections(fresh))
    stale = sorted(base_skipped - fresh_skipped)
    if stale:
        for path in stale:
            regressions.append(
                f"{name}:{path} baseline section was recorded as skipped — "
                "no numbers to lint against; regenerate the baseline with "
                "the section enabled"
            )
            print(
                f"trend,{name} {path}: baseline skipped [REFUSED]", flush=True
            )
    b_commit = baseline_record.get("commit", "")
    if _baseline_commit_is_ancestor(b_commit) is False:
        print(
            f"trend,{name} WARNING: baseline commit {b_commit[:12]} is not "
            "an ancestor of HEAD (rebase? foreign baseline?) — comparison "
            "may span divergent code",
            flush=True,
        )
    if baseline_record.get("dirty"):
        print(
            f"trend,{name} WARNING: baseline was recorded from a dirty "
            "worktree — its commit hash does not pin the measured code",
            flush=True,
        )
    base = trend_metrics(baseline_record.get("result", {}))
    new = trend_metrics(fresh)
    for path, (b_val, direction, portable) in sorted(base.items()):
        if path not in new:
            continue
        if ratios_only and not portable:
            continue
        n_val = new[path][0]
        if direction == "lower":
            # Zero-baseline counts (e.g. pad overhead 0.0) can't regress by
            # ratio; any increase from exactly zero is flagged.
            bad = n_val > 0 if b_val == 0 else n_val > b_val * (1.0 + tol)
        else:
            bad = n_val < b_val * (1.0 - tol)
        pct = "" if b_val == 0 else f" ({(n_val / b_val - 1) * 100:+.0f}%)"
        arrow = f"{b_val:.4g} -> {n_val:.4g}{pct}"
        status = "REGRESSION" if bad else "ok"
        print(f"trend,{name} {path}: {arrow} [{status}]", flush=True)
        if bad:
            regressions.append(f"{name}:{path} {arrow}")
    return regressions


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="comma-separated bench names")
    ap.add_argument(
        "--json-out",
        action="store_true",
        help="write BENCH_<name>[.small].json (commit hash + result dict)",
    )
    ap.add_argument(
        "--check-trend",
        action="store_true",
        help="fail on >--trend-tol regressions vs the committed baseline "
        "JSON of the same scale tier",
    )
    ap.add_argument(
        "--trend-tol",
        type=float,
        default=0.2,
        help="fractional regression tolerance for --check-trend (default 0.2)",
    )
    ap.add_argument(
        "--trend-metrics",
        choices=("all", "ratios"),
        default="all",
        help="'ratios' compares only speedups/ratios (machine-portable; "
        "use in CI where absolute timings are not comparable)",
    )
    ap.add_argument(
        "--use-pallas",
        action="store_true",
        help="benchmark the Pallas kernel path instead of pure XLA (exported "
        "to benches via REPRO_BENCH_USE_PALLAS; see benchmarks/_knobs.py)",
    )
    ap.add_argument(
        "--interpret",
        action=argparse.BooleanOptionalAction,
        default=False,
        help="with --use-pallas, run kernel bodies under the Pallas "
        "interpreter (CPU only; off by default)",
    )
    args = ap.parse_args()
    if args.use_pallas:
        os.environ["REPRO_BENCH_USE_PALLAS"] = "1"
        os.environ["REPRO_BENCH_INTERPRET"] = "1" if args.interpret else "0"
    obs_trace.maybe_configure_from_env()
    names = list(BENCHES) if not args.only else args.only.split(",")
    failures = []
    regressions = []
    for name in names:
        # Read the committed baseline BEFORE --json-out overwrites it.
        baseline = None
        if args.check_trend and bench_json_path(name).exists():
            baseline = json.loads(bench_json_path(name).read_text())
        t0 = time.time()
        print(f"=== {name} ===", flush=True)
        try:
            # Per-bench metrics isolation: whatever the instrumented solvers
            # record during THIS bench rides on its result (and baseline).
            obs_metrics.registry.reset()
            with obs_trace.span("bench", bench=name):
                result = BENCHES[name]()
            if isinstance(result, dict):
                snap = obs_metrics.registry.snapshot()
                if snap:
                    result["metrics"] = snap
            elapsed = time.time() - t0
            if args.check_trend and result is not None:
                if baseline is None:
                    # First run of a new bench (e.g. BENCH_serve.json before
                    # it ever landed): warn AND record the fresh result as
                    # the baseline, so the next run has something to lint
                    # against instead of KeyError-ing or silently skipping
                    # forever.
                    path = write_json(name, result, time.time() - t0)
                    print(
                        f"trend,{name} no committed baseline for tier "
                        f"'{_scale_tier()}' — recorded "
                        f"{path.relative_to(REPO_ROOT)} as the new baseline",
                        flush=True,
                    )
                else:
                    regressions += check_trend(
                        name,
                        result,
                        baseline,
                        tol=args.trend_tol,
                        ratios_only=args.trend_metrics == "ratios",
                    )
            if args.json_out and result is not None:
                path = write_json(name, result, elapsed)
                print(f"wrote {path.relative_to(REPO_ROOT)}", flush=True)
            print(f"=== {name} done ({elapsed:.1f}s) ===", flush=True)
        except Exception:
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"FAILED: {failures}")
        return 1
    if regressions:
        print("TREND REGRESSIONS (>{:.0%}):".format(args.trend_tol))
        for r in regressions:
            print(f"  {r}")
        return 2
    print("all benchmarks passed")
    return 0


if __name__ == "__main__":
    sys.exit(main())
