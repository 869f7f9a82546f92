"""One run of one cell: look-up by name, set-up, the measured window, the
per-layer reading and the check against the reference.

Everything a cell needs is found by name:
  BENCHMARK.json          the cell (config, traffic) and the metric list
  bench/configs/<c>.json  the deployment (generator and its arguments)
  bench/traffic/<t>.json  the mix: which driver, and its parameters
  bench/drivers/<d>.py    the entry point a window drives
  bench/limits/<cell>.json  the limit of each number the check compares
  bench/metrics/<m>.py    one reader per per-layer metric
"""
from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from . import checks

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


def load_json(path):
    with open(path) as fh:
        return json.load(fh)


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def lookup(workload, root=ROOT):
    """(cell, config, traffic, limits, driver module, metrics) by name."""
    bench = load_json(Path(root) / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cell = cells[workload]
    bdir = Path(root) / "bench"
    config = load_json(bdir / "configs" / f"{cell['config']}.json")
    traffic = load_json(bdir / "traffic" / f"{cell['traffic']}.json")
    limits = load_json(bdir / "limits" / f"{workload}.json")
    driver = load_module(bdir / "drivers" / f"{traffic['driver']}.py",
                         f"bench_driver_{traffic['driver']}")

    def applies(m):
        return workload in m.get("workloads", [workload])

    e2e = [m for m in bench["end_to_end"] if applies(m)]
    layer = [m for m in bench["per_layer"] if applies(m)]
    return cell, config, traffic, limits, driver, e2e, layer


def sub_seeds(seed, n):
    """n independent 31-bit seeds from one run seed of any size."""
    ss = np.random.SeedSequence(int(seed))
    return [int(x) % (2**31 - 1) for x in ss.generate_state(n, np.uint32)]


class CompileCounter:
    """Programs lowered (jit cache misses) and XLA backend compiles, from
    JAX's own monitoring events."""

    LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"
    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax

        self.lowered = 0
        self.backend = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self.LOWER:
            self.lowered += 1
        elif event == self.BACKEND:
            self.backend += 1

    def snapshot(self):
        from repro.obs.metrics import registry

        return dict(lowered=self.lowered, backend=self.backend,
                    fleet_cold=registry.counter("fleet.compile.cold").value)


class Window:
    """The measured window: `running()` until `seconds` have passed since
    `start()`. With a profile directory, the JAX profiler captures the
    window's first operation (up to the first `tick()`) inside a
    `bench.window` annotation: a whole call already writes a trace
    of millions of device events. The time the profiler then takes to write
    it is added to the window, so the rest of the window runs untraced."""

    def __init__(self, seconds, profile_dir=None):
        self.seconds = float(seconds)
        self.profile_dir = profile_dir
        self._annotation = None
        self.t_start = self.t_end = self.deadline = None
        self.trace_span = None  # host-clock (start, end) of the capture

    def start(self):
        if self.profile_dir is not None:
            import jax

            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.profile_dir, profiler_options=opts)
            self._annotation = jax.profiler.TraceAnnotation("bench.window")
            self._annotation.__enter__()
        self.t_start = time.perf_counter()
        self.deadline = self.t_start + self.seconds
        if self.profile_dir is not None:
            self.trace_span = [self.t_start, None]

    def running(self) -> bool:
        return time.perf_counter() < self.deadline

    def tick(self):
        """Call between operations: ends the capture."""
        self.stop_capture()

    def stop_capture(self):
        if self._annotation is not None:
            import jax

            self._annotation.__exit__(None, None, None)
            self._annotation = None
            self.trace_span[1] = time.perf_counter()
            jax.profiler.stop_trace()
            self.deadline += time.perf_counter() - self.trace_span[1]

    def finish(self):
        self.t_end = time.perf_counter()
        self.stop_capture()


class Reading:
    """What a per-layer metric reader sees after the window."""

    def __init__(self, ctx, spans, trace, window):
        self.ctx = ctx
        self.spans = spans  # closed obs spans of the window
        self.trace = trace  # tracing.reduce(...) of the capture, or None
        self.window = window

    def spans_named(self, name):
        return [s for s in self.spans if s.name == name]

    def in_capture(self, t0, t1) -> bool:
        """Whether a host-clock interval lies inside the profiled capture."""
        if self.window.trace_span is None or self.window.trace_span[1] is None:
            return False
        lo, hi = self.window.trace_span
        return t0 >= lo and t1 <= hi


def compare(numbers, limits):
    """[(name, value, limit, ok)] for every limited number."""
    out = []
    for name, limit in limits.items():
        value = numbers.get(name, float("inf"))
        out.append((name, value, limit, bool(value <= limit)))
    return out


def run_cell(workload, seed, seconds, trace, *, root=ROOT, t_process=None,
             overrides=None, fault=None, log=None):
    """Set up, measure and check one cell. Returns the result dict (without
    the device block, which the caller adds). `overrides` replaces config
    and traffic keys, and `fault` names a fault of harness/faults.py to
    plant under the timed path: both for the tests."""
    import jax

    from repro.obs import trace as obs_trace

    log = log or (lambda *a: print(*a, file=sys.stderr, flush=True))
    t_process = time.perf_counter() if t_process is None else t_process
    cell, config, traffic, limits, driver, e2e, layer = lookup(workload, root)
    if overrides:
        config = {**config, **overrides.get("config", {})}
        traffic = {**traffic, **overrides.get("traffic", {})}
    counter = CompileCounter()

    ctx = driver.setup(config, traffic, seed, log=log)
    setup_s = time.perf_counter() - t_process

    tmp = tempfile.TemporaryDirectory(prefix="bench-profile-") if trace else None
    with tmp or contextlib.nullcontext():
        if trace:
            os.environ["REPRO_JAX_TRACE"] = "1"
            obs_trace.configure(enabled=True)
            obs_trace.reset()
        win = Window(seconds, profile_dir=tmp.name if trace else None)
        before = counter.snapshot()
        win.start()
        if fault is None:
            driver.window(ctx, win)
        else:
            from . import faults

            driver.window(ctx, win, solve=faults.FAULTS[fault](driver.solve_fleet))
        win.finish()
        after = counter.snapshot()
        spans = obs_trace.TRACER.events() if trace else []
        if trace:
            obs_trace.configure(enabled=False)
            os.environ.pop("REPRO_JAX_TRACE", None)
        reduced = None
        if trace:
            from . import tracing

            ev = tracing.load(tmp.name)
            if ev is not None:
                reduced = tracing.reduce(ev["ops"], ev["modules"], ev["host"], ev["window"])
    compiles = {k: after[k] - before[k] for k in after}
    log(json.dumps({"compiles_in_window": compiles, "window_s": win.t_end - win.t_start}))

    mem = None
    with contextlib.suppress(Exception):
        mem = int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])

    metrics = {}
    breakdown = None
    if trace:
        reading = Reading(ctx, spans, reduced, win)
        for m in layer:
            mod = load_module(Path(root) / "bench" / "metrics" / f"{m['name']}.py",
                              "bench_metric_" + m["name"].replace(".", "_"))
            value = mod.read(reading)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        if reduced is not None:
            breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    else:
        values = driver.end_to_end(ctx)
        for m in e2e:
            if m["name"] == "setup_s":
                metrics["setup_s"] = {"value": setup_s, "unit": "s"}
            elif m["name"] in values:
                metrics[m["name"]] = {"value": float(values[m["name"]]), "unit": m["unit"]}

    attempted, failed = driver.counts(ctx)
    t_check = time.perf_counter()
    got, missing = driver.answers(ctx)
    numbers = checks.numbers(got)
    numbers["unanswered"] = missing
    log(json.dumps({"checked": numbers.pop("checked"),
                    "check_s": time.perf_counter() - t_check}))
    compared = compare(numbers, limits)
    correct = all(ok for *_, ok in compared) and attempted > 0
    for name, value, limit, ok in compared:
        log(f"check {name} {value!r} limit {limit!r} {'ok' if ok else 'FAILED'}")
    out = {
        "correct": bool(correct),
        "attempted": int(attempted),
        "failed": int(failed),
        "metrics": metrics,
        "memory_peak_bytes": mem,
        "compiles_in_window": compiles,
    }
    if trace and reduced is not None:
        out["busy_s"] = reduced["busy_s"]
        out["window_s"] = reduced["window_s"]
    if breakdown is not None:
        out["breakdown"] = breakdown
    out["checks"] = {n: {"value": v, "limit": l} for n, v, l, _ in compared}
    return out
