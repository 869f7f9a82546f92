"""Solver observability: round traces, host spans, and a metrics registry.

Three layers (DESIGN.md section 14):

  1. **On-device round traces** — `core/engine.py` writes per-round
     diagnostics (J_comm/J_comp split, placement churn, live mask,
     best-iterate round index) into preallocated NaN-padded buffers inside
     the jitted while_loop, under the same inertness contract as the J
     history; `fleet/solve.py` gathers them into the host-side
     `FleetTrace` riding on `FleetResult.trace`.
  2. **Host spans** — `obs.trace.span("solve_fleet.execute", chunk=i)`
     brackets pad/stack/commit/execute/gather boundaries in the fleet
     solver, the launch CLIs, and the benchmark harness; JSONL + Chrome
     trace_event output, optional `jax.profiler.TraceAnnotation`
     passthrough behind REPRO_JAX_TRACE=1, schema validated by
     `python -m repro.obs.validate`.
  3. **Metrics registry** — `obs.metrics.registry`, process-local
     counters/gauges/histograms (chunks executed, pad overhead, rounds vs
     budget, compile warm/cold, serve latencies) snapshotted into the
     launch CLIs' JSON and `benchmarks/run.py --json-out`.

Inside the compiled engine, `obs.trace.engine_scope` names its layers
(`ENGINE_SCOPES`: alt.engine, alt.init, alt.round, alt.placement,
alt.forwarding, alt.round_eval, alt.roundtrace, neumann) as
`jax.named_scope` metadata, which adds no operation. The chip benchmark
splits the engine's device time by them (`bench/harness/scopes.py`):
`placement_ms_per_inst_round.fleet`, `forwarding_ms_per_inst_round.fleet`,
`round_eval_ms_per_inst_round.fleet`, `neumann_ms_per_inst_round.fleet`,
`engine_gap_ms_per_inst_round.fleet` and `engine_ops_per_inst_round.fleet`.
"""
from .metrics import Counter, Gauge, Histogram, MetricsRegistry, registry  # noqa: F401
from .roundtrace import FleetTrace  # noqa: F401
from .trace import (  # noqa: F401
    TRACER,
    SpanEvent,
    Tracer,
    chrome_path_for,
    configure,
    flush,
    maybe_configure_from_env,
    span,
    tracer_enabled,
)
