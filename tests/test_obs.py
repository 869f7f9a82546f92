"""Observability subsystem tests (ISSUE 6 / DESIGN.md section 14).

Three layers, three contracts:

  * on-device round traces (core/engine.py `EngineTrace` -> `FleetTrace`):
    the trace buffers obey the exact NaN-past-freeze contract of the J
    history, frozen lanes stay *bitwise*-inert to extra trips, tracing
    on/off never changes a solved bit, and sharded == unsharded traces;
  * host spans (obs/trace.py): nesting, disabled no-op, JSONL + Chrome
    serialization, and the `repro.obs.validate` schema checker both in the
    accepting and the rejecting direction;
  * metrics registry (obs/metrics.py): get-or-create semantics, type-reuse
    errors, histogram percentiles, snapshot shape.
"""
import json
import re

import jax
import numpy as np
import pytest

from repro.core import random_connected
from repro.core.engine import engine_solve, engine_solve_single
from repro.fleet import sample_fleet, solve_fleet, stack_problems
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.obs.roundtrace import FleetTrace
from repro.obs.validate import validate_events, validate_lines

N_DEV = len(jax.devices())
needs_mesh = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs >= 2 devices; run under "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8",
)

KW = dict(m_max=8, t_phi=5, alpha=0.5, tol=1e-3, patience=3)


@pytest.fixture(autouse=True)
def _clean_global_obs_state():
    """Tests below enable the process-wide tracer/registry; leave none of
    that behind for other test modules."""
    yield
    obs_trace.TRACER.enabled = False
    obs_trace.TRACER.jsonl_path = None
    obs_trace.TRACER.chrome_path = None
    obs_trace.reset()
    obs_metrics.registry.reset()


# ---------------------------------------------------------------------------
# Layer 1: on-device round traces
# ---------------------------------------------------------------------------
class TestEngineTrace:
    def test_trace_nan_past_freeze_matches_history(self):
        """The trace buffers inherit the history's freeze mask exactly:
        NaN wherever the round was not applied, and `live` is that mask in
        arithmetic form."""
        fleet = sample_fleet(4, seed=11)
        res = solve_fleet(fleet, m_max=10, t_phi=4, patience=2)
        t = res.trace
        hist_nan = np.isnan(res.history)
        assert np.array_equal(np.isnan(t.J_comm), hist_nan)
        assert np.array_equal(np.isnan(t.J_comp), hist_nan)
        assert np.array_equal(np.isnan(t.moves), hist_nan)
        assert np.array_equal(t.live > 0, ~hist_nan)
        # live[b, m] == 1  <=>  m <= iters[b]
        for b in range(res.n_instances):
            applied = np.flatnonzero(t.live[b] > 0)
            assert applied[-1] == int(res.iters[b])
        # Column 0 is the structured init: applied to everyone, zero churn.
        assert np.all(t.live[:, 0] == 1.0)
        assert np.all(t.moves[:, 0] == 0.0)

    def test_trace_objective_split_consistent(self):
        """Per-round J_comm + J_comp == history J wherever applied, and
        best_round points at the history's minimum."""
        fleet = sample_fleet(4, seed=12)
        res = solve_fleet(fleet, m_max=10, t_phi=4, patience=2)
        t = res.trace
        applied = ~np.isnan(res.history)
        np.testing.assert_allclose(
            (t.J_comm + t.J_comp)[applied], res.history[applied], rtol=1e-5
        )
        for b in range(res.n_instances):
            m_best = int(t.best_round[b])
            hist = res.history[b][applied[b]]
            # track_best keeps the running min: the recorded round must hold
            # the minimal J seen (ties resolve to the earliest strict win).
            np.testing.assert_allclose(hist[m_best], hist.min(), rtol=1e-6)

    def test_frozen_lane_trace_bits_survive_extra_rounds(self):
        """[fast, slow] vs [fast, fast]: lane 0's trace entries must be
        bitwise-identical even though the mixed batch keeps looping for the
        slow lane (satellite 3's inertness requirement)."""
        fast = random_connected(12, 5, seed=3, load_scale=0.4)
        slow = random_connected(12, 5, seed=4, load_scale=1.1)
        kw = dict(m_max=20, t_phi=5, alpha=0.5, tol=1e-3, patience=2)

        mixed = engine_solve(stack_problems([fast, slow])[0], **kw)
        alone = engine_solve(stack_problems([fast, fast])[0], **kw)
        # Premise: lane 0 froze while lane 1 kept the loop alive.
        assert int(mixed["iters"][0]) < int(mixed["rounds"])
        assert int(mixed["rounds"]) > int(alone["rounds"])

        tm, ta = mixed["trace"], alone["trace"]
        for field in ("J_comm", "J_comp", "moves", "live", "best_round"):
            np.testing.assert_array_equal(
                np.asarray(getattr(tm, field)[0]),
                np.asarray(getattr(ta, field)[0]),
                err_msg=f"trace.{field} lane 0 not bitwise-inert",
            )

    def test_trace_off_is_bitwise_identical_and_none(self):
        """`trace=False` removes the buffers and changes nothing else."""
        fleet = sample_fleet(3, seed=13)
        kw = dict(m_max=6, t_phi=3, patience=2)
        on = solve_fleet(fleet, trace=True, **kw)
        off = solve_fleet(fleet, trace=False, **kw)
        assert off.trace is None and isinstance(on.trace, FleetTrace)
        assert np.array_equal(on.J, off.J)
        assert np.array_equal(on.history, off.history, equal_nan=True)
        assert np.array_equal(on.hosts, off.hosts)
        assert np.array_equal(on.iters, off.iters)
        assert on.rounds == off.rounds

    def test_congunaware_has_no_trace(self):
        res = solve_fleet(sample_fleet(2, seed=14), method="CongUnaware")
        assert res.trace is None
        assert res.m_max == 0

    def test_single_solve_squeezes_trace(self):
        out = engine_solve_single(random_connected(10, 4, seed=5), **KW)
        t = out["trace"]
        assert t.J_comm.ndim == 1 and t.best_round.ndim == 0

    def test_summary_carries_telemetry(self):
        res = solve_fleet(sample_fleet(3, seed=15), m_max=12, t_phi=4)
        s = res.summary()
        assert f"rounds={res.rounds}/12" in s
        assert "churn=" in s
        assert "shard[1dev" in s
        d = res.trace.to_dict()
        assert d["rounds"] == res.rounds
        assert len(d["churn_per_instance"]) == res.n_instances
        assert len(d["frozen_count_per_round"]) == res.rounds + 1

    def test_chunked_trace_gathers_all_instances(self):
        fleet = sample_fleet(5, seed=16)
        res = solve_fleet(fleet, m_max=4, t_phi=3, chunk_size=2)
        assert res.trace.n_instances == 5
        assert np.array_equal(np.isnan(res.trace.J_comm), np.isnan(res.history))

    @needs_mesh
    def test_sharded_trace_parity(self):
        """Sharded vs unsharded solve on a simulated mesh: identical live
        mask / best rounds / churn, allclose objective splits."""
        batch = 10 if N_DEV == 8 else N_DEV + 1  # force pad-and-trim
        fleet = sample_fleet(batch, seed=17)
        kw = dict(m_max=4, t_phi=3, patience=3)
        res_u = solve_fleet(fleet, **kw)
        res_s = solve_fleet(fleet, shard=True, **kw)
        tu, ts = res_u.trace, res_s.trace
        assert ts.n_instances == batch
        np.testing.assert_array_equal(ts.live, tu.live)
        np.testing.assert_array_equal(ts.best_round, tu.best_round)
        np.testing.assert_array_equal(ts.moves, tu.moves)
        np.testing.assert_allclose(ts.J_comm, tu.J_comm, rtol=1e-5)
        np.testing.assert_allclose(ts.J_comp, tu.J_comp, rtol=1e-5)


def _canonical_hlo(text):
    """Compiled HLO text without metadata, the stack-frame tables, and
    with instruction names numbered by first appearance (some names follow
    the name stack)."""
    text = re.sub(r', metadata=\{(?:[^{}"]|"(?:[^"\\]|\\.)*")*\}', "", text)
    text = re.sub(
        r"^(FileNames|FunctionNames|FileLocations|StackFrames)\n.*?\n\n", "",
        text, flags=re.S | re.M,
    )
    names = {}
    return re.sub(
        r"%[\w.\-]+", lambda m: names.setdefault(m.group(0), f"%n{len(names)}"),
        text,
    )


def _engine_hlo(stacked, **kw):
    return engine_solve.lower(stacked, **kw).compile().as_text()


class TestEngineScopes:
    """The engine's named scopes (`obs.trace.ENGINE_SCOPES`) are compile-time
    metadata: every layer is tagged, and nothing else changes."""

    KW = dict(m_max=3, t_phi=2, alpha=0.5, tol=1e-3, patience=2, trace=True)

    @pytest.fixture(scope="class")
    def stacked(self):
        return stack_problems(sample_fleet(2, seed=21))[0]

    @pytest.mark.parametrize("lane_chunk", [1, 0])
    def test_every_scope_tags_the_compiled_engine(self, stacked, lane_chunk):
        text = _engine_hlo(stacked, lane_chunk=lane_chunk, **self.KW)
        scope = re.compile(r"(?<![\w.])(alt\.\w+|neumann)(?![\w.])")
        paths = [scope.findall(o) for o in re.findall(r'op_name="([^"]*)"', text)]
        for name in obs_trace.ENGINE_SCOPES:
            assert any(name in p for p in paths), name
        # each layer holds instructions of its own, not only nested scopes
        innermost = {p[-1] for p in paths if p}
        for name in ("alt.placement", "alt.forwarding", "alt.round_eval",
                     "neumann"):
            assert name in innermost, name

    @pytest.mark.parametrize("lane_chunk", [1, 0])
    def test_scopes_add_no_operation(self, stacked, lane_chunk, monkeypatch):
        """The compiled engine with its scopes is the engine without them,
        once metadata and names are set aside, and solves bit for bit the
        same."""
        import contextlib

        from repro.core import engine as engine_mod
        from repro.kernels.neumann import ops as neumann_ops

        kw = dict(lane_chunk=lane_chunk, **self.KW)
        scoped = _engine_hlo(stacked, **kw)
        out = engine_solve(stacked, **kw)
        jax.clear_caches()
        for mod in (engine_mod, neumann_ops):
            monkeypatch.setattr(
                mod, "engine_scope", lambda name: contextlib.nullcontext()
            )
        try:
            plain = _engine_hlo(stacked, **kw)
            ref = engine_solve(stacked, **kw)
        finally:
            jax.clear_caches()
        assert "alt.engine" in scoped and "alt.engine" not in plain
        assert _canonical_hlo(scoped) == _canonical_hlo(plain)
        for key in ("J", "hosts", "history", "iters"):
            np.testing.assert_array_equal(
                np.asarray(out[key]), np.asarray(ref[key]), err_msg=key
            )

    def test_unknown_scope_raises(self):
        with pytest.raises(ValueError):
            obs_trace.engine_scope("alt.nothing")


# ---------------------------------------------------------------------------
# Layer 2: host spans + validator
# ---------------------------------------------------------------------------
class TestTracer:
    def test_disabled_tracer_records_nothing(self):
        tr = obs_trace.Tracer()
        with tr.span("noop", a=1):
            pass
        assert tr.events() == []

    def test_nesting_and_parent_ids(self):
        tr = obs_trace.Tracer()
        tr.configure(enabled=True)
        with tr.span("root", kind="outer"):
            with tr.span("child"):
                pass
            with tr.span("child2"):
                pass
        events = {e.name: e for e in tr.events()}
        root, child, child2 = events["root"], events["child"], events["child2"]
        assert root.parent == -1 and root.depth == 0
        assert child.parent == root.id and child.depth == 1
        assert child2.parent == root.id and child2.depth == 1
        # Children are recorded before the parent closes.
        names = [e.name for e in tr.events()]
        assert names.index("child") < names.index("root")
        assert root.attrs == {"kind": "outer"}

    def test_execute_splits_into_dispatch_and_wait(self):
        """With the tracer on, `solve_fleet.execute` holds a dispatch span
        and a wait span, and lasts at least as long as both."""
        obs_trace.configure(enabled=True)
        obs_trace.reset()
        solve_fleet(sample_fleet(2, seed=22), m_max=3, t_phi=2)
        events = obs_trace.TRACER.events()
        (execute,) = [e for e in events if e.name == "solve_fleet.execute"]
        kids = {e.name: e for e in events if e.parent == execute.id}
        assert set(kids) == {"solve_fleet.dispatch", "solve_fleet.wait"}
        assert execute.dur >= sum(e.dur for e in kids.values())
        assert kids["solve_fleet.dispatch"].ts <= kids["solve_fleet.wait"].ts

    def test_jsonl_roundtrip_validates(self, tmp_path):
        tr = obs_trace.Tracer()
        tr.configure(enabled=True)
        with tr.span("outer", n=2):
            with tr.span("inner"):
                pass
        path = tmp_path / "t.jsonl"
        tr.write_jsonl(path)
        records, errors = validate_lines(path.read_text().splitlines())
        assert errors == []
        assert len(records) == 2

    def test_chrome_trace_format(self, tmp_path):
        tr = obs_trace.Tracer()
        tr.configure(enabled=True)
        with tr.span("phase"):
            pass
        path = tmp_path / "t.trace.json"
        tr.write_chrome_trace(path)
        payload = json.loads(path.read_text())
        (ev,) = payload["traceEvents"]
        assert ev["ph"] == "X" and ev["name"] == "phase"
        assert ev["dur"] >= 0 and ev["cat"] == "repro"

    def test_chrome_path_for(self):
        assert obs_trace.chrome_path_for("a/b.jsonl") == "a/b.trace.json"
        assert obs_trace.chrome_path_for("x") == "x.trace.json"


class TestValidator:
    def _event(self, **over):
        base = dict(
            id=0, parent=-1, name="e", ts=0.0, dur=1.0, tid=1, depth=0,
            attrs={},
        )
        base.update(over)
        return base

    def test_accepts_well_formed(self):
        assert validate_events([self._event()]) == []

    def test_missing_fields(self):
        errs = validate_events([{"name": "x"}])
        assert any("missing required fields" in e for e in errs)

    def test_rejects_negative_and_wrong_types(self):
        assert validate_events([self._event(ts=-1.0)])
        assert validate_events([self._event(dur="fast")])
        assert validate_events([self._event(name="")])
        assert validate_events([self._event(attrs=[1])])

    def test_rejects_orphan_parent(self):
        errs = validate_events(
            [self._event(id=5, parent=99, depth=1)]
        )
        assert any("parent id 99" in e for e in errs)

    def test_rejects_bad_depth_and_containment(self):
        parent = self._event(id=1, ts=0.0, dur=1.0)
        bad_depth = self._event(id=2, parent=1, depth=2, ts=0.1, dur=0.1)
        escapes = self._event(id=3, parent=1, depth=1, ts=0.5, dur=2.0)
        errs = validate_events([parent, bad_depth, escapes])
        assert any("depth" in e for e in errs)
        assert any("not contained" in e for e in errs)

    def test_rejects_invalid_json_line(self):
        records, errors = validate_lines(["{not json"])
        assert records == [] and any("invalid JSON" in e for e in errors)


# ---------------------------------------------------------------------------
# Layer 3: metrics registry
# ---------------------------------------------------------------------------
class TestMetrics:
    def test_counter_gauge_roundtrip(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("a.events").inc()
        reg.counter("a.events").inc(2)
        reg.gauge("a.level").set(7)
        assert reg.snapshot() == {"a.events": 3, "a.level": 7}

    def test_type_reuse_raises(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("x")
        with pytest.raises(TypeError):
            reg.gauge("x")

    def test_histogram_percentiles(self):
        reg = obs_metrics.MetricsRegistry()
        h = reg.histogram("lat")
        for v in range(1, 101):
            h.observe(float(v))
        snap = reg.snapshot()["lat"]
        assert snap["count"] == 100
        assert snap["p50"] == pytest.approx(50.5)
        assert snap["p95"] == pytest.approx(95.05)
        assert snap["min"] == 1.0 and snap["max"] == 100.0

    def test_empty_histogram(self):
        h = obs_metrics.Histogram()
        assert h.snapshot() == {"count": 0}
        with pytest.raises(ValueError):
            h.percentile(50)

    def test_reset(self):
        reg = obs_metrics.MetricsRegistry()
        reg.counter("c").inc()
        reg.reset()
        assert reg.snapshot() == {}

    def test_solve_fleet_populates_registry(self):
        obs_metrics.registry.reset()
        solve_fleet(sample_fleet(3, seed=18), m_max=4, t_phi=3, chunk_size=2)
        snap = obs_metrics.registry.snapshot()
        assert snap["fleet.chunks_executed"] == 2
        assert snap["fleet.m_max"] == 4
        assert 0.0 <= snap["fleet.pad_overhead_fraction"] < 1.0
        assert snap["fleet.rounds_executed"] <= 4
        # Both chunks share one (shape, kwargs) signature; whether it was
        # cold depends on what earlier tests compiled, but the counts must
        # cover both chunks.
        assert (
            snap.get("fleet.compile.cold", 0)
            + snap.get("fleet.compile.warm", 0) == 2
        )


class TestLaneLayout:
    """The engine layout a solve ran under (`solve_fleet.execute`'s
    `layout`) and its lockstep waste (`fleet.lane_trips{,_frozen}`)."""

    @pytest.mark.parametrize("lane_chunk", [0, 1, 3])
    def test_lockstep_waste_from_the_gathered_result(
        self, lane_chunk, monkeypatch
    ):
        """Lane-trips are B x trips fused and Σ iters lane-major, and are
        read from the one device_get of the gather: no sync is added."""
        fleet = sample_fleet(5, seed=23)
        fetches = []
        device_get = jax.device_get
        monkeypatch.setattr(
            jax, "device_get", lambda t: fetches.append(1) or device_get(t)
        )
        obs_metrics.registry.reset()
        res = solve_fleet(fleet, lane_chunk=lane_chunk, **KW)
        snap = obs_metrics.registry.snapshot()
        assert len(fetches) == 1
        applied = int(res.iters.sum())
        trips = snap["fleet.lane_trips"]
        assert trips - snap["fleet.lane_trips_frozen"] == applied
        waste = snap["fleet.lockstep_waste_fraction"]
        if lane_chunk == 0:
            assert len(set(res.iters.tolist())) > 1  # some lanes froze early
            assert trips == len(fleet) * res.rounds
            assert waste == pytest.approx(
                1.0 - applied / (len(fleet) * res.rounds)
            )
            assert waste > 0.0
        elif lane_chunk == 1:
            assert trips == applied and waste == 0.0
        else:
            it = res.iters
            assert trips == 3 * it[:3].max() + 2 * it[3:].max()

    @pytest.mark.parametrize(
        "lane_chunk, layout", [(0, "fused"), (1, "lane_major"), (3, 3)]
    )
    def test_execute_span_names_the_layout(self, lane_chunk, layout):
        obs_trace.configure(enabled=True)
        obs_trace.reset()
        solve_fleet(sample_fleet(3, seed=22), lane_chunk=lane_chunk,
                    m_max=3, t_phi=2)
        (execute,) = [
            e for e in obs_trace.TRACER.events()
            if e.name == "solve_fleet.execute"
        ]
        assert execute.attrs["layout"] == layout


# ---------------------------------------------------------------------------
# Launch CLI integration
# ---------------------------------------------------------------------------
class TestLaunchIntegration:
    def test_fleet_cli_emits_metrics_trace_and_valid_jsonl(
        self, tmp_path, capsys
    ):
        from repro.launch.fleet import main

        out_path = tmp_path / "spans.jsonl"
        rc = main(
            [
                "--instances", "2", "--m-max", "3", "--t-phi", "3",
                "--trace-out", str(out_path),
            ]
        )
        assert rc == 0
        payload = json.loads(capsys.readouterr().out)
        assert payload["trace"]["rounds"] == payload["rounds"]
        assert "fleet.rounds_executed" in payload["metrics"]
        assert len(payload["trace"]["churn_per_instance"]) == 2
        records, errors = validate_lines(
            out_path.read_text().splitlines()
        )
        assert errors == [] and len(records) >= 4
        names = {r["name"] for r in records}
        assert {"launch.fleet.solve", "solve_fleet.execute"} <= names
