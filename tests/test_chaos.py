"""Fault-injection suite: chaos traces, repair semantics, warm-start parity,
input validation, and the epoch controller (DESIGN.md section 15).

The load-bearing contracts:
  * failure inertness — after ANY event sequence, no live partition is
    hosted on a masked-out node (hypothesis property), and repair leaves
    no phi mass flowing INTO dead nodes;
  * empty-trace stability — repairing with an all-live mask is bitwise
    identity on the State;
  * warm-start parity — a frozen warm lane returns exactly its init-state
    evaluation; an active warm re-solve from the cold optimum matches the
    cold objective at rtol 1e-5 on all four paper topologies;
  * the controller never ends an epoch without a servable placement.
"""
from __future__ import annotations

import dataclasses
import functools
import json

import numpy as np
import pytest

from repro.chaos import (
    NODE_DOWN,
    InstanceHealth,
    apply_health,
    generate_trace,
    repair_fleet,
)
from repro.core.scenarios import SCENARIOS
from repro.core.structs import BIG
from repro.fleet import (
    EmptyFleetError,
    NU_PAD,
    iot_hierarchy,
    pad_batch_to_multiple,
    sample_fleet,
    solve_fleet,
)

from _optional_deps import given, settings, st


def _small_fleet(n=3, seed=11):
    return sample_fleet(n, families=["iot_hierarchy"], seed=seed)


SOLVE_KW = dict(m_max=3, t_phi=3, round_to=8)


@functools.lru_cache(maxsize=1)
def _property_fixture():
    """One solved fleet shared by every hypothesis example (the property
    varies the EVENT sequence, not the solve)."""
    fleet = _small_fleet(3, seed=77)
    state = solve_fleet(fleet, keep_state=True, **SOLVE_KW).state
    return fleet, state


# ---------------------------------------------------------------------------
# Trace generation
# ---------------------------------------------------------------------------
def test_trace_deterministic_and_counted():
    fleet = _small_fleet()
    t1 = generate_trace(fleet, 12, seed=5, node_failures=3,
                        link_degradations=2, flash_crowds=1)
    t2 = generate_trace(fleet, 12, seed=5, node_failures=3,
                        link_degradations=2, flash_crowds=1)
    assert t1.events == t2.events
    c = t1.counts()
    assert c["node_down"] == 3
    assert c["link_degrade"] == 2
    assert c["flash_crowd"] == 1
    # recoveries never outnumber their faults
    assert c["node_up"] <= c["node_down"]
    assert c["link_restore"] <= c["link_degrade"]


def test_trace_never_kills_endpoints_or_disconnects():
    fleet = _small_fleet(4, seed=2)
    trace = generate_trace(fleet, 20, seed=9, node_failures=6,
                           link_degradations=3, flash_crowds=1)
    from repro.chaos.events import _connected_without, _protected_nodes

    protected = [_protected_nodes(p) for p in fleet]
    for _, fired, healths in trace.timeline():
        for ev in fired:
            if ev.kind == NODE_DOWN:
                assert ev.node not in protected[ev.instance]
        for i, h in enumerate(healths):
            if h.down:
                adj = np.asarray(fleet[i].net.adj)
                assert _connected_without(adj, h.down)


def test_apply_health_uses_pad_encoding():
    p = iot_hierarchy(seed=1, n_edge=3, devices_per_edge=2, n_apps=4)
    dead = next(
        v for v in range(p.net.n_nodes)
        if v not in set(map(int, np.asarray(p.apps.src)))
        | set(map(int, np.asarray(p.apps.dst)))
    )
    h = InstanceHealth(down=frozenset({dead}), rate_scale=2.0)
    q, live = apply_health(p, h)
    assert live[dead] == 0.0 and live.sum() == p.net.n_nodes - 1
    adj = np.asarray(q.net.adj)
    mu = np.asarray(q.net.mu)
    nu = np.asarray(q.net.nu)
    assert (adj[dead, :] == 0).all() and (adj[:, dead] == 0).all()
    assert (mu[dead, :] == BIG).all() and (mu[:, dead] == BIG).all()
    assert nu[dead] == np.float32(NU_PAD)
    np.testing.assert_allclose(
        np.asarray(q.apps.lam), np.asarray(p.apps.lam) * 2.0, rtol=1e-6
    )
    # pristine health is a structural no-op (same object, same program)
    q2, live2 = apply_health(p, InstanceHealth())
    assert q2 is p and live2.all()
    # perturbation never changes shapes or static metadata
    assert q.hop_bound == p.hop_bound
    assert q.net.adj.shape == p.net.adj.shape


def test_link_degrade_scales_both_directions():
    p = iot_hierarchy(seed=1, n_edge=3, devices_per_edge=2, n_apps=4)
    adj = np.asarray(p.net.adj)
    u, v = map(int, np.argwhere(np.triu((adj > 0) | (adj.T > 0), 1))[0])
    h = InstanceHealth(link_scale=(((u, v), 0.5),))
    q, live = apply_health(p, h)
    assert live.all()
    mu0, mu1 = np.asarray(p.net.mu), np.asarray(q.net.mu)
    for a, b in ((u, v), (v, u)):
        if adj[a, b] > 0:
            np.testing.assert_allclose(mu1[a, b], mu0[a, b] * 0.5, rtol=1e-6)


# ---------------------------------------------------------------------------
# Repair semantics
# ---------------------------------------------------------------------------
def test_repair_identity_on_empty_trace():
    fleet, state = _property_fixture()
    masks = [np.ones(p.net.n_nodes, np.float32) for p in fleet]
    rep = repair_fleet(fleet, state, masks, round_to=8)
    assert (np.asarray(rep.x) == np.asarray(state.x)).all()
    assert (np.asarray(rep.phi) == np.asarray(state.phi)).all()


def _assert_no_dead_hosting(fleet, state, masks):
    hosts = np.asarray(state.hosts())
    for b, m in enumerate(masks):
        m = np.asarray(m)
        parts = np.asarray(fleet[b].apps.parts)
        for a in range(parts.size):
            hs = hosts[b, a, : int(parts[a])]
            assert (hs < m.size).all(), f"instance {b} app {a}: host on pad"
            assert (m[hs] > 0).all(), (
                f"instance {b} app {a}: live partition on dead node "
                f"(hosts {hs}, dead {np.flatnonzero(m == 0)})"
            )


def test_repair_evicts_and_cleans_phi():
    fleet = _small_fleet(3, seed=21)
    res = solve_fleet(fleet, keep_state=True, **SOLVE_KW)
    trace = generate_trace(fleet, 14, seed=3, node_failures=4,
                           link_degradations=2, flash_crowds=1)
    checked = 0
    for _, fired, healths in trace.timeline():
        if not fired:
            continue
        pairs = [apply_health(p, h) for p, h in zip(fleet, healths)]
        probs = [q for q, _ in pairs]
        masks = [m for _, m in pairs]
        rep = repair_fleet(probs, res.state, masks, round_to=8)
        _assert_no_dead_hosting(fleet, rep, masks)
        # No phi mass flows INTO a dead node after repair: forced stages are
        # rebuilt as shortest-path trees on the adj-gated metric, where any
        # hop into a dead node costs BIG.
        phi = np.asarray(rep.phi)
        for b, m in enumerate(masks):
            dead = np.flatnonzero(np.asarray(m) == 0)
            if dead.size:
                checked += 1
                assert phi[b][..., dead].sum() == 0.0
    assert checked > 0, "trace produced no dead-node epochs to check"


@settings(max_examples=15, deadline=None)
@given(
    seed=st.integers(min_value=0, max_value=2**16),
    n_fail=st.integers(min_value=0, max_value=5),
    n_deg=st.integers(min_value=0, max_value=3),
    n_crowd=st.integers(min_value=0, max_value=1),
)
def test_property_no_partition_on_masked_node(seed, n_fail, n_deg, n_crowd):
    """After ANY generated event sequence, repair leaves no live partition
    on a masked-out node, and the perturbed problems stay finite."""
    fleet, state = _property_fixture()
    trace = generate_trace(
        fleet, 10, seed=seed, node_failures=n_fail,
        link_degradations=n_deg, flash_crowds=n_crowd,
    )
    for _, fired, healths in trace.timeline():
        if not fired:
            continue
        pairs = [apply_health(p, h) for p, h in zip(fleet, healths)]
        probs = [q for q, _ in pairs]
        masks = [m for _, m in pairs]
        for q in probs:
            assert np.isfinite(np.asarray(q.net.mu)).all()
            assert np.isfinite(np.asarray(q.net.nu)).all()
            assert np.isfinite(np.asarray(q.apps.lam)).all()
        rep = repair_fleet(probs, state, masks, round_to=8)
        _assert_no_dead_hosting(fleet, rep, masks)


# ---------------------------------------------------------------------------
# Warm start
# ---------------------------------------------------------------------------
def test_warm_start_frozen_lane_returns_init_eval():
    fleet, _ = _property_fixture()
    cold = solve_fleet(fleet, keep_state=True, **SOLVE_KW)
    warm = solve_fleet(
        fleet, warm_start=cold.state,
        warm_active=np.zeros(len(fleet), bool), keep_state=True, **SOLVE_KW
    )
    # All lanes frozen: zero engine trips, state bitwise-carried, J is the
    # evaluation of the warm state itself.
    assert warm.rounds == 0
    assert (warm.iters == 0).all()
    assert (np.asarray(warm.state.x) == np.asarray(cold.state.x)).all()
    assert (np.asarray(warm.state.phi) == np.asarray(cold.state.phi)).all()
    np.testing.assert_allclose(warm.J, cold.J, rtol=1e-5)


@pytest.mark.parametrize("name", sorted(SCENARIOS))
def test_warm_start_parity_all_topologies(name):
    """Warm re-solving FROM the cold optimum must keep the objective within
    rtol 1e-5 of the cold solve on every paper topology — the warm path may
    only hold or improve J (best-iterate tracking), never lose it."""
    p = SCENARIOS[name]()
    cold = solve_fleet([p], m_max=6, t_phi=4, keep_state=True)
    frozen = solve_fleet(
        [p], m_max=6, t_phi=4, warm_start=cold.state,
        warm_active=np.array([False]),
    )
    np.testing.assert_allclose(frozen.J, cold.J, rtol=1e-5)
    active = solve_fleet(
        [p], m_max=6, t_phi=4, warm_start=cold.state,
        warm_active=np.array([True]),
    )
    assert np.isfinite(active.J).all()
    assert active.J[0] <= cold.J[0] * (1.0 + 1e-5)


def test_warm_start_shape_mismatch_raises():
    fleet, state = _property_fixture()
    with pytest.raises(ValueError, match="envelope"):
        solve_fleet(fleet[:2], warm_start=state, **SOLVE_KW)


def test_warm_start_guards():
    fleet, state = _property_fixture()
    with pytest.raises(ValueError, match="warm_active requires"):
        solve_fleet(fleet, warm_active=np.ones(3, bool), **SOLVE_KW)
    with pytest.raises(ValueError, match="CongUnaware"):
        solve_fleet(fleet, method="CongUnaware", warm_start=state, **SOLVE_KW)
    with pytest.raises(ValueError, match="single-chunk"):
        solve_fleet(fleet, warm_start=state, chunk_size=2, **SOLVE_KW)


# ---------------------------------------------------------------------------
# solve_fleet input validation + pad edge cases
# ---------------------------------------------------------------------------
def test_validation_rejects_nonfinite_and_dead():
    fleet = _small_fleet()
    lam = np.asarray(fleet[1].apps.lam).astype(np.float32).copy()
    lam[0] = np.nan
    bad = dataclasses.replace(
        fleet[1], apps=dataclasses.replace(fleet[1].apps, lam=lam)
    )
    with pytest.raises(ValueError, match="instance 1.*lam"):
        solve_fleet([fleet[0], bad], **SOLVE_KW)

    all_dead = dataclasses.replace(
        fleet[0],
        net=dataclasses.replace(
            fleet[0].net,
            nu=np.full(fleet[0].net.n_nodes, NU_PAD, np.float32),
        ),
    )
    with pytest.raises(ValueError, match="instance 0.*stage 0.*live-host"):
        solve_fleet([all_dead], **SOLVE_KW)

    nu = np.asarray(fleet[0].net.nu).astype(np.float32).copy()
    nu[int(np.asarray(fleet[0].apps.src)[0])] = NU_PAD
    dead_src = dataclasses.replace(
        fleet[0], net=dataclasses.replace(fleet[0].net, nu=nu)
    )
    with pytest.raises(ValueError, match="src node.*dead"):
        solve_fleet([dead_src], **SOLVE_KW)


def test_empty_fleet_typed_errors():
    with pytest.raises(EmptyFleetError):
        pad_batch_to_multiple([], 4)
    p = iot_hierarchy(seed=1, n_edge=3, devices_per_edge=2, n_apps=4)
    dead = dataclasses.replace(
        p,
        net=dataclasses.replace(
            p.net, nu=np.full(p.net.n_nodes, NU_PAD, np.float32)
        ),
    )
    with pytest.raises(EmptyFleetError, match="dead"):
        pad_batch_to_multiple([dead, dead], 4)
    with pytest.raises(EmptyFleetError):
        solve_fleet([], **SOLVE_KW)


# ---------------------------------------------------------------------------
# Controller
# ---------------------------------------------------------------------------
def test_controller_every_epoch_servable():
    from repro.launch.control import run_control

    fleet = _small_fleet(3, seed=31)
    ctl = run_control(
        fleet, epochs=6, seed=13, m_max=3, t_phi=3, round_to=8,
        trace_kwargs=dict(
            node_failures=2, link_degradations=1, flash_crowds=1
        ),
    )
    s = ctl.summary()
    assert s["epochs"] == 6
    assert s["feasible_fraction"] == 1.0
    assert s["nonfinite_epochs"] == 0
    assert s["solve_errors"] == 0
    # epoch 0 is the cold bootstrap; later epochs warm-start
    assert ctl.reports[0].mode == "cold"
    assert all(r.mode == "warm" for r in ctl.reports[1:])
    # event-free epochs freeze the whole batch: zero engine trips
    quiet = [r for r in ctl.reports[1:] if r.perturbed == 0]
    assert all(r.rounds == 0 for r in quiet)


def _fail_first_solve(monkeypatch):
    """Make the controller's first `solve_fleet` call raise."""
    from repro.launch import control

    real = control.solve_fleet
    calls = []

    def flaky(probs, **kw):
        calls.append(kw.get("method"))
        if len(calls) == 1:
            raise RuntimeError("injected solve fault")
        return real(probs, **kw)

    monkeypatch.setattr(control, "solve_fleet", flaky)
    return calls


def test_controller_counts_raised_solves(monkeypatch):
    """A solve that raises is absorbed by the ladder (the next rung serves
    the epoch) but counted in the epoch's report and the summary."""
    from repro.launch.control import run_control

    calls = _fail_first_solve(monkeypatch)
    ctl = run_control(
        _small_fleet(2, seed=31), epochs=4, seed=13, m_max=2, t_phi=2,
        round_to=8,
        trace_kwargs=dict(node_failures=1, link_degradations=0, flash_crowds=0),
    )
    assert calls[:2] == ["ALT", "CoLocated"]  # epoch 0: cold raised
    assert [r.solve_errors for r in ctl.reports] == [1, 0, 0, 0]
    assert ctl.reports[0].outcome == "colocated"
    s = ctl.summary()
    assert s["solve_errors"] == 1
    assert s["feasible_fraction"] == 1.0


def test_controller_cli_fails_on_raised_solve(monkeypatch):
    from repro.launch.control import main

    _fail_first_solve(monkeypatch)
    rc = main([
        "--instances", "2", "--epochs", "4", "--seed", "4",
        "--node-failures", "0", "--link-degradations", "0",
        "--flash-crowds", "0", "--m-max", "2", "--t-phi", "2",
        "--assert-feasible",
    ])
    assert rc == 1


def test_controller_cli_smoke(tmp_path):
    from repro.launch.control import main

    out = tmp_path / "control.json"
    events = tmp_path / "events.json"
    rc = main([
        "--instances", "2", "--epochs", "5", "--seed", "4",
        "--node-failures", "1", "--link-degradations", "1",
        "--flash-crowds", "0", "--m-max", "2", "--t-phi", "2",
        "--json-out", str(out), "--events-out", str(events),
        "--assert-feasible",
    ])
    assert rc == 0
    payload = json.loads(out.read_text())
    assert payload["summary"]["feasible_fraction"] == 1.0
    assert payload["summary"]["solve_errors"] == 0
    assert len(payload["epochs"]) == 5
    sched = json.loads(events.read_text())
    assert sched["counts"]["node_down"] == 1
