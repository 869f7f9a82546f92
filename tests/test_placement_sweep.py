"""Blocked placement sweep (ISSUE 10, DESIGN.md section 18).

The sweep refactor made `placement_update`'s app scan a blocked schedule:
per block of `block_apps` apps the score-row ingredients are precomputed
batched, while the decisions stay a serial, conflict-exact walk. What is
pinned here:

  * bitwise invariance — `blocked_placement_update` (the blocked code path
    forced at ANY block size, including 1) reproduces the verbatim
    pre-refactor sequential scan bit-for-bit on all four paper topologies,
    both chained and colocated, and on mixed-partition / stage-padded
    instances. The oracle below is the deleted `lax.scan` implementation,
    kept verbatim;
  * end-of-solve parity — `solve_alt` / `solve_colocated` land on the SAME
    solution for block_apps in {1, 4, 0}: J within rtol 1e-5 (the ISSUE
    bar; measured equal to the bit) and identical hosts/iteration counts;
  * decision certificates — every committed move in `blocked_sweep_cert`
    carries `S_new < (1 - move_margin) * S_old` under its decision context,
    and unmoved partitions score unchanged (hypothesis property over random
    connected instances + deterministic anchors);
  * lane_chunk — the engine's round-body layout knob is bitwise-inert
    unsharded (also on a fleet padded like the benchmark's paper4 cell),
    `lane_chunk=None` resolves by platform (fused on a TPU or a mesh,
    lane-major on a CPU), and `solve_fleet` rejects a nonzero lane_chunk
    combined with a committed mesh (the guard only fires when a mesh
    actually commits, so that test runs under the simulated 8-device CPU
    mesh like tests/test_sharded_fleet.py);
  * Apsp0Cache — `repair_fleet` with a cached zero-load APSP is bitwise the
    uncached path, and `refresh_apsp0` hits exactly when (adj, mu, cost)
    are value-identical.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _optional_deps import given, settings, st

from repro.chaos import refresh_apsp0, repair_fleet
from repro.core import (
    SCENARIOS,
    State,
    blocked_placement_update,
    blocked_sweep_cert,
    forwarding_update,
    placement_update,
    random_connected,
    solve_alt,
    solve_colocated,
    structured_init,
)
from repro.core.placement import repair_phi
from repro.core.marginals import cost_to_go
from repro.core.structs import one_hot
from repro.fleet import load_grid, pad_problem_parts, sample_fleet, solve_fleet
from repro.fleet.solve import _auto_lane_chunk
from repro.obs import trace as obs_trace
from repro.kernels.minplus import apsp_with_nexthop

N_DEV = len(jax.devices())
needs_mesh = pytest.mark.skipif(
    N_DEV < 2,
    reason="needs >= 2 devices; run under "
    "XLA_FLAGS=--xla_force_host_platform_device_count=8",
)

MOVE_MARGIN = 0.02
BLOCKS = (1, 4, 0)  # sequential-size, mid block, one block over all apps


# ===========================================================================
# PRE-REFACTOR ORACLE — the deleted sequential `lax.scan` placement sweep,
# kept verbatim. Only the removal of the jit decorator and the explicit
# imports differ from the deleted source; every arithmetic expression,
# scan order, and the hysteresis pick are untouched. (`cost_to_go`,
# `apsp_with_nexthop` and `repair_phi` are unchanged by the refactor, so
# calling the production versions is exactly the deleted code's behavior.)
# ===========================================================================
def oracle_placement_update(
    problem, state, ctg=None, *, colocate=False, move_margin=0.02,
    solver="neumann",
):
    n = problem.net.n_nodes
    apps = problem.apps
    n_parts = apps.n_parts
    if ctg is None:
        ctg = cost_to_go(problem, state, solver=solver)
    q, dp, kappa, t, F, G = ctg
    dist, nexthop = apsp_with_nexthop(dp)

    hosts = state.hosts()  # [A, P]
    cm = problem.cost
    nu = problem.net.nu
    p_idx = jnp.arange(n_parts)

    from repro.core import costs as _costs

    def cprime(Gv):
        return cm.w_comp * _costs.comp_cost_prime(Gv, nu, cm)

    def body(Gv, inputs):
        (src_a, dst_a, h_old, lam_a, L_a, w_a, parts_a) = inputs
        loads_a = w_a * lam_a  # [P]
        live = p_idx < parts_a  # [P]
        # Remove this app's own loads so kappa is the marginal of adding it
        # (sequentially, in partition order — phantom loads are exact zeros).
        def remove(g, pin):
            h_p, load_p = pin
            return g - load_p * jax.nn.one_hot(h_p, n), None

        Gv, _ = jax.lax.scan(remove, Gv, (h_old, loads_a))

        def pick(S, h_prev):
            cand = jnp.argmin(S).astype(jnp.int32)
            better = S[cand] < (1.0 - move_margin) * S[h_prev]
            return jnp.where(better, cand, h_prev).astype(jnp.int32)

        if colocate:
            w_tot = jnp.sum(jnp.where(live, w_a, 0.0))
            load_tot = jnp.sum(jnp.where(live, loads_a, 0.0))
            L_fin = L_a[parts_a]
            S = (
                L_a[0] * dist[src_a, :]
                + w_tot * cprime(Gv)
                + L_fin * dist[:, dst_a]
            )
            h = pick(S, h_old[0])
            h_new = jnp.where(live, h, h_old)
            Gv = Gv + load_tot * jax.nn.one_hot(h, n)
            return Gv, h_new

        down = jnp.where(
            p_idx + 1 < parts_a,
            jnp.concatenate([h_old[1:], dst_a[None]]),
            dst_a,
        )  # [P]

        def step(carry, pin):
            g, up = carry
            live_p, h_old_p, down_p, L_up, L_dn, w_p, load_p = pin
            S = L_up * dist[up, :] + w_p * cprime(g) + L_dn * dist[:, down_p]
            h = jnp.where(live_p, pick(S, h_old_p), h_old_p)
            g = g + jnp.where(live_p, load_p, 0.0) * jax.nn.one_hot(h, n)
            return (g, h), h

        (Gv, _), h_new = jax.lax.scan(
            step,
            (Gv, src_a),
            (live, h_old, down, L_a[:-1], L_a[1:], w_a, loads_a),
        )
        return Gv, h_new

    _, hosts_new = jax.lax.scan(
        body,
        G,
        (apps.src, apps.dst, hosts, apps.lam, apps.L, apps.w, apps.parts),
    )

    x_new = one_hot(hosts_new, n)  # [A, P, V]
    new_state = State(x=x_new, phi=state.phi)
    return repair_phi(problem, state, new_state, nexthop)


def _sweep_state(problem):
    """Mid-solve state with congested routing, like the ALT loop's rounds:
    init, then a few forwarding sweeps so the marginals are not zero-load."""
    return forwarding_update(problem, structured_init(problem), t_phi=4)


# ---------------------------------------------------------------------------
# Bitwise invariance: blocked algorithm == verbatim sequential oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", list(SCENARIOS))
class TestBlockedSweepBitwise:
    @pytest.mark.parametrize("bk", BLOCKS)
    def test_matches_oracle(self, name, bk):
        p = SCENARIOS[name]()
        s = _sweep_state(p)
        ref = oracle_placement_update(p, s)
        got = blocked_placement_update(p, s, block_apps=bk)
        np.testing.assert_array_equal(
            np.asarray(got.hosts()), np.asarray(ref.hosts())
        )
        # phi goes through the identical repair_phi; the oracle chain is
        # unjitted, so the routing tensors get the fusion-tolerance budget.
        np.testing.assert_allclose(
            np.asarray(got.phi), np.asarray(ref.phi), rtol=1e-6, atol=1e-6
        )

    @pytest.mark.parametrize("bk", BLOCKS)
    def test_matches_oracle_colocated(self, name, bk):
        p = SCENARIOS[name]()
        s = _sweep_state(p)
        ref = oracle_placement_update(p, s, colocate=True)
        got = blocked_placement_update(p, s, colocate=True, block_apps=bk)
        np.testing.assert_array_equal(
            np.asarray(got.hosts()), np.asarray(ref.hosts())
        )

    def test_production_dispatch_bitwise(self, name):
        """The jitted production entry at every block size returns the SAME
        BITS as its own block_apps=1 dispatch — full state, not just hosts
        (both sides jitted, so no fusion budget is needed or granted)."""
        p = SCENARIOS[name]()
        s = _sweep_state(p)
        base = placement_update(p, s)  # dispatches the sequential scan
        for bk in BLOCKS:
            got = blocked_placement_update(p, s, block_apps=bk)
            np.testing.assert_array_equal(np.asarray(got.x), np.asarray(base.x))
            np.testing.assert_array_equal(
                np.asarray(got.phi), np.asarray(base.phi)
            )


class TestBlockedSweepMixedPartitions:
    def test_stage_padded_instance_bitwise(self):
        """Phantom partitions (DESIGN.md section 13) stay inert through the
        blocked schedule: the padded instance's real hosts match the
        unpadded sweep at every block size."""
        p = SCENARIOS["iot"]()
        padded = pad_problem_parts(p, 4)
        s = _sweep_state(p)
        sp = _sweep_state(padded)
        base = placement_update(p, s)
        for bk in BLOCKS:
            got = blocked_placement_update(padded, sp, block_apps=bk)
            np.testing.assert_array_equal(
                np.asarray(got.hosts())[:, :2], np.asarray(base.hosts())
            )

    def test_mixed_p_fleet_instances_bitwise(self):
        """Sampled instances across split depths P = 1..3: blocked == the
        production sequential dispatch on each, bit for bit."""
        for p in sample_fleet(3, seed=21, partitions=(1, 2, 3)):
            s = _sweep_state(p)
            base = placement_update(p, s)
            got = blocked_placement_update(p, s, block_apps=4)
            np.testing.assert_array_equal(
                np.asarray(got.x), np.asarray(base.x)
            )

    def test_block_larger_than_fleet_clamps(self):
        """block_apps beyond the app count behaves as one all-apps block."""
        p = SCENARIOS["iot"]()
        s = _sweep_state(p)
        a = p.apps.n_apps
        big = blocked_placement_update(p, s, block_apps=a + 100)
        one = blocked_placement_update(p, s, block_apps=0)
        np.testing.assert_array_equal(np.asarray(big.x), np.asarray(one.x))

    def test_negative_block_rejected(self):
        p = SCENARIOS["iot"]()
        s = _sweep_state(p)
        with pytest.raises(ValueError, match="block_apps"):
            placement_update(p, s, block_apps=-1)


# ---------------------------------------------------------------------------
# End-of-solve parity: the ALT loop lands on the same solution at any block
# ---------------------------------------------------------------------------
SOLVE_KW = dict(m_max=4, t_phi=3, alpha=0.5, tol=1e-3, patience=3)


@pytest.mark.parametrize("name", list(SCENARIOS))
class TestEndOfSolveParity:
    def test_solve_alt_block_invariant(self, name):
        p = SCENARIOS[name]()
        base = solve_alt(p, block_apps=1, **SOLVE_KW)
        for bk in (4, 0):
            got = solve_alt(p, block_apps=bk, **SOLVE_KW)
            np.testing.assert_allclose(got.J, base.J, rtol=1e-5)
            np.testing.assert_allclose(got.history, base.history, rtol=1e-5)
            assert got.iters == base.iters
            np.testing.assert_array_equal(
                np.asarray(got.state.x), np.asarray(base.state.x)
            )

    def test_solve_colocated_block_invariant(self, name):
        p = SCENARIOS[name]()
        base = solve_colocated(p, block_apps=1, **SOLVE_KW)
        got = solve_colocated(p, block_apps=0, **SOLVE_KW)
        np.testing.assert_allclose(got.J, base.J, rtol=1e-5)
        assert got.iters == base.iters


# ---------------------------------------------------------------------------
# Decision certificates: every committed move beats the hysteresis margin
# ---------------------------------------------------------------------------
def _check_cert(cert):
    s_new = np.asarray(cert["S_new"], np.float64)
    s_old = np.asarray(cert["S_old"], np.float64)
    h_old = np.asarray(cert["h_old"])
    h_fin = np.asarray(cert["h_fin"])
    moved_hosts = h_old != h_fin
    np.testing.assert_array_equal(np.asarray(cert["moved"]), moved_hosts)
    # Colocated certs carry ONE joint decision column. The margin property
    # covers the DECISION (joint host vs the kept partition-0 host), not the
    # first-sweep collapse of a not-yet-colocated chain onto the kept host —
    # that pulls partitions 1.. to partition 0's host with no score change.
    if s_new.shape != moved_hosts.shape:
        moved = moved_hosts[:, :1]
    else:
        moved = moved_hosts
    assert np.all(s_new[moved] < (1.0 - MOVE_MARGIN) * s_old[moved]), (
        "a committed move does not beat the hysteresis margin under its "
        "own decision context"
    )
    # Unmoved partitions were scored at their old host: no phantom gains.
    np.testing.assert_array_equal(s_new[~moved], s_old[~moved])


class TestSweepCert:
    @pytest.mark.parametrize("name", list(SCENARIOS))
    @pytest.mark.parametrize("colocate", [False, True])
    def test_cert_margin_holds(self, name, colocate):
        p = SCENARIOS[name]()
        s = _sweep_state(p)
        cert = blocked_sweep_cert(p, s, colocate=colocate, block_apps=4)
        _check_cert(cert)

    def test_cert_hosts_match_update(self):
        p = SCENARIOS["mesh"]()
        s = _sweep_state(p)
        cert = blocked_sweep_cert(p, s, block_apps=4)
        got = blocked_placement_update(p, s, block_apps=4)
        np.testing.assert_array_equal(
            np.asarray(cert["h_fin"]), np.asarray(got.hosts())
        )
        assert int(cert["block"]) == 4

    @settings(max_examples=8, deadline=None)
    @given(
        # Fixed (V, A) so every draw reuses the compiled programs; the
        # property varies the instance and the block size.
        seed=st.integers(min_value=0, max_value=31),
        bk=st.sampled_from([2, 3, 5]),
        colocate=st.booleans(),
    )
    def test_property_cert_and_bitwise(self, seed, bk, colocate):
        """For any random connected instance and block size: the margin
        certificate holds AND the blocked sweep is bitwise the sequential
        production dispatch."""
        p = random_connected(12, 5, seed=seed)
        s = _sweep_state(p)
        cert = blocked_sweep_cert(p, s, colocate=colocate, block_apps=bk)
        _check_cert(cert)
        base = placement_update(p, s, colocate=colocate)
        got = blocked_placement_update(p, s, colocate=colocate, block_apps=bk)
        np.testing.assert_array_equal(np.asarray(got.x), np.asarray(base.x))
        np.testing.assert_array_equal(np.asarray(got.phi), np.asarray(base.phi))


# ---------------------------------------------------------------------------
# lane_chunk: round-body layout is bitwise-inert; mesh combination rejected
# ---------------------------------------------------------------------------
FLEET_KW = dict(m_max=3, t_phi=3, alpha=0.5, tol=1e-3, patience=4)


def _fleet():
    return [
        SCENARIOS["iot"](),
        random_connected(12, 5, seed=3),
        random_connected(14, 6, seed=4),
        random_connected(11, 4, seed=5),
    ]


def _paper4_fleet():
    """The paper's four topologies at a low and a high load, padded
    together as in the benchmark's paper4 cell: V 17-30, A 20-40."""
    names = ("iot", "mesh", "smallworld", "geant")
    return [p for n in names for p in load_grid(SCENARIOS[n], (0.4, 1.2))]


class TestLaneChunk:
    @pytest.mark.parametrize(
        "platform, meshed, expected",
        [("tpu", False, "fused"), ("cpu", False, "lane_major"),
         ("cpu", True, "fused")],
    )
    def test_lane_chunk_auto_resolves_by_platform(
        self, platform, meshed, expected, monkeypatch
    ):
        """lane_chunk=None takes the fused batch on a TPU and under a
        mesh, lane-major on a CPU; the unsharded cases run through
        solve_fleet with the platform probe patched and read the decision
        off the `solve_fleet.execute` span."""
        mesh = object() if meshed else None
        lane_chunk = _auto_lane_chunk(mesh, platform)
        assert lane_chunk == {"fused": 0, "lane_major": 1}[expected]
        if meshed:
            return  # the solve itself: test_lane_chunk_auto_resolves_fused_on_mesh
        monkeypatch.setattr(jax, "default_backend", lambda: platform)
        obs_trace.configure(enabled=True)
        obs_trace.reset()
        try:
            solve_fleet(_fleet(), **FLEET_KW)
            (execute,) = [
                e for e in obs_trace.TRACER.events()
                if e.name == "solve_fleet.execute"
            ]
        finally:
            obs_trace.configure(enabled=False)
            obs_trace.reset()
        assert execute.attrs["layout"] == expected

    @pytest.mark.parametrize("lane_chunk", [0, 3])
    def test_paper4_fleet_bitwise_across_layouts(self, lane_chunk):
        """A heterogeneous paper4-like fleet whose lanes freeze at
        different rounds gives per-lane J, hosts, iters and history
        bitwise-equal to lane-major: the fused lockstep trips ride the
        frozen lanes masked."""
        fleet = _paper4_fleet()
        kw = dict(FLEET_KW, m_max=6, patience=2)
        base = solve_fleet(fleet, lane_chunk=1, **kw)
        assert len(set(base.iters.tolist())) > 1
        got = solve_fleet(fleet, lane_chunk=lane_chunk, **kw)
        np.testing.assert_array_equal(got.J, base.J)
        np.testing.assert_array_equal(got.hosts, base.hosts)
        np.testing.assert_array_equal(got.iters, base.iters)
        np.testing.assert_array_equal(got.history, base.history)

    def test_lane_chunk_bitwise_inert(self):
        fleet = _fleet()
        base = solve_fleet(fleet, lane_chunk=1, **FLEET_KW)
        for lc in (0, 3):
            got = solve_fleet(fleet, lane_chunk=lc, **FLEET_KW)
            np.testing.assert_array_equal(got.J, base.J)
            np.testing.assert_array_equal(got.hosts, base.hosts)
            np.testing.assert_array_equal(got.history, base.history)
            np.testing.assert_array_equal(got.iters, base.iters)

    def test_block_apps_threads_through_fleet(self):
        fleet = _fleet()
        base = solve_fleet(fleet, block_apps=1, **FLEET_KW)
        got = solve_fleet(fleet, block_apps=4, **FLEET_KW)
        np.testing.assert_allclose(got.J, base.J, rtol=1e-5)
        np.testing.assert_array_equal(got.hosts, base.hosts)

    @needs_mesh
    def test_lane_chunk_with_mesh_rejected(self):
        """A nonzero lane_chunk breaks the instance-axis sharding, so a
        committed mesh must reject it loudly. The guard fires only when a
        mesh actually commits — a single-device host falls back unsharded
        (with a warning) before the check, hence the mesh marker."""
        fleet = _fleet() * 2  # 8 instances over the 8 simulated devices
        with pytest.raises(ValueError, match="lane_chunk"):
            solve_fleet(fleet, shard=True, lane_chunk=2, **FLEET_KW)

    @needs_mesh
    def test_lane_chunk_auto_resolves_fused_on_mesh(self):
        """lane_chunk=None under a committed mesh resolves to the fused
        layout and solves; explicit 0 is equally accepted."""
        fleet = _fleet() * 2
        res_auto = solve_fleet(fleet, shard=True, **FLEET_KW)
        res_zero = solve_fleet(fleet, shard=True, lane_chunk=0, **FLEET_KW)
        assert res_auto.shard.sharded and res_zero.shard.sharded
        np.testing.assert_array_equal(res_auto.J, res_zero.J)


# ---------------------------------------------------------------------------
# Apsp0Cache: cached zero-load APSP is bitwise the uncached repair path
# ---------------------------------------------------------------------------
class TestApsp0Cache:
    def test_miss_then_hit_then_invalidate(self):
        probs = _fleet()
        c1 = refresh_apsp0(probs, None)
        assert not c1.reused and c1.misses == 1 and c1.hits == 0
        c2 = refresh_apsp0(probs, c1)
        assert c2 is c1 and c2.reused and c2.hits == 1
        # A different topology invalidates by value: miss, counters carry.
        other = probs[:-1] + [random_connected(11, 4, seed=99)]
        c3 = refresh_apsp0(other, c2)
        assert not c3.reused and c3.misses == 2 and c3.hits == 1

    def test_repair_with_cache_bitwise(self):
        probs = _fleet()
        res = solve_fleet(probs, keep_state=True, **FLEET_KW)
        masks = [np.ones(p.net.n_nodes, np.float32) for p in probs]
        masks[0][int(np.asarray(res.hosts)[0, 0, 0])] = 0.0  # kill a host
        cache = refresh_apsp0(probs, None)
        cold = repair_fleet(probs, res.state, masks)
        warm = repair_fleet(probs, res.state, masks, apsp0=cache)
        np.testing.assert_array_equal(np.asarray(warm.x), np.asarray(cold.x))
        np.testing.assert_array_equal(
            np.asarray(warm.phi), np.asarray(cold.phi)
        )

    def test_cache_shapes_cover_envelope(self):
        probs = _fleet()
        cache = refresh_apsp0(probs, None)
        v_env = max(p.net.n_nodes for p in probs)
        assert cache.dist.shape == (len(probs), v_env, v_env)
        assert cache.nexthop.shape == (len(probs), v_env, v_env)
        d, nh = cache.sp()
        assert d.shape == cache.dist.shape and nh.dtype == jnp.int32
