"""Batched multi-scenario ALT solving over padded problem ensembles.

`solve_fleet` pads a heterogeneous list of `Problem`s to a common (V, A)
envelope (fleet/pad.py), stacks them into a single pytree, and hands the
stack to the shared device-resident round engine (core/engine.py): the whole
ALT pipeline — structured init, placement reassignment, forwarding sweeps,
objective, best-iterate/stall/freeze bookkeeping — runs as ONE jitted
program over the instance axis (a lockstep while_loop vmapped over lanes
on a TPU or a committed mesh, lane-major `lax.map` chunks on a CPU — see
`lane_chunk`). There is no fleet-local copy of the loop body any more; the
sequential solvers in core/alt.py run the exact same engine at B=1, so the
two paths share every future fix.

Equivalence contract: for every instance, the returned J matches the
sequential `solve_alt` on the unpadded problem (same m_max / t_phi / alpha /
tol / patience / solver) up to float32 rounding — trivially so, since both
run the same compiled loop. Early stopping is per-instance freeze masking
inside the engine; on top of that, the while_loop predicate ("any live
instance below m_max") exits the whole batch early once every instance has
stalled, instead of burning all `m_max` rounds like the old fixed-length
scan (`FleetResult.rounds` records the trips actually executed).

Scaling hooks (DESIGN.md sections 9-12):

  * `shard=True` runs the engine over a real instance-axis mesh: the stacked
    batch is committed to `NamedSharding(mesh, P("fleet"))`, padded up to a
    device multiple with inert repeats when it doesn't divide (trimmed on
    gather), and the engine outputs are verified to still carry the fleet
    layout. Every layout decision is explicit: `FleetResult.shard` records
    what happened (`ShardPlan`), and a fallback (single device) is logged —
    never silent. `devices=` caps the mesh to the first N local devices.
  * `chunk_size=B` splits very large ensembles into fixed-B chunks that all
    pad to the *global* (V, A) envelope and unified hop bound, so arbitrary
    fleet sizes reuse ONE compiled program per (V, A, B) signature instead
    of compiling one giant batch. Each chunk early-exits independently.
  * `envelope_cap_gb=G` bounds the per-device footprint of the engine's
    phi-shaped `[B, A, K, V, V]` buffers by auto-capping the chunk size for
    the (V, A) tier at hand — `chunk_size` alone caps B globally but not
    the per-device envelope, which is what blows up first at V >= 512.

Observability (DESIGN.md section 14): `trace=True` (default) carries the
engine's on-device round trace through the gather as `FleetResult.trace`
(per-round J split, placement churn, live mask, best-round index — same
NaN-past-freeze contract as `history`); the host-side stack/commit/execute/
gather boundaries are bracketed by `obs.trace` spans (execute split into
dispatch and, when tracing, the wait for the device), and per-solve
telemetry (chunks, pad overhead, rounds vs budget, warm/cold compiles)
lands in `obs.metrics.registry`.
"""
from __future__ import annotations

import dataclasses
import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np

from ..core.alt import ALL_METHODS, linearize, method_kwargs
from ..core.engine import engine_solve
from ..core.flow import objective
from ..core.placement import structured_init
from ..core.structs import Problem, State
from ..distributed.sharding import carries_fleet_sharding, shard_fleet
from ..obs.metrics import registry as obs_registry
from ..obs.roundtrace import FleetTrace
from ..obs.trace import span, tracer_enabled
from .pad import (
    NU_PAD,
    fleet_envelope,
    fleet_part_envelope,
    stack_problems,
    unify_hop_bound,
)

METHODS = ("ALT", "OneShot", "CongUnaware", "CoLocated")

logger = logging.getLogger("repro.fleet")

# Static accounting for the envelope cap: how many phi-shaped [A, K, V, V]
# float32 buffers one engine lane keeps alive at the round-body peak —
# carry.state + carry.best_state + the round-local next iterate, the
# placement sweep's delta tensor, and headroom for the forwarding sweeps'
# XLA temporaries. Deliberately conservative: the cap is a guard rail, not
# an allocator.
_PHI_COPIES = 8

# Process-local approximation of XLA's compile cache, keyed on what actually
# decides the compiled program: padded shapes, hop bound, device count, and
# the static solve kwargs. Drives the fleet.compile.{cold,warm} counters; it
# can undercount colds after `jax.clear_caches()` (we never see that), which
# the metrics consumers accept as the cost of staying sync-free.
_COMPILE_CACHE_KEYS: set = set()


def _validate_problems(problems) -> None:
    """Reject inputs that would push NaN/inf through the fixed point.

    The quadratic cost extension keeps J *finite* past rho_max, but a
    non-finite rate or capacity anywhere poisons every downstream reduction
    silently — by the time the caller sees J = NaN the provenance is gone.
    Checks are host-side numpy over the raw (unpadded) instances, so error
    messages can name the instance/app/stage; `solve_fleet(validate=False)`
    skips them for hot inner loops that re-solve already-validated fleets.

    A node with nu <= NU_PAD is DEAD under the §9/§15 encoding (padding and
    chaos both use it), so "the live-host set is empty" and "a live app's
    endpoint is dead" are both input errors here, not solver NaNs later.
    """
    for i, p in enumerate(problems):
        arrays = {
            "adj": np.asarray(p.net.adj),
            "mu": np.asarray(p.net.mu),
            "nu": np.asarray(p.net.nu),
            "lam": np.asarray(p.apps.lam),
            "L": np.asarray(p.apps.L),
            "w": np.asarray(p.apps.w),
        }
        for name, arr in arrays.items():
            if not np.isfinite(arr).all():
                raise ValueError(
                    f"solve_fleet: instance {i}: non-finite values in "
                    f"{name!r} — refusing to propagate NaN/inf through the "
                    "traffic fixed point"
                )
        if (arrays["lam"] < 0).any():
            raise ValueError(
                f"solve_fleet: instance {i}: negative arrival rate lam"
            )
        if (arrays["mu"] <= 0).any():
            raise ValueError(
                f"solve_fleet: instance {i}: non-positive link rate mu"
            )
        if (arrays["nu"] <= 0).any():
            raise ValueError(
                f"solve_fleet: instance {i}: non-positive compute rate nu"
            )
        live = arrays["nu"] > NU_PAD
        lam = arrays["lam"]
        if not live.any():
            a = int(np.argmax(lam > 0)) if (lam > 0).any() else 0
            raise ValueError(
                f"solve_fleet: instance {i}, app {a}, stage 0: live-host "
                f"set is empty — all {live.size} nodes are dead "
                f"(nu <= NU_PAD = {NU_PAD:g}), no node can host any stage"
            )
        src = np.asarray(p.apps.src)
        dst = np.asarray(p.apps.dst)
        for a in np.flatnonzero(lam > 0):
            for role, node in (("src", int(src[a])), ("dst", int(dst[a]))):
                if not live[node]:
                    raise ValueError(
                        f"solve_fleet: instance {i}, app {int(a)}: {role} "
                        f"node {node} is dead — its traffic cannot be "
                        + ("injected" if role == "src" else "absorbed")
                    )


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """Explicit record of one solve's instance-axis layout decision.

    The pre-PR-4 `shard=True` was a `device_put` hint that silently no-oped
    whenever the batch didn't divide the device count or only one device was
    visible. Layout is now always an explicit decision: `reason` says what
    was chosen and why, `solve_fleet` logs any fallback, and the plan rides
    on `FleetResult.shard` so callers (CLI, benchmarks, tests) can assert on
    it instead of guessing from timings.

    requested      : the caller passed shard=True
    n_devices      : devices in the fleet mesh actually used (1 = unsharded)
    batch          : real instances handed to solve_fleet
    padded_batch   : engine lanes actually run, summed over chunks (>= batch;
                     the excess is inert repeats, trimmed on gather)
    reason         : "sharded" | "single-device" | "not-requested"
    output_sharded : every chunk's engine outputs were verified to carry the
                     fleet NamedSharding (False whenever n_devices == 1, or
                     on the fallback a silent layout change used to hide)
    """

    requested: bool
    n_devices: int = 1
    batch: int = 0
    padded_batch: int = 0
    reason: str = "not-requested"
    output_sharded: bool = False

    @property
    def sharded(self) -> bool:
        return self.n_devices > 1

    def describe(self) -> str:
        """One-liner for summaries/CLIs: devices, lane padding, reason."""
        return (
            f"{self.n_devices}dev B={self.batch}->{self.padded_batch} "
            f"{self.reason}"
        )


@dataclasses.dataclass
class FleetResult:
    """Per-instance results of one batched fleet solve.

    J / J_comm / J_comp : [B] final (best-iterate) objective values
    history             : [B, m_max + 1] outer-iteration J trace; entries
                          after an instance froze are NaN
    iters               : [B] outer iterations actually applied per instance
    rounds              : outer while_loop trips actually executed (max over
                          chunks); < m_max whenever every instance froze early
    hosts               : [B, A, P] chosen partition hosts over the fleet's
                          partition envelope (padded apps and phantom
                          partitions hold meaningless-but-harmless indices)
    parts               : [B, A] effective per-app partition counts (phantom
                          partitions past these are padding)
    node_mask/app_mask  : [B, V] / [B, A] validity masks from padding
    shard               : the instance-axis layout decision (`ShardPlan`)
    m_max               : the effective round budget this solve ran under
                          (0 for CongUnaware, 1 for OneShot) — lets
                          `summary()` report "rounds executed vs budget"
    trace               : host-side `FleetTrace` of the engine's on-device
                          round diagnostics (None when trace=False or for
                          the zero-iteration CongUnaware baseline)
    state               : the solved stacked `State` over the fleet envelope
                          (device arrays, pad lanes trimmed) when the caller
                          passed `keep_state=True`; the warm-start currency
                          — feed it back as `solve_fleet(warm_start=...)`
                          next epoch. None by default: the [B, A, K, V, V]
                          phi buffers are too big to keep alive casually.
    """

    method: str
    J: np.ndarray
    J_comm: np.ndarray
    J_comp: np.ndarray
    history: np.ndarray
    iters: np.ndarray
    rounds: int
    hosts: np.ndarray
    parts: np.ndarray
    node_mask: np.ndarray
    app_mask: np.ndarray
    shard: ShardPlan = dataclasses.field(
        default_factory=lambda: ShardPlan(requested=False)
    )
    m_max: int = 0
    trace: FleetTrace | None = None
    state: State | None = None

    @property
    def n_instances(self) -> int:
        return int(self.J.shape[0])

    def per_instance(self) -> list[dict]:
        out = []
        for b in range(self.n_instances):
            hist = self.history[b]
            n_real = int(self.node_mask[b].sum())
            real = self.app_mask[b] > 0
            hosts = self.hosts[b][real]
            parts = self.parts[b][real].astype(int)
            # Only the real partitions of real apps count: phantom-partition
            # hosts are padding, trimmed before the leak check below.
            real_hosts = [h[:pa] for h, pa in zip(hosts, parts)]
            # Padded-envelope indices must never leak to consumers: a host
            # beyond the real-node block would be a solver bug (padded
            # nodes carry a prohibitive marginal compute cost), so flag it
            # and clamp into the valid range either way.
            leaked = int(sum(np.sum(h >= n_real) for h in real_hosts))
            row = {
                "J": float(self.J[b]),
                "J_comm": float(self.J_comm[b]),
                "J_comp": float(self.J_comp[b]),
                "history": [float(h) for h in hist[~np.isnan(hist)]],
                "iters": int(self.iters[b]),
                "hosts": [
                    np.minimum(h, n_real - 1).tolist() for h in real_hosts
                ],
                # The instance's split depth(s): one int when uniform, else
                # the per-app list (heterogeneous per-app splits are legal).
                "partitions": (
                    int(parts[0]) if len(set(parts.tolist())) <= 1
                    else parts.tolist()
                ),
            }
            if leaked:
                row["padded_host_leaks"] = leaked
            out.append(row)
        return out

    def summary(self) -> str:
        rounds = f"rounds={self.rounds}"
        if self.m_max:
            tag = " early-exit" if self.rounds < self.m_max else ""
            rounds = f"rounds={self.rounds}/{self.m_max}{tag}"
        churn = (
            f"  churn={self.trace.mean_churn():.2f}/round"
            if self.trace is not None else ""
        )
        return (
            f"fleet[{self.method}] B={self.n_instances} "
            f"J: min={self.J.min():.3f} med={np.median(self.J):.3f} "
            f"max={self.J.max():.3f}  iters: {self.iters.min()}-{self.iters.max()}"
            f"  {rounds}{churn}  shard[{self.shard.describe()}]"
        )


def _solve_one_congunaware(
    problem: Problem, *, use_pallas: bool, interpret: bool, solver: str
) -> dict:
    """Zero-iteration baseline: linear-cost init scored under true costs."""
    state = structured_init(
        linearize(problem), use_pallas=use_pallas, interpret=interpret
    )
    J, aux = objective(
        problem, state, solver=solver, use_pallas=use_pallas,
        interpret=interpret,
    )
    return {
        "J": J,
        "J_comm": aux["J_comm"],
        "J_comp": aux["J_comp"],
        "hosts": state.hosts(),
        "history": J[None],
        "iters": jnp.int32(0),
    }


@functools.partial(jax.jit, static_argnames=("use_pallas", "interpret", "solver"))
def _solve_fleet_congunaware(
    stacked: Problem, *, use_pallas: bool, interpret: bool, solver: str
):
    return jax.vmap(
        functools.partial(
            _solve_one_congunaware, use_pallas=use_pallas,
            interpret=interpret, solver=solver,
        )
    )(stacked)


def _solve_fleet_stacked(
    stacked: Problem,
    *,
    method: str,
    m_max: int,
    t_phi: int,
    alpha: float,
    tol: float,
    patience: int,
    use_pallas: bool,
    solver: str,
    interpret: bool = False,
    trace: bool = True,
    keep_state: bool = False,
    block_apps: int = 1,
    lane_chunk: int = 0,
    init_state: State | None = None,
    active0=None,
) -> dict:
    """Dispatch one stacked batch onto the shared round engine."""
    if method == "CongUnaware":
        out = dict(
            _solve_fleet_congunaware(
                stacked, use_pallas=use_pallas, interpret=interpret,
                solver=solver,
            )
        )
        out["rounds"] = jnp.int32(0)
        out["trace"] = None
        return out
    # keep_state=False drops the full [B, A, K, V, V] State inside the
    # engine: the fleet result only surfaces hosts, a chunked solve would
    # otherwise keep every chunk's phi buffers alive until the final
    # gather, and the lane-major layout would stack B of them for nothing.
    out = dict(
        engine_solve(
            stacked,
            m_max=1 if method == "OneShot" else m_max,
            t_phi=t_phi,
            alpha=alpha,
            tol=tol,
            patience=patience,
            colocate=method == "CoLocated",
            track_best=method != "OneShot",
            use_pallas=use_pallas,
            interpret=interpret,
            solver=solver,
            trace=trace,
            block_apps=block_apps,
            lane_chunk=lane_chunk,
            keep_state=keep_state,
            init_state=init_state,
            active0=active0,
        )
    )
    return out


def _plan_mesh(shard: bool, devices: int | None):
    """Decide the instance-axis layout up front — explicit and logged.

    Returns (mesh_or_None, n_devices, reason). The old `_shard_over_devices`
    hint silently kept the single-device layout whenever the batch didn't
    divide the device count; now a non-divisible batch is padded (see
    `_run_chunk`) and the only remaining fallback — a single visible device
    — is surfaced in the plan and the log."""
    if not shard:
        if devices is not None:
            raise ValueError("devices= only applies with shard=True")
        return None, 1, "not-requested"
    from ..launch.mesh import make_fleet_mesh

    mesh = make_fleet_mesh(devices)
    n_dev = int(mesh.devices.size)
    if n_dev < 2:
        logger.warning(
            "solve_fleet(shard=True): only one device in the mesh; "
            "running unsharded (reason=single-device)"
        )
        return None, 1, "single-device"
    return mesh, n_dev, "sharded"


def _auto_lane_chunk(mesh, platform: str) -> int:
    """The engine layout `lane_chunk=None` resolves to (DESIGN.md section 18).

    A committed mesh needs the fused batch (0). Unsharded, the best layout
    follows the hardware. On a TPU every round-body operation at fleet
    shapes is bound by launch latency, not bytes, so one fused operation
    over all lanes costs little more than over one: on a v5e the fused
    batch solved `paper4.loadsweep` at 92.1 inst/s against 35.1 lane-major
    and 42.6-51.5 for lane chunks of 2-10 (PERF.md section 6). On a
    CPU the fused batch thrashes cache (~1.9x per lane at V=64), and
    lane-major (1) wins; any other platform keeps that measured CPU
    layout."""
    if mesh is not None or platform == "tpu":
        return 0
    return 1


def _lane_trips(iters: np.ndarray, lane_chunk: int) -> int:
    """Lane-trips the engine's loops ran over one chunk's lanes (pad lanes
    included), from the per-lane `iters` the gather already holds.

    Lanes run in lockstep groups — all of them fused (0), or `lax.map`'s
    groups of `lane_chunk` consecutive lanes, the remainder last — and each
    group's loop runs until its slowest lane froze, a lane's own trips
    being its `iters`. Lane-trips minus Σ iters were spent on frozen lanes."""
    size = iters.size if lane_chunk == 0 else lane_chunk
    return sum(
        int(iters[i : i + size].max()) * len(iters[i : i + size])
        for i in range(0, iters.size, size)
    )


def _run_chunk(
    problems, *, envelope, hop_bound, n_parts, round_to, mesh, batch_to,
    solve_kw, warm=None,
):
    """Stack (and, when sharding, pad + commit) one chunk and solve it.

    batch_to : pad the lane count up to this target with inert repeats (the
        chunked path passes `chunk_size` so every chunk compiles to the same
        program); a fleet mesh additionally rounds the target up to a device
        multiple. Returns (engine_out, stacked_info, n_real, n_lanes,
        outputs_sharded).
    warm : optional (State, active_mask_or_None) pair seeding the engine
        carry — the State covers the `real` instances over the already-
        padded fleet envelope; pad lanes repeat lane 0 with active=False so
        a warm pad lane costs a single init eval, and a mesh commits the
        warm arrays alongside the stacked problem."""
    real = len(problems)
    target = max(real, batch_to or 0)
    if mesh is not None:
        n_dev = int(mesh.devices.size)
        target = -(-target // n_dev) * n_dev
    if target > real:
        problems = list(problems) + [problems[0]] * (target - real)
    with span("solve_fleet.stack", batch=target, real=real):
        stacked, info = stack_problems(
            problems, round_to=round_to, envelope=envelope, hop_bound=hop_bound,
            n_parts=n_parts,
        )
    if mesh is not None:
        with span("solve_fleet.commit", devices=int(mesh.devices.size)):
            stacked, info = shard_fleet((stacked, info), mesh)
    init_state = active0 = None
    if warm is not None:
        w_state, w_active = warm
        exp = (real,) + tuple(stacked.apps.w.shape[1:]) + (
            int(stacked.net.adj.shape[-1]),
        )
        if tuple(w_state.x.shape) != exp:
            raise ValueError(
                f"solve_fleet: warm_start placement shape "
                f"{tuple(w_state.x.shape)} does not match this fleet's "
                f"stacked envelope {exp} — the (V, A, K) envelope drifted "
                "since the state was produced; re-solve cold"
            )
        if target > real:
            w_state = jax.tree_util.tree_map(
                lambda x: jnp.concatenate(
                    [x, jnp.repeat(x[:1], target - real, axis=0)]
                ),
                w_state,
            )
        act = (
            jnp.ones(real, bool)
            if w_active is None
            else jnp.asarray(np.asarray(w_active)).reshape(real).astype(bool)
        )
        act = jnp.concatenate([act, jnp.zeros(target - real, bool)])
        if mesh is not None:
            w_state, act = shard_fleet((w_state, act), mesh)
        init_state, active0 = w_state, act
    key = (
        stacked.net.adj.shape,
        stacked.apps.L.shape,
        stacked.hop_bound,
        1 if mesh is None else int(mesh.devices.size),
        init_state is not None,
        tuple(sorted(solve_kw.items())),
    )
    cold = key not in _COMPILE_CACHE_KEYS
    _COMPILE_CACHE_KEYS.add(key)
    obs_registry.counter(
        "fleet.compile.cold" if cold else "fleet.compile.warm"
    ).inc()
    lane_chunk = solve_kw["lane_chunk"]
    with span(
        "solve_fleet.execute", batch=target, cold_compile=cold,
        layout={0: "fused", 1: "lane_major"}.get(lane_chunk, lane_chunk),
    ):
        # dispatch: argument handling, input transfer and enqueue
        with span("solve_fleet.dispatch"):
            out = _solve_fleet_stacked(
                stacked, init_state=init_state, active0=active0, **solve_kw
            )
        if tracer_enabled():
            # Only when tracing: make the span cover the device work, not
            # just the dispatch. Untraced solves keep async dispatch.
            with span("solve_fleet.wait"):
                jax.block_until_ready(out["J"])
    out["parts"] = stacked.apps.parts
    sharded_out = mesh is not None and carries_fleet_sharding(out["J"])
    if mesh is not None and not sharded_out:
        # The whole point of PR 4: a layout change must never be silent.
        logger.warning(
            "solve_fleet: engine outputs lost the fleet sharding "
            "(B=%d over %d devices) — recording output_sharded=False",
            target, int(mesh.devices.size),
        )
    return out, info, real, target, sharded_out


def envelope_cap_chunk(
    problems, *, round_to: int, n_devices: int, cap_gb: float
) -> int:
    """Largest chunk size keeping one device's phi-shaped buffers under
    `cap_gb` for this fleet's (V, A) tier.

    The engine's dominant footprint is the `[B_dev, A, K, V, V]` family
    (state/best/next phi plus the placement sweep's delta — `_PHI_COPIES`
    float32 copies per lane at the round-body peak). `chunk_size` caps B
    globally; this caps the *per-device envelope*, which is what actually
    blows up at V >= 512 (ROADMAP item)."""
    if cap_gb <= 0:
        raise ValueError(f"envelope_cap_gb must be positive, got {cap_gb}")
    v, a = fleet_envelope(problems, round_to=round_to)
    k_stages = fleet_part_envelope(problems) + 1
    per_lane_bytes = _PHI_COPIES * a * k_stages * v * v * 4
    lanes_per_device = max(1, int(cap_gb * 2**30 // per_lane_bytes))
    return lanes_per_device * max(1, n_devices)


def solve_fleet(
    problems,
    *,
    method: str = "ALT",
    m_max: int = 30,
    t_phi: int = 10,
    alpha: float = 0.5,
    tol: float = 1e-3,
    patience: int = 4,
    round_to: int = 1,
    shard: bool = False,
    devices: int | None = None,
    use_pallas: bool = False,
    interpret: bool = False,
    solver: str = "neumann",
    block_apps: int = 1,
    lane_chunk: int | None = None,
    chunk_size: int | None = None,
    envelope_cap_gb: float | None = None,
    trace: bool = True,
    warm_start: State | None = None,
    warm_active=None,
    keep_state: bool = False,
    validate: bool = True,
) -> FleetResult:
    """Solve a heterogeneous fleet of problems as one batched computation.

    problems   : list of `Problem` (arbitrary mixed sizes; padded internally)
    method     : "ALT" | "OneShot" | "CongUnaware" | "CoLocated", matching
                 the sequential solvers in core/alt.py instance-for-instance
    round_to   : round the padded (V, A) envelope up to this multiple so a
                 long-running control plane compiles few distinct shapes
    shard      : run the engine with the instance axis committed over a 1-D
                 fleet mesh of local devices; non-divisible batches are
                 padded up to a device multiple with inert repeats (trimmed
                 on gather). The decision taken is surfaced as
                 `FleetResult.shard` and logged — never silent.
    devices    : cap the fleet mesh to the first N local devices
                 (requires shard=True; asking for more than exist raises)
    solver     : "neumann" (hop-capped propagation, default) | "lu" (dense)
    block_apps : placement sweep schedule (core/placement.py module doc):
                 1 = the paper's sequential per-app scan (default), k > 1 =
                 blocked Jacobi scoring with conflict-checked acceptance in
                 size-k blocks, 0 = one block over all apps. Ignored by
                 CongUnaware (no placement sweep).
    lane_chunk : engine layout over the instance axis (engine_solve):
                 0 = the fused batch — one lockstep while_loop whose round
                 body vmaps over all lanes (the only layout compatible with
                 a committed fleet mesh); k >= 1 = lane-major — each lane's
                 WHOLE solve runs inside `lax.map(..., batch_size=k)`, so
                 its phi-shaped buffers stay cache-resident across rounds
                 and a converged lane stops computing immediately instead
                 of riding lockstep until the slowest lane stalls. Results
                 are bitwise-identical across layouts on the CPU. None
                 (default) = auto, by the platform the engine runs on
                 (`_auto_lane_chunk`): fused on a TPU or under a committed
                 mesh, lane-major otherwise. Asking for a nonzero chunk
                 together with shard=True raises.
    interpret  : with use_pallas=True, run the kernel bodies under the Pallas
                 interpreter (CPU tests only); ignored when
                 use_pallas=False.
    chunk_size : split ensembles larger than this into fixed-B chunks that
                 share one global (V, A) envelope + hop bound, reusing a
                 single compiled program per (V, A, B) signature; the tail
                 chunk is padded with repeats of its first instance (results
                 trimmed). None = one batch. When sharding, the chunk size
                 is rounded up to a device multiple so every chunk keeps the
                 committed layout.
    envelope_cap_gb : bound the per-device footprint of the phi-shaped
                 [B, A, K, V, V] engine buffers by auto-capping the chunk
                 size for this fleet's (V, A) tier (see `envelope_cap_chunk`)
    trace      : carry the engine's on-device round trace (J split, churn,
                 live mask, best round) out as `FleetResult.trace`; False
                 drops the buffers from the compiled loop entirely. Results
                 are bitwise-identical either way.
    warm_start : a stacked `State` over this fleet's envelope — typically
                 `FleetResult.state` from the previous control epoch, after
                 `chaos.repair_fleet` — seeding the engine carry instead of
                 `structured_init` (DESIGN.md section 15). Shape-checked
                 against the stacked envelope (a drifted envelope raises).
                 Single-chunk only: a warm fleet must fit one engine batch.
    warm_active: optional [B] bool mask (requires warm_start); False lanes
                 are frozen from round 0 and return exactly the warm state's
                 evaluation — the "re-solve only the perturbed instances"
                 mechanism. None = all lanes active.
    keep_state : surface the solved stacked `State` as `FleetResult.state`
                 (the warm-start currency for the next epoch). Unsupported
                 for CongUnaware (its baseline never forms an engine state).
    validate   : host-side input validation (`_validate_problems`): reject
                 non-finite rates/capacities, dead src/dst endpoints and
                 empty live-host sets with a named ValueError instead of
                 letting NaN propagate through the fixed point.
    """
    if method not in METHODS:
        raise ValueError(f"unknown method {method!r}; expected one of {METHODS}")
    if validate:
        _validate_problems(problems)
    if warm_active is not None and warm_start is None:
        raise ValueError("warm_active requires warm_start")
    if warm_start is not None and method == "CongUnaware":
        raise ValueError(
            "warm_start is meaningless for CongUnaware (a zero-iteration "
            "baseline that never runs the engine)"
        )
    if keep_state and method == "CongUnaware":
        raise ValueError("keep_state is unsupported for CongUnaware")
    solve_kw = dict(
        method=method, m_max=m_max, t_phi=t_phi, alpha=alpha, tol=tol,
        patience=patience, use_pallas=use_pallas, interpret=interpret,
        solver=solver, trace=trace, keep_state=keep_state,
        block_apps=block_apps,
    )
    n = len(problems)
    mesh, n_dev, reason = _plan_mesh(shard, devices)

    if lane_chunk is not None and lane_chunk != 0 and mesh is not None:
        raise ValueError(
            f"lane_chunk={lane_chunk} is incompatible with a committed fleet "
            "mesh: lax.map lane chunks break the instance-axis sharding — "
            "use lane_chunk=0 (or leave it None) when shard=True"
        )
    if lane_chunk is None:
        lane_chunk = _auto_lane_chunk(mesh, jax.default_backend())
    solve_kw["lane_chunk"] = lane_chunk

    if envelope_cap_gb is not None:
        cap = envelope_cap_chunk(
            problems, round_to=round_to, n_devices=n_dev,
            cap_gb=envelope_cap_gb,
        )
        if chunk_size is None or cap < chunk_size:
            if cap < n:
                logger.info(
                    "solve_fleet: envelope cap %.3g GB/device limits this "
                    "(V, A) tier to chunks of B=%d (was %s)",
                    envelope_cap_gb, cap, chunk_size,
                )
            chunk_size = cap
    if mesh is not None and chunk_size is not None and chunk_size % n_dev:
        # Round the chunk itself so every chunk (not just the tail) runs at
        # a device multiple and reuses one compiled, committed program.
        chunk_size = -(-chunk_size // n_dev) * n_dev

    warm = None
    if warm_start is not None:
        if chunk_size is not None and n > chunk_size:
            raise ValueError(
                f"warm_start is single-chunk only: fleet of {n} instances "
                f"would split into chunks of {chunk_size} — raise chunk_size/"
                "envelope_cap_gb or re-solve cold"
            )
        warm = (warm_start, warm_active)

    chunk_kw = dict(round_to=round_to, mesh=mesh, solve_kw=solve_kw)
    if chunk_size is None or n <= chunk_size:
        outs = [
            _run_chunk(problems, envelope=None, hop_bound=None, n_parts=None,
                       batch_to=None, warm=warm, **chunk_kw)
        ]
    else:
        # One global envelope + hop bound + partition envelope so every
        # chunk hits the same compiled program.
        envelope = fleet_envelope(problems, round_to=round_to)
        hop_bound = unify_hop_bound(problems)
        part_env = fleet_part_envelope(problems)
        outs = [
            _run_chunk(
                list(problems[i : i + chunk_size]), envelope=envelope,
                hop_bound=hop_bound, n_parts=part_env, batch_to=chunk_size,
                **chunk_kw,
            )
            for i in range(0, n, chunk_size)
        ]

    plan = ShardPlan(
        requested=shard,
        n_devices=n_dev,
        batch=n,
        padded_batch=sum(lanes for (_, _, _, lanes, _) in outs),
        reason=reason,
        output_sharded=mesh is not None
        and all(ok for (_, _, _, _, ok) in outs),
    )

    def chunk_fields(o, i):
        d = dict(
            J=o["J"], J_comm=o["J_comm"], J_comp=o["J_comp"],
            history=o["history"], iters=o["iters"], rounds=o["rounds"],
            hosts=o["hosts"], parts=o["parts"],
            node_mask=i.node_mask, app_mask=i.app_mask,
        )
        if o.get("trace") is not None:
            t = o["trace"]
            d.update(
                trace_J_comm=t.J_comm, trace_J_comp=t.J_comp,
                trace_moves=t.moves, trace_live=t.live,
                trace_best_round=t.best_round,
            )
        return d

    with span("solve_fleet.gather", chunks=len(outs)):
        # ONE device->host sync for every result field across every chunk
        # (device_get on the whole tree): a sync per field per chunk costs
        # more host round-trips than the arrays are worth — the gathered
        # fields are all small [B]- or [B, m_max]-shaped summaries.
        host = jax.device_get(
            [chunk_fields(o, i) for (o, i, _, _, _) in outs]
        )

        def gather(name):
            parts_ = [hc[name][:k] for hc, (_, _, k, _, _) in zip(host, outs)]
            # device_get hands back read-only buffers; the result contract
            # is plain owned numpy (callers mutate hosts in place). The
            # fields are small, so the copy is noise.
            return (
                np.array(parts_[0])
                if len(parts_) == 1
                else np.concatenate(parts_)
            )

        kept_state = None
        if keep_state:
            # Trim pad lanes per chunk, then concatenate; stays on device —
            # this is the next epoch's warm-start input, not a host export.
            states = [
                jax.tree_util.tree_map(lambda x, k=k: x[:k], o["state"])
                for (o, _, k, _, _) in outs
            ]
            kept_state = (
                states[0]
                if len(states) == 1
                else jax.tree_util.tree_map(
                    lambda *xs: jnp.concatenate(xs), *states
                )
            )
        fleet_trace = None
        if all(o.get("trace") is not None for (o, _, _, _, _) in outs):
            fleet_trace = FleetTrace(
                J_comm=gather("trace_J_comm"),
                J_comp=gather("trace_J_comp"),
                moves=gather("trace_moves"),
                live=gather("trace_live"),
                best_round=gather("trace_best_round"),
            )
        result = FleetResult(
            method=method,
            J=gather("J"),
            J_comm=gather("J_comm"),
            J_comp=gather("J_comp"),
            history=gather("history"),
            iters=gather("iters"),
            rounds=max(int(hc["rounds"]) for hc in host),
            hosts=gather("hosts"),
            parts=gather("parts"),
            node_mask=gather("node_mask"),
            app_mask=gather("app_mask"),
            shard=plan,
            m_max=(
                0 if method == "CongUnaware"
                else 1 if method == "OneShot" else m_max
            ),
            trace=fleet_trace,
            state=kept_state,
        )

    # Lockstep waste, from the gathered per-lane iters (no device sync):
    # lane-trips executed vs the rounds the lanes applied.
    trips = sum(_lane_trips(hc["iters"], lane_chunk) for hc in host)
    applied = sum(int(hc["iters"].sum()) for hc in host)
    obs_registry.counter("fleet.lane_trips").inc(trips)
    obs_registry.counter("fleet.lane_trips_frozen").inc(trips - applied)
    obs_registry.gauge("fleet.lockstep_waste_fraction").set(
        0.0 if trips == 0 else 1.0 - applied / trips
    )
    obs_registry.counter("fleet.chunks_executed").inc(len(outs))
    obs_registry.gauge("fleet.rounds_executed").set(result.rounds)
    obs_registry.gauge("fleet.m_max").set(result.m_max)
    obs_registry.gauge("fleet.early_exit_saved_rounds").set(
        max(0, result.m_max - result.rounds)
    )
    obs_registry.gauge("fleet.pad_overhead_fraction").set(
        0.0 if plan.padded_batch == 0
        else (plan.padded_batch - plan.batch) / plan.padded_batch
    )
    return result


def solve_sequential(problems, *, method: str = "ALT", **kw) -> list:
    """Reference path: per-instance solving through the same engine at B=1.

    Used by benchmarks/fleet_bench.py for the batched-vs-sequential speedup
    and by tests for the equivalence guarantee. Kwargs are filtered through
    `core.alt.METHOD_KWARGS` — one shared dict for every method, so the
    sequential baselines can never diverge from the fleet's."""
    fn = ALL_METHODS[method]
    return [fn(p, **method_kwargs(method, kw)) for p in problems]
