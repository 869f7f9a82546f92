"""Device-resident ALT round engine: ONE `while_loop` core behind both the
sequential solvers (core/alt.py) and the batched fleet solver (fleet/solve.py).

The paper's Algorithm 1 is a single alternating loop (placement sweep ->
T_phi forwarding sweeps -> objective). This module is the one place that
loop lives: a pure `round_step(carry) -> carry` implementing the restructured
round dataflow (one `round_eval` feeding both the history/stall logic and the
next placement sweep — DESIGN.md section 10), plus best-iterate tracking,
per-instance stall counters, and freeze masking, all carried on device.

`engine_solve` wraps `round_step` in a jitted `lax.while_loop` whose
predicate is "any live instance below m_max": a fully converged batch — or
the B=1 sequential case — exits as soon as every instance has stalled,
instead of padding to `m_max` rounds the way the old fixed-length scan did.
Because a while_loop cannot stack per-trip outputs, the per-round objective
trace is written into a preallocated `[B, m_max + 1]` history buffer via a
dynamic column update; unwritten slots stay NaN (the same "NaN past the
freeze point" contract the fleet result has always exposed).

The same mechanism carries the optional round trace (`EngineTrace`,
DESIGN.md section 14): per-round J_comm/J_comp split, placement churn
(live (app, partition) hosts that moved), a live/applied mask, and the
best-iterate round index — all written by the identical masked dynamic
column update, so they obey the exact NaN-past-freeze contract, add no
host syncs inside the loop, and stay bitwise-inert on frozen lanes.
`trace=False` removes the buffers entirely; the solved result is
bitwise-identical either way (the trace is written FROM the round's
values, never read by it).

Batch semantics (DESIGN.md section 11):
  * the whole round body is vmapped over the leading instance axis, so a
    stacked fleet and a single `[1, ...]`-stacked problem run the exact same
    compiled loop — sequential solving IS the engine at B=1, squeezed.
    `engine_solve(lane_chunk=k >= 1)` flips the nesting to lane-major (each
    lane's full solve inside `lax.map`, DESIGN.md section 18) with
    bitwise-identical per-lane outputs;
  * frozen instances (stalled for `patience` rounds) are masked out of every
    carry update, so extra trips driven by still-live instances leave their
    results bit-identical;
  * the early exit is batch-wide, matching the sequential per-instance
    `break` exactly at B=1 and costing live instances nothing at B>1. It is
    also *shard-safe*: the `[B]` active mask is reduced to ONE replicated
    `any_active` scalar inside the round body (where the partitioner emits a
    single all-reduce when the instance axis is laid out over a fleet mesh),
    and the while_loop predicate only ever reads that scalar — no per-trip
    host sync, no collective inside the cond.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from ..obs.trace import engine_scope
from .forwarding import forwarding_update
from .marginals import round_eval
from .placement import placement_update, structured_init
from .structs import (
    Problem,
    State,
    app_live_mask,
    partition_live_mask,
)


def _bwhere(pred, a, b):
    """Pytree select with a [B] predicate broadcast from the left."""

    def sel(x, y):
        p = pred.reshape(pred.shape + (1,) * (x.ndim - pred.ndim))
        return jnp.where(p, x, y)

    return jax.tree_util.tree_map(sel, a, b)


def _objective_of(aux):
    """The objective split alone — the best-iterate slot never carries the
    [A, K, V, V]-sized ctg tensors, which would double the loop-carry
    footprint for nothing."""
    return {"J": aux["J"], "J_comm": aux["J_comm"], "J_comp": aux["J_comp"]}


@dataclasses.dataclass(frozen=True)
class EngineTrace:
    """On-device round-trace buffers (obs layer 1, DESIGN.md section 14).

    Every `[B, m_max + 1]` buffer follows the J-history contract: column m
    is written by the masked dynamic update of round m and keeps its init
    value (NaN, or 0.0 for `live`) wherever the round was not applied —
    past an instance's freeze point a masked write stores exactly the init
    value, so frozen lanes stay bitwise-independent of later trips.

    J_comm     : [B, m_max + 1] communication objective per applied round
    J_comp     : [B, m_max + 1] computation objective per applied round
    moves      : [B, m_max + 1] placement churn — live (app, partition)
                 hosts that changed this round; column 0 is 0.0 (the init
                 has no previous placement)
    live       : [B, m_max + 1] 1.0 iff the round was applied to the
                 instance; the other buffers' NaN mask in arithmetic form
                 (host side derives per-round frozen-instance counts from it)
    best_round : [B] int32 round index of the running best iterate
    """

    J_comm: jax.Array
    J_comp: jax.Array
    moves: jax.Array
    live: jax.Array
    best_round: jax.Array


jax.tree_util.register_dataclass(
    EngineTrace,
    data_fields=["J_comm", "J_comp", "moves", "live", "best_round"],
    meta_fields=[],
)


def placement_churn(problem: Problem, new: State, old: State) -> jax.Array:
    """[B] count of live (app, partition) hosts that differ between two
    batched states — phantom apps (lambda = 0) and phantom partitions
    (p >= parts) are masked out so stage/app padding cannot leak churn."""
    live = (
        partition_live_mask(problem.apps)
        * app_live_mask(problem.apps)[..., None]
    )  # [B, A, P]
    return jnp.sum((new.hosts() != old.hosts()) * live, axis=(-2, -1))


@dataclasses.dataclass(frozen=True)
class EngineCarry:
    """The while_loop carry: everything one ALT round reads and writes.

    state      : [B, ...] current iterate (placement x + forwarding phi)
    aux        : `round_eval` output at `state` — objective split plus the
                 (q, dp, kappa, t, F, G) ctg tuple the next placement sweep
                 consumes (no re-solve of the traffic fixed point)
    best_state : [B, ...] best-iterate state seen so far
    best_obj   : {"J","J_comm","J_comp"} at `best_state`
    best_J     : [B] running minimum objective
    stall      : [B] int32 rounds since the last tol-sized improvement
    iters      : [B] int32 rounds actually applied per instance
    active     : [B] bool; False once an instance froze (stall >= patience)
    any_active : scalar bool, `jnp.any(active)` reduced once per trip in the
                 body; the while_loop predicate reads only this replicated
                 scalar, keeping the early exit shard-safe when `active` is
                 laid out over a fleet mesh axis
    m          : scalar int32 trip counter (= rounds the while_loop ran)
    history    : [B, m_max + 1] objective trace; NaN past each freeze point
    trace      : `EngineTrace` round-trace buffers, or None when tracing
                 is off (the slot vanishes from the pytree entirely)
    """

    state: State
    aux: dict
    best_state: State
    best_obj: dict
    best_J: jax.Array
    stall: jax.Array
    iters: jax.Array
    active: jax.Array
    any_active: jax.Array
    m: jax.Array
    history: jax.Array
    trace: EngineTrace | None


jax.tree_util.register_dataclass(
    EngineCarry,
    data_fields=[
        "state", "aux", "best_state", "best_obj", "best_J", "stall",
        "iters", "active", "any_active", "m", "history", "trace",
    ],
    meta_fields=[],
)


def round_step(
    problem: Problem,
    carry: EngineCarry,
    *,
    t_phi: int,
    alpha: float,
    tol: float,
    patience: int,
    colocate: bool,
    use_pallas: bool,
    solver: str,
    interpret: bool = False,
    block_apps: int = 1,
) -> EngineCarry:
    """One batched ALT round: Algorithm 1's loop body plus bookkeeping.

    Placement is fed the PREVIOUS round's evaluation (carry.aux["ctg"]),
    then T_phi forwarding sweeps run, then one `round_eval` closes the round.
    Stall is measured against the best J *before* this round's update, and
    every carry slot of a frozen instance is masked back to its old value.
    `block_apps` selects the placement sweep schedule (placement.py module
    doc): 1 = sequential scan, k > 1 / 0 = blocked sweep.
    The round body is one vmapped program over all B lanes — the layout
    choice over the instance axis (fused rounds vs lane-major chunks) lives
    in `engine_solve(lane_chunk=...)`, which decides whether this step runs
    over the whole batch per trip or inside a per-lane solve.
    """

    def one_round(p, s, ctg):
        with engine_scope("alt.placement"):
            nxt = placement_update(
                p, s, ctg, colocate=colocate, use_pallas=use_pallas,
                interpret=interpret, solver=solver, block_apps=block_apps,
            )
        with engine_scope("alt.forwarding"):
            nxt = forwarding_update(
                p, nxt, t_phi=t_phi, alpha=alpha, solver=solver,
                use_pallas=use_pallas, interpret=interpret,
            )
        with engine_scope("alt.round_eval"):
            J, aux_nxt = round_eval(
                p, nxt, solver=solver, use_pallas=use_pallas,
                interpret=interpret,
            )
        return nxt, J, aux_nxt

    nxt, J, aux_nxt = jax.vmap(one_round)(
        problem, carry.state, carry.aux["ctg"]
    )

    improved = J < carry.best_J * (1.0 - tol)
    stall_nxt = jnp.where(improved, 0, carry.stall + 1)
    is_best = J < carry.best_J
    best_state_nxt = _bwhere(is_best, nxt, carry.best_state)
    best_obj_nxt = _bwhere(is_best, _objective_of(aux_nxt), carry.best_obj)
    best_J_nxt = jnp.minimum(J, carry.best_J)

    # Freeze masking: instances that already stalled keep every slot.
    active = carry.active
    col = carry.m + 1
    history = carry.history.at[:, col].set(jnp.where(active, J, jnp.nan))
    trace = carry.trace
    if trace is not None:
        # Same masked dynamic-column writes as the history: inactive lanes
        # store exactly the buffer's init value (NaN / 0.0), so the trace
        # inherits the freeze-point contract bit for bit. Everything here is
        # computed from values the round already produced — no extra solves,
        # no host syncs, and the main dataflow never reads a trace buffer.
        with engine_scope("alt.roundtrace"):
            moved = placement_churn(problem, nxt, carry.state)
            trace = EngineTrace(
                J_comm=trace.J_comm.at[:, col].set(
                    jnp.where(active, aux_nxt["J_comm"], jnp.nan)
                ),
                J_comp=trace.J_comp.at[:, col].set(
                    jnp.where(active, aux_nxt["J_comp"], jnp.nan)
                ),
                moves=trace.moves.at[:, col].set(
                    jnp.where(active, moved.astype(trace.moves.dtype), jnp.nan)
                ),
                live=trace.live.at[:, col].set(active.astype(trace.live.dtype)),
                best_round=jnp.where(
                    active & is_best, col.astype(jnp.int32), trace.best_round
                ),
            )
    active_nxt = active & (stall_nxt < patience)
    return EngineCarry(
        state=_bwhere(active, nxt, carry.state),
        aux=_bwhere(active, aux_nxt, carry.aux),
        best_state=_bwhere(active, best_state_nxt, carry.best_state),
        best_obj=_bwhere(active, best_obj_nxt, carry.best_obj),
        best_J=jnp.where(active, best_J_nxt, carry.best_J),
        stall=jnp.where(active, stall_nxt, carry.stall),
        iters=carry.iters + active.astype(jnp.int32),
        active=active_nxt,
        # The only cross-instance reduction in the loop: one scalar per trip,
        # computed in the body so the predicate stays collective-free.
        any_active=jnp.any(active_nxt),
        m=carry.m + 1,
        history=history,
        trace=trace,
    )


def _engine_solve_batch(
    stacked: Problem,
    *,
    m_max: int,
    t_phi: int,
    alpha: float,
    tol: float,
    patience: int,
    colocate: bool,
    track_best: bool,
    use_pallas: bool,
    interpret: bool,
    solver: str,
    trace: bool,
    block_apps: int,
    keep_state: bool,
    init_state: State | None,
    active0: jax.Array | None,
) -> dict:
    """The fused-batch engine core: init + one lockstep `lax.while_loop`
    whose round body vmaps over every lane (see `engine_solve`)."""

    if init_state is None:

        def init_one(p):
            s = structured_init(
                p, colocate=colocate, use_pallas=use_pallas, interpret=interpret
            )
            J, aux = round_eval(
                p, s, solver=solver, use_pallas=use_pallas, interpret=interpret
            )
            return s, J, aux

        with engine_scope("alt.init"):
            state0, J0, aux0 = jax.vmap(init_one)(stacked)
    else:
        state0 = init_state
        with engine_scope("alt.init"):
            J0, aux0 = jax.vmap(
                lambda p, s: round_eval(
                    p, s, solver=solver, use_pallas=use_pallas,
                    interpret=interpret,
                )
            )(stacked, state0)
    batch = J0.shape[0]
    history0 = jnp.full((batch, m_max + 1), jnp.nan, dtype=J0.dtype)
    trace0 = None
    if trace:
        nan_buf = jnp.full((batch, m_max + 1), jnp.nan, dtype=J0.dtype)
        trace0 = EngineTrace(
            J_comm=nan_buf.at[:, 0].set(aux0["J_comm"]),
            J_comp=nan_buf.at[:, 0].set(aux0["J_comp"]),
            moves=nan_buf.at[:, 0].set(0.0),
            live=jnp.zeros((batch, m_max + 1), J0.dtype).at[:, 0].set(1.0),
            best_round=jnp.zeros(batch, jnp.int32),
        )
    if active0 is None:
        active_init = jnp.ones(batch, bool)
    else:
        active_init = jnp.asarray(active0).reshape(batch).astype(bool)
    carry = EngineCarry(
        state=state0,
        aux=aux0,
        best_state=state0,
        best_obj=_objective_of(aux0),
        best_J=J0,
        stall=jnp.zeros(batch, jnp.int32),
        iters=jnp.zeros(batch, jnp.int32),
        active=active_init,
        any_active=jnp.any(active_init),
        m=jnp.int32(0),
        history=history0.at[:, 0].set(J0),
        trace=trace0,
    )
    step = functools.partial(
        round_step,
        stacked,
        t_phi=t_phi,
        alpha=alpha,
        tol=tol,
        patience=patience,
        colocate=colocate,
        use_pallas=use_pallas,
        solver=solver,
        interpret=interpret,
        block_apps=block_apps,
    )

    def body(c):
        with engine_scope("alt.round"):
            return step(c)

    carry = jax.lax.while_loop(
        lambda c: (c.m < m_max) & c.any_active, body, carry
    )
    if track_best:
        out_state, out_obj = carry.best_state, carry.best_obj
    else:
        out_state, out_obj = carry.state, _objective_of(carry.aux)
    out = {
        "J": out_obj["J"],
        "J_comm": out_obj["J_comm"],
        "J_comp": out_obj["J_comp"],
        "hosts": out_state.hosts(),
        "history": carry.history,
        "iters": carry.iters,
        "rounds": carry.m,
        "trace": carry.trace,
    }
    if keep_state:
        out["state"] = out_state
    return out


@functools.partial(
    jax.jit,
    static_argnames=(
        "m_max", "t_phi", "alpha", "tol", "patience", "colocate",
        "track_best", "use_pallas", "interpret", "solver", "trace",
        "block_apps", "lane_chunk", "keep_state",
    ),
)
def engine_solve(
    stacked: Problem,
    *,
    m_max: int,
    t_phi: int,
    alpha: float,
    tol: float,
    patience: int,
    colocate: bool = False,
    track_best: bool = True,
    use_pallas: bool = False,
    interpret: bool = False,
    solver: str = "neumann",
    trace: bool = True,
    block_apps: int = 1,
    lane_chunk: int = 0,
    keep_state: bool = True,
    init_state: State | None = None,
    active0: jax.Array | None = None,
) -> dict:
    """Run the alternating method on a stacked `[B, ...]` problem pytree.

    Warm start (DESIGN.md section 15): `init_state` seeds the while_loop
    carry from a caller-provided `[B, ...]` State (e.g. the previous control
    epoch's placement after failure repair) instead of `structured_init`;
    `active0` is an optional [B] bool mask freezing instances from round 0 —
    a frozen-from-start lane never runs a round and returns exactly its
    init-state evaluation, so an epoch whose fault touched 2 of 64 instances
    burns rounds only on those 2. Both are traced pytree arguments (None vs
    provided changes the trace, same as `trace=`); the cold path (both None)
    is the exact pre-warm-start program. When every lane starts frozen the
    loop body never runs and the init evaluation IS the result — the
    controller's "every epoch ends with a servable placement" guarantee.

    `lane_chunk` picks the layout over the instance axis (DESIGN.md
    section 18). 0 = the fused batch: ONE lockstep while_loop whose round
    body vmaps over all B lanes — the only layout compatible with a
    committed instance-axis mesh, and the faster one on a TPU, where each
    round-body operation costs its launch latency whatever the lane count.
    k >= 1 = lane-major: each lane's WHOLE solve (init eval + its own
    while_loop) runs inside `lax.map` over k-lane chunks, so a lane's
    [A, K, V, V] working set stays cache-resident across its rounds, the
    per-round slice/stack traffic of mapping the round body is paid once
    per solve instead of once per trip, and a converged lane stops
    computing immediately (the per-instance early exit of the sequential
    path, inside one compiled program) — the faster layout on a CPU.
    `solve_fleet(lane_chunk=None)` picks between them by platform (on a
    TPU v5e the fused batch solves the benchmark's paper4 fleets at 92.1
    instances/s against 35.1 lane-major; DESIGN.md section 18).
    Per-lane outputs are bitwise-identical across layouts on the CPU: each
    lane runs the same op sequence either way, freeze masking keeps
    lockstep trips inert past a lane's own stall point, and the
    NaN-past-freeze buffer contract writes the same values in both
    schedules.

    `keep_state=False` drops the full `[B, ...]` State from the output dict
    (the fleet path's default — it only surfaces `hosts` unless the caller
    asked for the warm-start currency), which in the lane-major layout also
    skips stacking B phi-shaped buffers on the way out.

    Returns a dict of device arrays (leading axis B throughout):
      J / J_comm / J_comp : final objective split (best iterate, or the
                            final state when `track_best=False` — the
                            OneShot semantics)
      state               : the returned State (best or final); absent
                            when `keep_state=False`
      hosts               : [B, A, P] partition hosts of the returned state
      history             : [B, m_max + 1] objective trace, NaN past freeze
      iters               : [B] int32 rounds applied per instance
      rounds              : scalar int32 while_loop trips actually executed
                            (< m_max whenever the whole batch froze early;
                            lane-major: the max over per-lane loop trips,
                            the same number by the freeze-point argument)
      trace               : `EngineTrace` round-trace buffers (None when
                            `trace=False`); every other output is
                            bitwise-identical across the two settings
    """
    kw = dict(
        m_max=m_max, t_phi=t_phi, alpha=alpha, tol=tol, patience=patience,
        colocate=colocate, track_best=track_best, use_pallas=use_pallas,
        interpret=interpret, solver=solver, trace=trace,
        block_apps=block_apps, keep_state=keep_state,
    )
    if lane_chunk == 0:
        with engine_scope("alt.engine"):
            return _engine_solve_batch(
                stacked, init_state=init_state, active0=active0, **kw
            )

    def lane_solve(args):
        p, s0, a0 = args

        def lift(t):
            return (
                None if t is None
                else jax.tree_util.tree_map(lambda x: x[None], t)
            )

        out = _engine_solve_batch(
            lift(p),
            init_state=lift(s0),
            active0=None if a0 is None else a0[None],
            **kw,
        )
        squeezed = {
            k: jax.tree_util.tree_map(lambda x: x[0], v)
            for k, v in out.items()
            if k != "rounds"
        }
        squeezed["rounds"] = out["rounds"]
        return squeezed

    with engine_scope("alt.engine"):
        out = jax.lax.map(
            lane_solve,
            (stacked, init_state, active0),
            batch_size=lane_chunk if lane_chunk > 1 else None,
        )
        # Per-lane loop trips stack to [B]; the engine contract is ONE
        # scalar (trips the batch would have executed in lockstep = the
        # slowest lane).
        out["rounds"] = jnp.max(out["rounds"])
    return out


def stack_single(problem: Problem) -> Problem:
    """Lift one problem to a `[1, ...]` stacked pytree (engine batch of one).

    Static metadata (`hop_bound`, `CostModel.kind`) passes through untouched;
    Python-float cost scalars become rank-1 arrays like `stack_problems`
    produces, so B=1 and B>1 hit the same engine code path."""
    return jax.tree_util.tree_map(lambda x: jnp.asarray(x)[None], problem)


def engine_solve_single(problem: Problem, **kw) -> dict:
    """Sequential entry point: the engine at B=1, squeezed.

    Same return dict as `engine_solve` minus the batch axis (`rounds` was
    already a scalar; at B=1 it equals `iters`)."""
    out = engine_solve(stack_single(problem), **kw)
    squeezed = {
        k: jax.tree_util.tree_map(lambda x: x[0], v)
        for k, v in out.items()
        if k != "rounds"
    }
    squeezed["rounds"] = out["rounds"]
    return squeezed
