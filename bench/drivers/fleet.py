"""Driver `fleet`: back-to-back `repro.fleet.solve_fleet` calls.

Traffic parameters (bench/traffic/<mix>.json):
  load_grid        loads of the config's Fig.-4 sweep: a call solves every
                   topology at every load
  pool, fleet_seed  the fleets the window sends in turn: `pool` of them,
                   each with application draws of its own from `fleet_seed`,
                   in the same order for every run, so that every seed does
                   the same work (a seed's own draws change the rounds a
                   call takes). The pool outlasts a window, so no call
                   repeats an input that an earlier call of the run sent
  sample_per_call  answers per call kept for the check, lanes drawn from
                   the run's seed: what the run's seed changes
  solve            keyword arguments of every call (the timed path)

Set-up solves one more fleet, drawn from the run's seed and sent nowhere
else, to compile the one program the window uses (every fleet has the same
topologies, so the same envelope and hop bound). Each call is a closed
loop: the next starts when the previous returned. A call returns after the
program's own device_get of its results, so the host clock around it
covers the device work.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import gen
from harness.cell import sub_seeds
from repro.fleet import solve_fleet
from repro.obs.trace import span


class Ctx:
    pass


@jax.jit
def _lanes(phi, idx):
    """The sampled lanes' forwarding fractions, kept on the device."""
    return jnp.take(phi, idx, axis=0)


def setup(config, traffic, seed, log=print):
    ctx = Ctx()
    ctx.kw = dict(traffic.get("solve", {}))
    n_pool = int(traffic["pool"])
    warm_seed, sample_seed = sub_seeds(seed, 2)
    ctx.pool = [gen.draw(config, traffic, s) for s in sub_seeds(traffic["fleet_seed"], n_pool)]
    ctx.problems = [[gen.problem(i) for i in f] for f in ctx.pool]
    ctx.rng = np.random.RandomState(sample_seed)
    ctx.k = int(traffic["sample_per_call"])
    t0 = time.perf_counter()
    res = solve_fleet([gen.problem(i) for i in gen.draw(config, traffic, warm_seed)], **ctx.kw)
    if res.state is not None:
        _lanes(res.state.phi, jnp.zeros(ctx.k, jnp.int32)).block_until_ready()
    log(f"fleet: pool of {n_pool} x {len(ctx.problems[0])}, warmed in "
        f"{time.perf_counter() - t0:.2f} s")
    ctx.records = []
    return ctx


def window(ctx, win, solve=solve_fleet):
    ctx.records = []
    i = 0
    while win.running():
        j = i % len(ctx.problems)
        t0 = time.perf_counter()
        with span("bench.fleet.call", call=i):
            res = solve(ctx.problems[j], **ctx.kw)
        t1 = time.perf_counter()
        lanes = np.sort(ctx.rng.choice(len(ctx.problems[j]), ctx.k, replace=False))
        phi = None if res.state is None else _lanes(res.state.phi, lanes.astype(np.int32))
        ctx.records.append(dict(
            call=i, pool=j, t0=t0, t1=t1, J=res.J, hosts=res.hosts,
            iters=res.iters, lanes=lanes, phi=phi,
        ))
        del res
        i += 1
        win.tick()
    ctx.t_start, ctx.t_end = win.t_start, (
        ctx.records[-1]["t1"] if ctx.records else win.t_start
    )


def end_to_end(ctx):
    solved = sum(int(np.isfinite(r["J"]).sum()) for r in ctx.records)
    return {"solved_inst_per_s": solved / max(ctx.t_end - ctx.t_start, 1e-9)}


def counts(ctx):
    attempted = sum(len(r["J"]) for r in ctx.records)
    solved = sum(int(np.isfinite(r["J"]).sum()) for r in ctx.records)
    return attempted, attempted - solved


def answers(ctx):
    """The sampled answers of the window, (instance, hosts, phi, J) each,
    and how many of them never came. Drops the device copies."""
    got, missing = [], 0
    for r in ctx.records:
        phi = None if r["phi"] is None else np.asarray(r["phi"])
        r["phi"] = None
        for n, b in enumerate(r["lanes"]):
            if phi is None:
                missing += 1
            else:
                got.append((ctx.pool[r["pool"]][b], r["hosts"][b], phi[n], r["J"][b]))
    return got, missing
