"""Faults planted under a driver's timed path, for the tests and for the
readings that set the limits. Each wraps `solve_fleet` and breaks what it
returns the way a faulty program would."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def unchanged(solve):
    """A solve whose steps return their state unchanged: zero ALT rounds."""
    def run(problems, **kw):
        return solve(problems, **{**kw, "m_max": 0})
    return run


def cut_short(rounds):
    def fault(solve):
        """A solve cut short: `rounds` ALT rounds, whatever the stopping
        rule says."""
        def run(problems, **kw):
            return solve(problems, **{**kw, "m_max": rounds})
        return run
    return fault


def half_batch(solve):
    """Half of the batch left out: the second half of the lanes gets the
    first half's answers."""
    def run(problems, **kw):
        res = solve(problems, **kw)
        b = len(res.J)
        src = np.arange(b) % max(b // 2, 1)
        pick = lambda x: None if x is None else x[src]
        state = res.state
        if state is not None:
            state = jax.tree_util.tree_map(lambda x: x[jnp.asarray(src)], state)
        return dataclasses.replace(
            res, J=pick(res.J), hosts=pick(res.hosts), iters=pick(res.iters),
            state=state,
        )
    return run


def altered(solve):
    """An answer altered where it is produced: every instance's first
    partition moves to the next node."""
    def run(problems, **kw):
        res = solve(problems, **kw)
        hosts = res.hosts.copy()
        n = np.asarray([int(np.asarray(p.net.adj).shape[0]) for p in problems])
        hosts[:, 0, 0] = (hosts[:, 0, 0] + 1) % n
        return dataclasses.replace(res, hosts=hosts)
    return run


FAULTS = {"unchanged": unchanged, "one_round": cut_short(1), "two_rounds": cut_short(2),
          "half_batch": half_batch, "altered": altered}
