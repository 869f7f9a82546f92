"""The check shared by the drivers: every sampled answer against the
reference, reduced to the numbers that `bench/limits/<cell>.json` bounds."""
from __future__ import annotations

import numpy as np

from . import reference

NO_GAIN_TOL = 1e-3  # an answer "gains" when J < (1 - tol) * start cost


def numbers(answers, control=False) -> dict:
    """The numbers over `answers`, an iterable of (instance, hosts, phi, J):
    the worst consistency reading (reference.check), the share of answers
    that gain nothing over the zero-load start, and the median polish gain.
    The median, because on the few answers where the program's routing is
    stuck far from its optimum (overloaded links) the polish finds most of
    J whatever the rounds did; over the sample, the median moves with the
    rounds. With control=True each answer is first replaced by the
    control's (reference.control_answer)."""
    worst = {"bad_hosts": 0, "stray": 0.0, "mass_err": 0.0, "J_gap": 0.0}
    n = no_gain = 0
    gains = []
    for inst, hosts, phi, J in answers:
        if control:
            phi, J = reference.control_answer(inst, hosts, phi)
        r = reference.check(inst, hosts, phi, J)
        worst["bad_hosts"] += r["bad_hosts"]
        for k in ("stray", "mass_err", "J_gap"):
            worst[k] = max(worst[k], r[k])
        if not float(J) < (1.0 - NO_GAIN_TOL) * reference.start_cost(inst):
            no_gain += 1
        gains.append(float("inf") if r["bad_hosts"] else
                     reference.polish_gain(inst, hosts, phi))
        n += 1
    worst["no_gain_pct"] = 100.0 * no_gain / n if n else float("inf")
    worst["polish_gain"] = float(np.median(gains)) if gains else float("inf")
    worst["checked"] = n
    return worst
