"""Host time of a fleet call outside its device execution and result
gather (input validation, padding and stacking), per instance, in ms."""


def read(reading):
    calls = reading.spans_named("bench.fleet.call")
    if not calls:
        return None
    inner = {}
    for s in reading.spans:
        if s.name in ("solve_fleet.execute", "solve_fleet.gather"):
            inner[s.parent] = inner.get(s.parent, 0.0) + s.dur
    host = sum(c.dur - inner.get(c.id, 0.0) for c in calls)
    n = sum(len(r["J"]) for r in reading.ctx.records)
    return 1e3 * host / n if n else None
