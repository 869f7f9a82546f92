"""Device time of the programs that ran in the profiled capture, over the
ALT rounds of the instances solved inside it, in ms per instance-round."""


def read(reading):
    t = reading.trace
    if t is None:
        return None
    rounds = sum(
        int(r["iters"].sum()) for r in reading.ctx.records
        if reading.in_capture(r["t0"], r["t1"])
    )
    device_s = sum(t["programs"].values())
    return 1e3 * device_s / rounds if rounds and device_s > 0 else None
