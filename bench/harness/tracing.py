"""Reduction of a profiler trace to device busy time, per-program device
time, the top device operations and the idle gaps by host span.

The profile is read with `jax.profiler.ProfileData`. The reduction itself
works on plain (name, start_ns, end_ns) tuples, so it is tested on a small
recorded trace without a chip (tests/bench).
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict

WINDOW_SPAN = "bench.window"


_OP = re.compile(r"^(%[\w.\-]+) = .*?\b([a-z][\w\-]*)\(")


def op_name(hlo: str) -> str:
    """`%fusion.190 fusion` from the full HLO text the TPU trace names an
    operation by."""
    m = _OP.match(hlo)
    return f"{m.group(1)} {m.group(2)}" if m else hlo[:80]


def leaves(events):
    """The events of one trace line that contain no other event: a while
    or conditional op spans the ops of its body, which are the operations
    that ran."""
    ev = sorted(events, key=lambda e: (e[1], -e[2]))
    return [
        e for i, e in enumerate(ev)
        if not (i + 1 < len(ev) and ev[i + 1][1] < e[2])
    ]


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy, lo, hi):
    """The complement of merged `busy` intervals inside [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return out


def innermost(spans, t):
    """Name of the shortest host span that covers time t, or None."""
    best = None
    for name, s, e in spans:
        if s <= t <= e and (best is None or e - s < best[2] - best[1]):
            best = (name, s, e)
    return None if best is None else best[0]


def _clipped_totals(events, lo, hi, n_dev):
    out = defaultdict(float)
    for name, s, e in events:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            out[name] += (e - s) / n_dev
    return out


def reduce(ops, modules, host, window, top=10):
    """Device numbers over `window` = (start_ns, end_ns).

    ops, modules : per device, [(name, start_ns, end_ns)] operation and
                   program events; ops are the leaf operations (`leaves`)
    host         : [(name, start_ns, end_ns)] host spans on the same clock
    Returns busy_s (averaged over the devices), window_s, per-program
    seconds, the top operations by total time (per device) and the idle
    time of the first device by innermost covering host span.
    """
    lo, hi = window
    n_dev = max(len(ops), 1)
    per_dev = [union(clip([(s, e) for _, s, e in d], lo, hi)) for d in ops]
    busy_ns = sum(sum(e - s for s, e in b) for b in per_dev) / n_dev
    per_op = _clipped_totals([ev for d in ops for ev in d], lo, hi, n_dev)
    per_prog = _clipped_totals([ev for d in modules for ev in d], lo, hi, n_dev)
    busy = per_dev[0] if per_dev else []
    idle = defaultdict(int)
    spans = [h for h in host if h[0] != WINDOW_SPAN]
    for s, e in gaps(busy, lo, hi):
        idle[innermost(spans, (s + e) / 2) or "no host span"] += e - s
    rank = lambda d: [
        [k, v / 1e9] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]
    ]
    return {
        "busy_s": busy_ns / 1e9,
        "window_s": (hi - lo) / 1e9,
        "programs": {k: v / 1e9 for k, v in per_prog.items()},
        "device_ops": rank(per_op),
        "idle_gaps": rank(idle),
        "idle_s": sum(idle.values()) / 1e9,
    }


def load(log_dir, device_prefix="/device:TPU:"):
    """Events of the newest profile under `log_dir`: device ops and programs
    of every `device_prefix` plane, host annotations, and the window span.
    Returns None where the profile holds no device plane or no window."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return None
    pd = ProfileData.from_file(paths[-1])
    ops, modules, host = [], [], []
    for plane in pd.planes:
        if plane.name.startswith(device_prefix):
            ops.append([])
            modules.append([])
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops[-1] = leaves([
                        (op_name(ev.name), ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                    ])
                elif line.name == "XLA Modules":
                    modules[-1] = [
                        (ev.name.split("(")[0], ev.start_ns, ev.start_ns + ev.duration_ns)
                        for ev in line.events
                    ]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend(
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if ev.duration_ns > 0 and not ev.name.startswith("$")
                )
    win = [h for h in host if h[0] == WINDOW_SPAN]
    if not win or not any(ops):
        return None
    return dict(ops=ops, modules=modules, host=host, window=(win[0][1], win[0][2]))
