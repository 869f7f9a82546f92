"""Device time of the ALT engine's layers, from a profile.

The program names its layers inside the compiled engine with
`jax.named_scope` (`repro.obs.trace.ENGINE_SCOPES`). A name scope is
metadata of each HLO instruction (`op_name`), not an event, so the device
trace does not carry it: the TPU's `XLA Ops` events are named by the
instruction's HLO text alone. The program's HLO does carry it, in the
profile's `/host:metadata` plane, one `Hlo Proto` per program keyed by the
program's name and id (`jit_engine_solve(<id>)`, the name of its `XLA
Modules` event). `load` reads those protos once per profile, maps each
distinct (program, instruction) to its scope path once, and gives every leaf
operation the path of its instruction; an operation the compiler added
without metadata (a copy, a loop's compare) takes the path of the innermost
operation it runs inside (its while loop). A fusion carries the metadata of
its root instruction.

`reduce` splits one program's time by scope path: the busy time of each
path (each instant of the union of leaf operations counted once), the
number of leaf-operation executions, and the in-program gaps (device idle
between two consecutive leaf operations inside one program event), each gap
going to the deepest scope the two operations share. Busy and gap time add
up to the program's time.

The benchmark harness reduces the window's capture before the per-layer
readers run and then deletes it, so the readers of these metrics profile
one more call of the same fleet after the window (`measure`): the same
inputs and the same compiled program as the captured call.
"""
from __future__ import annotations

import bisect
import glob
import json
import re
import sys
import tempfile
import time
import traceback
from collections import defaultdict

from . import tracing

try:
    from repro.obs.trace import ENGINE_SCOPES
except ImportError:  # a program that names no layers
    ENGINE_SCOPES = ()

PROGRAM = "jit_engine_solve"
DEVICE_PLANE = "/device:TPU:"
METADATA_PLANE = "/host:metadata"
_INSTR = re.compile(r"^%([\w.\-]+) = ")


def scope_path(op_name: str, scopes=ENGINE_SCOPES) -> tuple:
    """The engine scopes in an HLO `op_name`, outermost first:
    `jit(engine_solve)/alt.engine/while/body/vmap(alt.forwarding)/...`
    gives ("alt.engine", "alt.forwarding")."""
    if not scopes:
        return ()
    pat = "|".join(re.escape(s) for s in sorted(scopes, key=len, reverse=True))
    return tuple(re.findall(rf"(?<![\w.])(?:{pat})(?![\w.])", op_name))


# -- the HLO of each program, from the profile's metadata plane ---------------
# The profile is an XSpace protobuf; the few fields read here are parsed from
# the wire format, so nothing beyond JAX is needed. Field numbers:
# XSpace.planes 1; XPlane.name 2, .event_metadata 4 (map entry: key 1, value
# 2); XEventMetadata.name 2, .stats 5; XStat.bytes_value 6; HloProto.
# hlo_module 1; HloModuleProto.computations 3; HloComputationProto.
# instructions 2; HloInstructionProto.name 1, .metadata 7; OpMetadata.op_name 2.


def _varint(b, i):
    shift = out = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        if c < 0x80:
            return out, i
        shift += 7


def _fields(b):
    """(field number, value) of each field of one serialized message; a
    length-delimited value is a memoryview, the others are skipped as 0."""
    b = memoryview(b)
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wt = key >> 3, key & 7
        if wt == 0:
            v, i = _varint(b, i)
        elif wt == 1:
            v, i = 0, i + 8
        elif wt == 2:
            ln, i = _varint(b, i)
            v, i = b[i:i + ln], i + ln
        elif wt == 5:
            v, i = 0, i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wt}")
        yield f, v


def _first(b, field):
    return next((v for f, v in _fields(b) if f == field), None)


def hlo_op_names(xspace: bytes) -> dict:
    """{program name: {instruction name: op_name}} of every program whose
    HLO the profile's metadata plane holds."""
    out = {}
    for f, plane in _fields(xspace):
        if f != 1 or bytes(_first(plane, 2) or b"") != METADATA_PLANE.encode():
            continue
        for f2, entry in _fields(plane):
            if f2 != 4:
                continue
            meta = _first(entry, 2)
            if meta is None:
                continue
            name = bytes(_first(meta, 2) or b"").decode()
            ops = {}
            for f3, stat in _fields(meta):
                proto = _first(stat, 6) if f3 == 5 else None
                module = None if proto is None else _first(proto, 1)
                if module is None:
                    continue
                for f4, comp in _fields(module):
                    if f4 != 3:
                        continue
                    for f5, instr in _fields(comp):
                        if f5 != 2:
                            continue
                        md = _first(instr, 7)
                        op = None if md is None else _first(md, 2)
                        ops[bytes(_first(instr, 1)).decode()] = (
                            "" if op is None else bytes(op).decode())
            out[name] = ops
    return out


# -- events -------------------------------------------------------------------


def inherit(events):
    """Events (name, start, end, path) with an empty path given the path of
    the innermost event that encloses them, in (start, -end) order."""
    out, stack = [], []
    for name, s, e, path in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and stack[-1][1] <= s:
            stack.pop()
        if not path and stack:
            path = stack[-1][2]
        out.append((name, s, e, path))
        stack.append((s, e, path))
    return out


def load(log_dir):
    """Leaf operations with scope paths and program events of the newest
    profile under `log_dir`, or None where it holds no device plane:
    dict(ops=[[(name, start_ns, end_ns, path)]], modules=[[(name, s, e)]],
    named=<share of distinct instructions whose op_name was found>)."""
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return None
    with open(paths[-1], "rb") as fh:
        raw = fh.read()
    op_names = hlo_op_names(raw)
    pd = ProfileData.from_serialized_xspace(raw)
    ops, modules = [], []
    seen = found = 0
    for plane in pd.planes:
        if not plane.name.startswith(DEVICE_PLANE):
            continue
        d_ops, d_mod = [], []
        for line in plane.lines:
            if line.name == "XLA Modules":
                d_mod = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events]
            elif line.name == "XLA Ops":
                d_ops = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                         for ev in line.events]
        d_mod.sort(key=lambda m: m[1])
        starts = [m[1] for m in d_mod]
        cache = {}
        events = []
        for name, s, e in d_ops:
            i = bisect.bisect_right(starts, s) - 1
            program = d_mod[i][0] if i >= 0 and s < d_mod[i][2] else ""
            key = (program, name)
            if key not in cache:
                m = _INSTR.match(name)
                op = op_names.get(program, {}).get(m.group(1)) if m else None
                seen += 1
                found += op is not None
                cache[key] = (tracing.op_name(name), scope_path(op or ""))
            label, path = cache[key]
            events.append((label, s, e, path))
        ops.append(tracing.leaves(inherit(events)))
        modules.append([(n.split("(")[0], s, e) for n, s, e in d_mod])
    if not any(ops):
        return None
    return dict(ops=ops, modules=modules, named=found / max(seen, 1))


# -- the reduction --------------------------------------------------------------


def _common(a, b):
    n = 0
    while n < min(len(a), len(b)) and a[n] == b[n]:
        n += 1
    return a[:n]


def reduce(ops, modules, window=None):
    """Time of the engine programs (`PROGRAM`) split by scope path.

    ops     : per device, leaf operations [(name, start_ns, end_ns, path)]
    modules : per device, program events [(name, start_ns, end_ns)]
    window  : (start_ns, end_ns) to clip to, or None for all of it
    Returns, averaged over the devices, {"scopes": {"a/b": {"busy_s",
    "ops", "gap_s"}}, "busy_s", "gap_s", "ops", "program_s",
    "unmapped_busy_s"}; the path "" holds what lies under no scope
    (unmapped operations, and the idle at a program's two ends).
    """
    lo, hi = window if window is not None else (float("-inf"), float("inf"))
    n_dev = max(len(ops), 1)
    busy = defaultdict(float)
    gap = defaultdict(float)
    count = defaultdict(int)
    program_ns = 0.0
    for d_ops, d_mod in zip(ops, modules):
        d_ops = sorted(d_ops, key=lambda o: o[1])
        starts = [o[1] for o in d_ops]
        for name, m0, m1 in d_mod:
            ms, me = max(m0, lo), min(m1, hi)
            if name != PROGRAM or me <= ms:
                continue
            program_ns += me - ms
            t, prev = ms, None
            for _, s, e, path in d_ops[bisect.bisect_left(starts, m0):
                                       bisect.bisect_left(starts, me)]:
                s, e = max(s, t), min(e, me)
                if e <= s:
                    continue
                if s > t:
                    gap[() if prev is None else _common(prev, path)] += s - t
                busy[path] += e - s
                count[path] += 1
                t, prev = e, path
            if me > t:
                gap[()] += me - t
    paths = sorted(set(busy) | set(gap), key=lambda p: (len(p), p))
    scopes = {
        "/".join(p): {"busy_s": busy[p] / n_dev / 1e9, "ops": count[p] / n_dev,
                      "gap_s": gap[p] / n_dev / 1e9}
        for p in paths
    }
    return {
        "scopes": scopes,
        "busy_s": sum(busy.values()) / n_dev / 1e9,
        "gap_s": sum(gap.values()) / n_dev / 1e9,
        "ops": sum(count.values()) / n_dev,
        "program_s": program_ns / n_dev / 1e9,
        "unmapped_busy_s": busy[()] / n_dev / 1e9,
    }


def under(table, scope, field="busy_s"):
    """`field` summed over every path that lies under `scope`."""
    return sum(v[field] for k, v in table["scopes"].items()
               if scope in k.split("/"))


# -- one more profiled call, for the readers --------------------------------------


def measure(reading):
    """The scope table of one profiled call of the fleet that the window's
    capture held, with its instance-rounds, once per reading (the readers
    share it); None where the window captured no call or the profile holds
    no device plane. Logs the table to stderr as one JSON line."""
    if "engine_scopes" in reading.__dict__:
        return reading.engine_scopes
    reading.engine_scopes = None
    captured = [r for r in reading.ctx.records if reading.in_capture(r["t0"], r["t1"])]
    if not captured:
        return None
    import jax

    from repro.fleet import solve_fleet

    ctx = reading.ctx
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 0
    t0 = time.perf_counter()
    try:
        with tempfile.TemporaryDirectory(prefix="bench-scopes-") as tmp:
            jax.profiler.start_trace(tmp, profiler_options=opts)
            try:
                res = solve_fleet(ctx.problems[captured[0]["pool"]], **ctx.kw)
            finally:
                jax.profiler.stop_trace()
            t1 = time.perf_counter()
            ev = load(tmp)
    except Exception:  # a reader reports nothing rather than end the run
        traceback.print_exc(file=sys.stderr)
        return None
    if ev is None:
        return None
    table = reduce(ev["ops"], ev["modules"])
    table["inst_rounds"] = int(res.iters.sum())
    table["named"] = ev["named"]
    print(json.dumps({"engine_scopes": table, "profile_s": t1 - t0,
                      "load_reduce_s": time.perf_counter() - t1}),
          file=sys.stderr, flush=True)
    reading.engine_scopes = table
    return table


def per_inst_round(reading, field, scope=None):
    """`field` ("busy_s", "gap_s" or "ops") of the engine program, or of
    everything under `scope`, per instance-round of the profiled call. None
    without a trace, and for a scope where no operation carries one."""
    if reading.trace is None:
        return None
    table = measure(reading)
    if table is None or not table["inst_rounds"]:
        return None
    if scope is None:
        total = table[field]
    else:
        if not any(table["scopes"][k]["ops"] for k in table["scopes"] if k):
            return None
        total = under(table, scope, field)
    return total / table["inst_rounds"]
