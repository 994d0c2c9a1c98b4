"""The program's own spans and named scopes in a JAX profiler trace, with
the device's timestamps put on the host's clock.

`CompiledProgram.run` writes host spans (`TraceAnnotation`) on the
caller's thread: `repro.run` around the call, and inside it
`repro.prepare`, `repro.salt_probe`, then `repro.compile` (a compile-cache
miss) or `repro.enqueue` (a hit).  The executor puts named scopes on the
ops it emits, `<NodeType>:<dest>/<stage>/...` (stages of a group-by:
keys, value, sort, permute, reduce, salt_fold, combine; the Pallas
segment kernel's pads and transposes: layout).  On a TPU each device op's
event metadata carries the stat `tf_op`: the op's HLO `op_name`,
`jit(<fn>)/<scopes>/<primitive>:`, a fusion carrying its root's.
`jax.profiler.ProfileData` gives events but not event-metadata stats, so
those are read from the `.xplane.pb`'s protobuf wire format here.

The profiler puts device and host events on one clock only roughly, so
before device idle time is set beside the host's spans each device's
timestamps are shifted by the offset its program runs imply
(`clock_offset`).

A trace from a program without these spans and scopes (the parent of the
change that added them) reads as having none: the readers built on this
module then return None.  `devtrace` keeps reading the benchmark's
`bench.*` spans, on the trace's own timestamps; this module reads
`repro.*` and the window.
"""
from __future__ import annotations

import dataclasses
import os
import re
import sys

import devtrace

# where `bench/run.py --trace 1` writes its trace
TRACE_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "traces")
SPAN_PREFIX = "repro."
RUN_SPAN = "repro.run"
OUTSIDE = "outside repro.run"
NO_OP_NAME = "(no op_name)"            # ops XLA made without metadata
NODE = re.compile(r"^[A-Z]\w*:")       # a plan node's scope, `SegmentReduce:C`
TF_OP = "tf_op"
MODULES_LINE = "XLA Modules"           # device: one event per program run
ENQUEUED = "DoEnqueueProgram"          # host, TPU runtime: a run enqueued
COMPLETED = "CompleteCallbacks"        # host, TPU runtime: a run completed


@dataclasses.dataclass
class ProgTrace:
    window: tuple | None   # (start_ns, end_ns) of the bench.window span
    devices: list          # per device: [(op name, scope path or None
    #                        when the op has no tf_op, start, end)], on the
    #                        host's clock (see `clock_offset`)
    spans: list            # host [(repro.* name, start_ns, end_ns)]
    offsets: list = dataclasses.field(default_factory=list)
    #                        per device: `clock_offset`'s (ns added, upper
    #                        bound, runs agreeing, runs paired) or None


# ---- the xplane's wire format (XSpace / XPlane / XEventMetadata / XStat) --

def _varint(b, i: int) -> tuple:
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of one message; length-delimited values are
    memoryview slices, so skipping a large field copies nothing."""
    i, n = 0, len(b)
    while i < n:
        key, i = _varint(b, i)
        f, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(b, i)
        elif wire == 1:
            v, i = b[i:i + 8], i + 8
        elif wire == 5:
            v, i = b[i:i + 4], i + 4
        elif wire == 2:
            size, i = _varint(b, i)
            v, i = b[i:i + size], i + size
        else:
            raise ValueError(f"xplane: wire type {wire} not understood")
        yield f, v


def _map_value(entry):
    """The value (field 2) of a protobuf map entry."""
    return next((v for f, v in _fields(entry) if f == 2), b"")


def op_scopes(path: str) -> dict:
    """HLO text of each device op (the event name ProfileData gives) ->
    its `tf_op` stat, from the device planes' event metadata."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, pv in _fields(plane):
            if pf == 2:
                name = bytes(pv).decode()
            elif pf == 4:
                events.append(pv)
            elif pf == 5:
                sm = dict(_fields(_map_value(pv)))
                stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
        if not devtrace.DEVICE_PLANE.match(name):
            continue
        tf_op = [k for k, v in stat_names.items() if v == TF_OP]
        for ev in events:
            md = _map_value(ev)
            text, value = "", None
            for mf, mv in _fields(md):
                if mf == 2:
                    text = bytes(mv).decode()
                elif mf == 5:
                    st = dict(_fields(mv))
                    if st.get(1) in tf_op:
                        value = bytes(st[5]).decode() if 5 in st \
                            else stat_names.get(st.get(7), "")
            if value is not None:
                out[text] = value
    return out


def scope_path(tf_op: str) -> str:
    """`jit(traced)/SegmentReduce:C/permute/gather:` ->
    `SegmentReduce:C/permute`: the scopes between the outer jit and the
    primitive."""
    parts = tf_op.split("/")
    return "/".join(parts[1:-1])


def clock_offset(runs: dict, enqueued: dict, completed: dict):
    """What to add to one device's timestamps to put them on the host's
    clock, which the profiler does not align exactly (on a TPU v5 lite
    the device's read 0.3-1.4 ms early).  A program run starts on the
    device after the host began to enqueue it and ends before the host
    runs its completion callbacks, so each run bounds the offset to an
    interval; `runs` maps run id -> (device start, end), the others run
    id -> host start.  The offset is the least value inside the most
    intervals (an idle device starts a run at once; a run paired wrongly
    is outvoted).  Returns (offset, its upper bound, runs agreeing, runs
    paired), or None when no run pairs up."""
    paired = [(enqueued[r] - s, completed[r] - e if r in completed
               else float("inf"))
              for r, (s, e) in runs.items()
              if r is not None and r in enqueued]
    iv = [(lo, hi) for lo, hi in paired if lo <= hi]
    if not iv:
        return None
    # sweep: an interval opens at its lower end, closes after its upper
    ends = sorted([(lo, 0) for lo, _ in iv] + [(hi, 1) for _, hi in iv])
    best, depth, at = -1, 0, None
    for x, closing in ends:
        depth += -1 if closing else 1
        if depth > best:
            best, at = depth, x
    upper = min(hi for lo, hi in iv if lo <= at <= hi)
    return at, upper, best, len(paired)


def load(path: str) -> ProgTrace:
    from jax.profiler import ProfileData
    scopes = op_scopes(path)
    pd = ProfileData.from_file(path)
    devices, runs, spans, wins = [], [], [], []
    host = {ENQUEUED: {}, COMPLETED: {}}    # (run id, device) -> start
    for plane in pd.planes:
        if devtrace.DEVICE_PLANE.match(plane.name):
            ops, mods = [], {}
            for line in plane.lines:
                if line.name == devtrace.OPS_LINE:
                    ops += [(devtrace.op_name(e.name),
                             scope_path(scopes[e.name])
                             if e.name in scopes else None,
                             e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events]
                elif line.name == MODULES_LINE:
                    for e in line.events:
                        mods[dict(e.stats).get("run_id")] = (
                            e.start_ns, e.start_ns + e.duration_ns)
            devices.append(ops)
            runs.append((int(plane.name.rsplit(":", 1)[1]), mods))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    span = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append(span)
                    elif e.name == devtrace.WINDOW_SPAN:
                        wins.append(span)
                    elif e.name in host:
                        st = dict(e.stats)
                        key = (st.get("run_id"), st.get("device_ordinal", 0))
                        got = host[e.name]
                        got[key] = min(got.get(key, e.start_ns), e.start_ns)
    offsets = []
    for i, (ordinal, mods) in enumerate(runs):
        enq, done = ({r: t for (r, d), t in host[k].items() if d == ordinal}
                     for k in (ENQUEUED, COMPLETED))
        off = clock_offset(mods, enq, done)
        offsets.append(off)
        if off is not None:
            devices[i] = [(n, sc, s + off[0], e + off[0])
                          for n, sc, s, e in devices[i]]
    window = (min(s for _, s, _ in wins), max(e for _, _, e in wins)) \
        if wins else None
    return ProgTrace(window=window, devices=devices, spans=sorted(
        spans, key=lambda s: (s[1], -s[2])), offsets=offsets)


# ---- reductions ----

def node_stage(scope: str) -> tuple:
    """The innermost plan-node scope of a path and the scopes below it
    (the stage), nested jits left out:
    `SeqLoop/while/body/SegmentReduce:C/reduce/jit(segment_reduce)/layout`
    -> (`SegmentReduce:C`, `reduce/layout`); ("", "") outside every
    node."""
    parts = scope.split("/") if scope else []
    idx = [i for i, p in enumerate(parts) if NODE.match(p)]
    if not idx:
        return "", ""
    i = idx[-1]
    return parts[i], "/".join(p for p in parts[i + 1:]
                              if not p.startswith(("jit(", "pjit(")))


def scoped(pt: ProgTrace) -> bool:
    """Whether any device op carries a plan node's scope."""
    return any(node_stage(sc)[0] for ops in pt.devices
               for _, sc, _, _ in ops)


def by_stage(pt: ProgTrace) -> dict:
    """(innermost node, stage) -> seconds in the window, mean per device;
    control-flow ops, whose bodies have events of their own, are left out
    as in `devtrace.summarize`."""
    lo, hi = pt.window
    out: dict = {}
    for ops in pt.devices:
        for name, sc, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0 and devtrace.opcode(name) not in devtrace.CONTAINERS:
                k = node_stage(sc) if sc is not None else (NO_OP_NAME, "")
                out[k] = out.get(k, 0.0) + d
    return {k: v * 1e-9 / max(len(pt.devices), 1) for k, v in out.items()}


def span_seconds(pt: ProgTrace, name: str) -> float:
    """Seconds of the spans called `name` inside the window."""
    lo, hi = pt.window
    return sum(max(0, min(e, hi) - max(s, lo))
               for n, s, e in pt.spans if n == name) * 1e-9


def innermost(spans: list) -> list:
    """Properly nested spans of one thread, sorted by (start, -end), as
    disjoint [(innermost span's name, start, end)] pieces."""
    out, stack, t = [], [], None

    def close_until(limit):
        nonlocal t
        while stack and stack[-1][2] <= limit:
            n, _, e = stack.pop()
            if t < e:
                out.append((n, t, e))
                t = e
    for name, s, e in spans:
        close_until(s)
        if stack and t < s:
            out.append((stack[-1][0], t, s))
        stack.append((name, s, e))
        t = s
    close_until(float("inf"))
    return out


def idle_by_span(pt: ProgTrace) -> dict:
    """Device idle seconds in the window (mean per device) by the
    innermost repro.* span the host was in; idle time outside every
    `repro.run` goes under OUTSIDE (the harness, the completion wait)."""
    lo, hi = pt.window
    pieces = [(n, max(s, lo), min(e, hi)) for n, s, e in innermost(pt.spans)
              if min(e, hi) > max(s, lo)]
    out: dict = {}
    for ops in pt.devices:
        gaps = devtrace.idle(devtrace.union(
            [(s, e) for _, _, s, e in ops], lo, hi), lo, hi)
        total = sum(e - s for s, e in gaps)
        j, inside = 0, 0.0
        for gs, ge in gaps:
            while j < len(pieces) and pieces[j][2] <= gs:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][1] < ge:
                n, s, e = pieces[k]
                ov = min(e, ge) - max(s, gs)
                if ov > 0:
                    out[n] = out.get(n, 0.0) + ov
                    inside += ov
                k += 1
        out[OUTSIDE] = out.get(OUTSIDE, 0.0) + total - inside
    nd = max(len(pt.devices), 1)
    return {k: v * 1e-9 / nd for k, v in out.items()}


def run_idle_seconds(pt: ProgTrace) -> float:
    """Device idle seconds (mean per device) while the host was inside
    `repro.run`."""
    return sum(v for k, v in idle_by_span(pt).items() if k != OUTSIDE)


# ---- what the readers (bench/metrics/<name>.py) share ----

def log_tables(pt: ProgTrace, jobs: int) -> None:
    """The run's log (stderr): device ms per job by node and stage, idle
    seconds by innermost repro.* span, and each span's count, median and
    longest (whether a mean is every job's or one stall's)."""
    rows = sorted(by_stage(pt).items(), key=lambda kv: -kv[1])
    lines = ["device ms per job by scope (node / stage):"]
    lines += [f"  {node or '(no plan node)'} / {stage or '-'}: "
              f"{1e3 * v / max(jobs, 1)}" for (node, stage), v in rows]
    lines.append("device idle s in the window by innermost repro.* span:")
    lines += [f"  {k}: {v}" for k, v in sorted(
        idle_by_span(pt).items(), key=lambda kv: -kv[1])]
    for i, off in enumerate(pt.offsets):
        lines.append(f"device {i} clock: " + (
            "no program run pairs with the host's" if off is None else
            f"+{off[0] * 1e-6} ms to the host's (at most {off[1] * 1e-6}"
            f" ms; {off[2]} of {off[3]} runs agree)"))
    lines.append("repro.* spans in the window: count, median ms, max ms:")
    lo, hi = pt.window
    for name in sorted({n for n, _, _ in pt.spans}):
        d = sorted((e - s) * 1e-6 for n, s, e in pt.spans
                   if n == name and lo <= s and e <= hi)
        if d:
            lines.append(f"  {name}: {len(d)}, {d[len(d) // 2]}, {d[-1]}")
    print("\n".join(lines), file=sys.stderr, flush=True)


def of(reading) -> ProgTrace | None:
    """The program's spans and scopes in the traced window a reader is
    given (`run.Reading`), loaded once per reading; None when the run was
    not traced, or the trace holds no repro.* span or no window (a
    program without the spans)."""
    if reading.trace is None or not reading.jobs:
        return None
    if not hasattr(reading, "_progtrace"):
        pt = load(devtrace.latest_xplane(TRACE_DIR))
        if pt.window is None or not pt.spans or not pt.devices:
            pt = None
        else:
            log_tables(pt, reading.jobs)
        reading._progtrace = pt
    return reading._progtrace
