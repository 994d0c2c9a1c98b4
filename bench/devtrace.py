"""Reduction of a JAX profiler trace to device busy time, op times and idle
gaps attributed to the benchmark's own host spans.

The loader reads the `.xplane.pb` that `jax.profiler.trace` writes, with
`jax.profiler.ProfileData` alone.  Device planes are `/device:TPU:<n>`; on
each, the line `XLA Ops` holds one event per HLO op executed, named by the
op's HLO text (`%sort.23 = (s32[...]) sort(...), ...`), of which the op's
name (`sort.23`) is kept.  A control-flow op (`while`, `conditional`,
`call`) spans the ops of its body, which have events of their own: it
counts towards busy time but not among the op times.  Host spans are the
events named `bench.*` on the host planes (`TraceAnnotation`).  Everything
after loading works on plain (name, start_ns, end_ns) tuples, so the tests
can check it on a committed trace and on hand-made events.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"
CONTAINERS = ("while", "conditional", "call")
NO_SPAN = "host:no-bench-span"
# HLO collectives, their async -start/-done halves and the fusions XLA
# names after them
COLLECTIVE = re.compile(r"^(all-reduce|reduce-scatter|all-gather|"
                        r"collective-permute|all-to-all)")


@dataclasses.dataclass
class Trace:
    devices: list      # per device: [(op name, start_ns, end_ns)]
    spans: list        # host [(span name, start_ns, end_ns)]


@dataclasses.dataclass
class Summary:
    window_s: float    # the traced window (bench.window span)
    busy_s: float      # union of op intervals in the window, mean/device
    ops_s: dict        # op name -> seconds in the window, mean per device
    gaps: list         # [(host span, seconds)] idle gaps, longest first


def latest_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str) -> Trace:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, spans = [], []
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            ops = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops += [(op_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns)
                            for e in line.events]
            devices.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                          for e in line.events
                          if e.name.startswith(SPAN_PREFIX)]
    return Trace(devices, spans)


def op_name(hlo_text: str) -> str:
    """`%fusion.39 = s32[...] fusion(...), ...` -> `fusion.39`."""
    return hlo_text.split(" = ", 1)[0].lstrip("%")


def opcode(name: str) -> str:
    """`fusion.39` -> `fusion`."""
    return re.sub(r"\.\d+$", "", name)


def union(intervals, lo: float, hi: float) -> list:
    """Disjoint sorted intervals covering the parts of `intervals` inside
    [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def idle(busy: list, lo: float, hi: float) -> list:
    """The complement of sorted disjoint `busy` within [lo, hi]."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def attribute(gap, spans) -> str:
    """The innermost-by-overlap bench span the host was in during `gap`:
    the one that overlaps it most, the window itself counting only when no
    other does."""
    best, most = NO_SPAN, 0.0
    for name, s, e in spans:
        if name == WINDOW_SPAN:
            continue
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > most:
            best, most = name, ov
    return best


def summarize(tr: Trace) -> Summary:
    wins = [(s, e) for n, s, e in tr.spans if n == WINDOW_SPAN]
    if not wins or not tr.devices:
        raise ValueError("trace holds no bench.window span or no device")
    lo, hi = min(s for s, _ in wins), max(e for _, e in wins)
    nd = len(tr.devices)
    busy_ns, ops_ns, gaps = 0.0, {}, []
    for di, ops in enumerate(tr.devices):
        cover = union([(s, e) for _, s, e in ops], lo, hi)
        busy_ns += sum(e - s for s, e in cover)
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0 and opcode(name) not in CONTAINERS:
                ops_ns[name] = ops_ns.get(name, 0.0) + d
        if di == 0:
            gaps = [(attribute(g, tr.spans), (g[1] - g[0]) * 1e-9)
                    for g in idle(cover, lo, hi)]
    gaps.sort(key=lambda g: -g[1])
    return Summary(window_s=(hi - lo) * 1e-9, busy_s=busy_ns * 1e-9 / nd,
                   ops_s={k: v * 1e-9 / nd for k, v in ops_ns.items()},
                   gaps=gaps)


def op_seconds(summary: Summary, pattern) -> float:
    """Seconds (mean per device) of the ops whose name matches."""
    return sum(v for k, v in summary.ops_s.items() if pattern.search(k))


def breakdown(summary: Summary, top: int = 10) -> dict:
    ops = sorted(summary.ops_s.items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in summary.gaps[:top]]}
