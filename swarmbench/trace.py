"""Reduce a profiler trace (``.xplane.pb``) to what the per-layer metrics
read: device busy time and idle gaps, per-op device time, collective time,
and the harness's host spans that label each gap.

Device planes are ``/device:TPU:<n>`` and their ``XLA Ops`` line holds one
event per operation that ran, named here by its HLO name (`op_name`). A
control operation such as a ``while`` loop is an event of its own that
spans the events of its body. Host spans (`SPANS`) are the harness's own,
timed with `time.time_ns` and set on the `Trace` by the harness, or, in a
trace recorded with the host tracer on, its `jax.profiler.TraceAnnotation`
events. Times are nanoseconds since the epoch.
"""
from __future__ import annotations

import bisect
import glob
import os
import re
from dataclasses import dataclass, field

SPANS = ("dispatch", "wait_gates")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
COLLECTIVE = re.compile(
    r"all-gather|all-reduce|collective-permute|reduce-scatter|all-to-all")


@dataclass
class Trace:
    ops: dict = field(default_factory=dict)    # device -> [(s, e, name)]
    spans: list = field(default_factory=list)  # [(s, e, name)]


def find(directory: str) -> str:
    """The one ``.xplane.pb`` file the profiler wrote under ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one trace under {directory}, "
                                f"found {len(paths)}")
    return paths[0]


def op_name(event_name: str) -> str:
    """An operation's HLO name: a TPU trace names each event by its whole
    HLO instruction, ``%fused_merge_all.23 = f32[4,128]{...} custom-call(...)``;
    the name is what comes before `` = ``, without the ``%``."""
    head = event_name.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def load(path: str) -> Trace:
    """Device operations and any harness spans the host plane holds, on
    the host's clock: nanoseconds since the epoch, as `time.time_ns` reads
    them (event times count from the profile's start, which the ``Task
    Environment`` plane records)."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    base = next((dict(p.stats).get("profile_start_time", 0) for p in planes
                 if p.name == "Task Environment"), 0)
    out = Trace()
    for plane in planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                out.ops[int(m.group(1))] = [
                    (base + e.start_ns, base + e.start_ns + e.duration_ns,
                     op_name(e.name)) for e in line.events]
            elif not m:
                out.spans += [(base + e.start_ns,
                               base + e.start_ns + e.duration_ns, e.name)
                              for e in line.events if e.name in SPANS]
    out.spans.sort()
    return out


def union(intervals, lo, hi):
    """Disjoint sorted intervals covering ``intervals`` clipped to
    [lo, hi]."""
    merged = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def gaps(intervals, lo, hi):
    """Idle stretches of [lo, hi] that no interval covers."""
    out, t = [], lo
    for s, e in union(intervals, lo, hi):
        if s > t:
            out.append((t, s))
        t = e
    if hi > t:
        out.append((t, hi))
    return out


def label(gap, spans, starts=None) -> str:
    """The host span that overlaps ``gap`` most, or ``host`` for none.
    ``spans`` are sorted by start and do not overlap one another (the
    harness opens them one after another on one thread); ``starts``, their
    start times, may be passed to save rebuilding them."""
    starts = [s for s, _, _ in spans] if starts is None else starts
    best, name = 0, "host"
    i = bisect.bisect_left(starts, gap[1]) - 1
    while i >= 0 and spans[i][1] > gap[0]:
        s, e, n = spans[i]
        ov = min(e, gap[1]) - max(s, gap[0])
        if ov > best:
            best, name = ov, n
        i -= 1
    return name


def window(trace: Trace):
    """[lo, hi] of the traced window: the harness's spans, or else the
    device events."""
    if trace.spans:
        return trace.spans[0][0], max(e for _, e, _ in trace.spans)
    evs = [ev for ops in trace.ops.values() for ev in ops]
    if not evs:
        return None
    return min(s for s, _, _ in evs), max(e for _, e, _ in evs)


def summarize(trace: Trace, top: int = 10) -> dict | None:
    """Busy and window seconds (mean over devices), per-op seconds, the
    longest idle gaps with their host labels, and collective seconds. None
    when the trace holds no device operation."""
    if not any(trace.ops.values()):
        return None
    lo, hi = window(trace)
    devices = sorted(trace.ops)
    starts = [s for s, _, _ in trace.spans]
    busy, coll, by_op, n_op, idle = [], [], {}, {}, []
    for d in devices:
        ops = [ev for ev in trace.ops[d] if ev[1] > lo and ev[0] < hi]
        busy.append(sum(e - s for s, e in union(ops, lo, hi)))
        coll.append(sum(e - s for s, e in
                        union([ev for ev in ops if COLLECTIVE.search(ev[2])],
                              lo, hi)))
        for s, e, n in ops:
            by_op[n] = by_op.get(n, 0) + (e - s)
            n_op[n] = n_op.get(n, 0) + 1
        idle += [(label(g, trace.spans, starts), g[1] - g[0])
                 for g in gaps(ops, lo, hi)]
    nd = len(devices)
    idle_by_label = {}
    for name, ns in idle:
        idle_by_label[name] = idle_by_label.get(name, 0) + ns / nd
    return {
        "devices": nd,
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / nd * 1e-9,
        "collective_s": sum(coll) / nd * 1e-9,
        "op_s": {n: ns / nd * 1e-9 for n, ns in by_op.items()},
        "op_n": {n: k / nd for n, k in n_op.items()},
        "device_ops": [[n, ns / nd * 1e-9] for n, ns in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[n, ns * 1e-9] for n, ns in
                      sorted(idle, key=lambda kv: -kv[1])[:top]],
        "idle_by_label_s": {n: ns * 1e-9 for n, ns in idle_by_label.items()},
    }
