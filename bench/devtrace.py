"""Reduction of a profiler trace to device busy time, idle gaps and the
device operations that took most time.

A trace is read into flat ``Event`` records.  The device side is every
operation on the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane; the
host side is the harness's own spans, whose names start with
``bench.``.  One host span, ``bench.traced_window``, marks the window
that the reduction covers.  Busy time is the union of the operation
intervals inside that window, averaged over the device planes; an idle
gap is a stretch of the window with no operation on the device, named by
the innermost harness span that holds its midpoint.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time
from collections import defaultdict
from typing import List, NamedTuple, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."
WINDOW = "bench.traced_window"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def read_xplane(path: str) -> List[Event]:
    """The device operations and harness spans of one ``.xplane.pb``."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name != OPS_LINE:
                continue
            for ev in line.events:
                if device or ev.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, short(ev.name),
                                     float(ev.start_ns),
                                     float(ev.duration_ns)))
    return out


def short(name: str) -> str:
    """An operation's name without the HLO text after it:
    ``%fusion.12 = f32[...] fusion(...)`` becomes ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def options():
    """The profiler's options: the device's operations and the host's
    annotations, without the Python tracer and without each program's
    HLO, which holds the matrix where a program embeds it as a
    constant."""
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    return opts


def capture(fn, logdir: str, cost: Optional[dict] = None) -> List[Event]:
    """Run ``fn`` under the profiler, inside a ``bench.traced_window``
    span, and read back what the trace holds.  ``cost``, where given,
    receives the seconds of each stage and the size of the trace."""
    import jax

    t0 = time.perf_counter()
    jax.profiler.start_trace(logdir, profiler_options=options())
    t1 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(WINDOW):
            fn()
    finally:
        t2 = time.perf_counter()
        jax.profiler.stop_trace()
    t3 = time.perf_counter()
    paths = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace file under {logdir}, "
                           f"found {paths}")
    events = read_xplane(paths[0])
    if cost is not None:
        cost.update(start_s=t1 - t0, stop_s=t3 - t2,
                    read_s=time.perf_counter() - t3,
                    bytes=os.path.getsize(paths[0]), events=len(events))
    return events


def traced(fn, cost: Optional[dict] = None) -> Optional[dict]:
    """Run ``fn`` under the profiler and reduce its trace (``reduce``);
    the trace is written to a temporary directory and removed."""
    logdir = tempfile.mkdtemp(prefix="bench-trace-")
    try:
        events = capture(fn, logdir, cost)
        t0 = time.perf_counter()
        out = reduce(events)
        if cost is not None:
            cost["reduce_s"] = time.perf_counter() - t0
        return out
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


def union(intervals) -> List[tuple]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: List[list] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [tuple(m) for m in merged]


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce(events: List[Event], top: int = 10) -> Optional[dict]:
    """Busy and window seconds, the ``top`` device operations by total
    time and the ``top`` longest idle gaps, or None where the trace holds
    no device operation or no window span."""
    windows = [e for e in events if e.name == WINDOW]
    spans = [e for e in events
             if e.name.startswith(SPAN_PREFIX) and e.name != WINDOW]
    by_plane = defaultdict(list)
    for e in events:
        if DEVICE_PLANE.match(e.plane):
            by_plane[e.plane].append(e)
    if not windows or not by_plane:
        return None
    lo, hi = windows[0].start_ns, windows[0].end_ns
    busy = {}
    for plane, ops in by_plane.items():
        merged = union(_clip([(o.start_ns, o.end_ns) for o in ops], lo, hi))
        busy[plane] = merged
    if not any(busy.values()):
        return None
    busy_s = sum(sum(e - s for s, e in m) for m in busy.values()) \
        / len(busy) / 1e9
    window_s = (hi - lo) / 1e9

    first = sorted(by_plane)[0]
    per_op = defaultdict(float)
    for o in by_plane[first]:
        d = sum(e - s for s, e in _clip([(o.start_ns, o.end_ns)], lo, hi))
        if d > 0:
            per_op[o.name] += d / 1e9
    device_ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:top]

    gaps, t = [], lo
    for s, e in busy[first] + [(hi, hi)]:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    named = []
    for s, e in gaps:
        mid = (s + e) / 2
        holders = [sp for sp in spans if sp.start_ns <= mid < sp.end_ns]
        name = min(holders, key=lambda sp: sp.dur_ns).name if holders \
            else WINDOW
        named.append((name, (e - s) / 1e9))
    idle_gaps = sorted(named, key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_s, "window_s": window_s,
            "device_ops": [list(kv) for kv in device_ops],
            "idle_gaps": [list(kv) for kv in idle_gaps]}
