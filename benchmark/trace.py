"""Reduce a JAX profiler trace (`*.xplane.pb`) of the traced part of a
window to device busy time, device-program time and idle gaps.

- The traced part is the host span `bench.window` that the harness opens
  around it; every device interval is clipped to it. Host and device
  events share one clock in the trace.
- A device is a plane named `/device:TPU:<n>`. What runs on it is read
  from its `XLA Modules` line: one event per program run (`jit_check`,
  `jit_check_chunk`, …). Its `XLA Ops` line is not read: the search's
  loops put about a million op events a second there, which Python
  cannot walk inside a run's time limit (PERF.md §3). Busy time is the
  union of the program intervals; program time is the sum of their
  durations. Both are averaged over the devices.
- A program is named without its fingerprint: `jit_check(7001…)` is
  `jit_check`.
- An idle gap is a stretch of the traced part in which no program ran on
  device 0. It is labelled by the `bench.*` host span that holds its
  midpoint: `bench.check` (inside an entry call: the program's host
  work) or `bench.between` (the harness's own work between calls).
"""

from __future__ import annotations

import bisect
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
PROGRAMS = "XLA Modules"
SPAN_PREFIX = "bench."
TOP = 10


def program_name(name):
    """`jit_check(7001927594704723328)` -> `jit_check`."""
    return re.sub(r"\(\d+\)$", "", name)


def read_planes(path):
    """{'spans': [(name, start_ns, end_ns)], 'devices': {plane: [(name,
    start_ns, end_ns)]}} from an xplane file."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(str(path))
    spans, devices = [], {}
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [
                (e.name, e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines if line.name == PROGRAMS
                for e in line.events]
        elif plane.name.startswith("/host:"):
            spans.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                         for line in plane.lines for e in line.events
                         if e.name.startswith(SPAN_PREFIX))
    return {"spans": spans, "devices": devices}


def _union(intervals):
    """Merged, sorted [start, end] intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def _label(spans, starts, t):
    """The span (of the window's non-overlapping inner spans, sorted by
    start) holding time t, or the window's."""
    i = bisect.bisect_right(starts, t) - 1
    if i >= 0 and t < spans[i][2]:
        return spans[i][0]
    return "bench.window"


def reduce(planes):
    """Seconds of busy and program time per device, the traced window,
    the programs that took most time and the longest idle gaps. None
    where the trace holds no window or no program ran."""
    spans = planes["spans"]
    windows = [(s, e) for n, s, e in spans if n == "bench.window"]
    devices = planes["devices"]
    if not windows or not devices:
        return None
    lo, hi = windows[0]
    inner = sorted((sp for sp in spans if sp[0] != "bench.window"
                    and sp[2] > lo and sp[1] < hi), key=lambda sp: sp[1])
    starts = [sp[1] for sp in inner]
    n = len(devices)
    busy = 0.0
    by_name = {}
    gaps = []
    for i, plane in enumerate(sorted(devices)):
        evs = [(nm, max(s, lo), min(e, hi)) for nm, s, e in devices[plane]
               if e > lo and s < hi]
        merged = _union((s, e) for _, s, e in evs)
        busy += sum(e - s for s, e in merged)
        for nm, s, e in evs:
            nm = program_name(nm)
            by_name[nm] = by_name.get(nm, 0.0) + (e - s)
        if i == 0:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for s, e in zip(edges[::2], edges[1::2]):
                if e > s:
                    gaps.append((_label(inner, starts, (s + e) / 2), e - s))
    if busy <= 0:
        return None
    ns = 1e-9
    top = sorted(by_name.items(), key=lambda kv: -kv[1])[:TOP]
    gaps.sort(key=lambda g: -g[1])
    idle = {}
    for label, g in gaps:
        idle[label] = idle.get(label, 0.0) + g * ns
    return {
        "devices": n,
        "window_s": (hi - lo) * ns,
        "busy_s": busy * ns / n,
        "program_s": sum(by_name.values()) * ns / n,
        "idle_by_label": idle,
        "breakdown": {
            "device_ops": [[nm, t * ns / n] for nm, t in top],
            "idle_gaps": [[label, g * ns] for label, g in gaps[:TOP]],
        },
    }
