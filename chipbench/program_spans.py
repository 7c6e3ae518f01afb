"""The program's own spans in a profiler trace (``.xplane.pb``).

For every host event whose name starts with ``prefix`` (``coded.``: the
spans that ``repro.launch.train.train_coded`` opens through
``repro.telemetry.annotate``), by name:

    count     events of that name
    seconds   each event's duration, in trace order
    self_s    each event's duration less the spans of the prefix nested
              directly inside it on its thread
    args      each event's stats (the span's counters), as a dict
    idle_s    device idle time inside the union of the name's events:
              that union, less the merged device-op intervals, clipped to
              the traced window and averaged over devices; None in a
              trace with no device plane (a CPU trace)

The window and the device's busy intervals are taken as
``trace_reduce.reduce_profile`` takes them: the host span ``window``, and
each device plane's ``XLA Ops`` line (or ``XLA Modules``).  It reads the
trace with ``jax.profiler.ProfileData`` and nothing else.
"""
from __future__ import annotations

import os
from collections import defaultdict

from chipbench.trace_reduce import (MODULES_LINE, OPS_LINE, WINDOW, _clip,
                                    find_trace, merged)

PREFIX = "coded."


def _measure(intervals) -> float:
    return sum(e - s for s, e in intervals)


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted disjoint interval lists."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            total += hi - lo
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _self_times(events) -> list:
    """Each event's duration less its direct children's, for the events
    of one thread (``(start, end, ...)`` tuples, nested or disjoint)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][0], -events[i][1]))
    own = [events[i][1] - events[i][0] for i in range(len(events))]
    stack: list = []
    for i in order:
        s, e = events[i][0], events[i][1]
        while stack and events[stack[-1]][1] < e:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def program_spans(profile, prefix: str = PREFIX) -> dict:
    """Reduce a loaded ``ProfileData`` (see module docstring)."""
    threads, windows, devices = [], [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: [(float(e.start_ns),
                                float(e.start_ns) + float(e.duration_ns))
                               for e in ln.events] for ln in plane.lines}
            ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
            if ops:
                devices.append(ops)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                evs = []
                for e in ln.events:
                    s = float(e.start_ns)
                    ev = (s, s + float(e.duration_ns), e.name)
                    if e.name == WINDOW:
                        windows.append(ev)
                    elif e.name.startswith(prefix):
                        evs.append(ev + (dict(e.stats),))
                if evs:
                    threads.append(evs)

    if windows:
        lo, hi = windows[0][0], windows[0][1]
    elif devices:
        lo = min(s for ops in devices for s, _ in ops)
        hi = max(e for ops in devices for _, e in ops)
    else:
        lo = hi = None
    busy = [merged(_clip(ops, lo, hi)) for ops in devices]

    found = sorted((ev + (own,) for evs in threads
                    for ev, own in zip(evs, _self_times(evs))),
                   key=lambda ev: ev[:2])
    out: dict = defaultdict(lambda: {"count": 0, "seconds": [],
                                     "self_s": [], "args": []})
    intervals = defaultdict(list)
    for s, e, name, args, own in found:
        rec = out[name]
        rec["count"] += 1
        rec["seconds"].append((e - s) * 1e-9)
        rec["self_s"].append(own * 1e-9)
        rec["args"].append(args)
        intervals[name].append((s, e))
    for name, rec in out.items():
        if not busy:
            rec["idle_s"] = None
            continue
        union = merged(_clip(intervals[name], lo, hi))
        rec["idle_s"] = sum(_measure(union) - _overlap(union, b)
                            for b in busy) / len(busy) * 1e-9
    return dict(out)


def read_file(path: str, prefix: str = PREFIX) -> dict:
    """:func:`program_spans` of the trace at ``path`` (a file, or a
    directory a trace was written under)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_trace(path)
    return program_spans(ProfileData.from_file(path), prefix)
