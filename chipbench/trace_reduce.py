"""From a profiler trace (``.xplane.pb``) to the benchmark's device numbers.

    busy_s        union of the intervals in which an operation ran on a
                  device, inside the traced window, averaged over devices
    window_s      length of the traced window: the host span named
                  ``window`` (the harness's own annotation), or the span
                  of all events when there is none
    idle_share    1 - busy_s / window_s
    programs      device seconds per jitted program (the ``XLA Modules``
                  line of each device plane)
    ops           device seconds per operation name (the ``XLA Ops`` line)
    gaps          idle device time, attributed to the innermost harness
                  annotation on the host that covers the gap's midpoint

It reads the trace with ``jax.profiler.ProfileData`` and nothing else.
"""
from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict
from typing import Iterable

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW = "window"


def find_trace(directory: str) -> str:
    """The one ``.xplane.pb`` that a ``jax.profiler`` trace wrote under
    ``directory``."""
    found = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {directory}")
    return found[-1]


def _events(line) -> list:
    return [(float(e.start_ns), float(e.start_ns) + float(e.duration_ns),
             e.name) for e in line.events]


def merged(intervals: Iterable[tuple]) -> list:
    """``(start, end)`` intervals merged into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def _clip(intervals, lo, hi) -> list:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def reduce_profile(profile, annotations: Iterable[str] = (),
                   top: int = 10) -> dict:
    """Reduce a loaded ``ProfileData`` (see module docstring)."""
    annotations = set(annotations) | {WINDOW}
    devices, host = [], []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: _events(ln) for ln in plane.lines}
            if lines.get(OPS_LINE) or lines.get(MODULES_LINE):
                devices.append(lines)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                host += [ev for ev in _events(ln) if ev[2] in annotations]
    if not devices:
        raise ValueError("the trace has no device plane with XLA ops")

    windows = [ev for ev in host if ev[2] == WINDOW]
    if windows:
        lo, hi = windows[0][0], windows[0][1]
    else:
        spans = [ev for lines in devices for evs in lines.values()
                 for ev in evs]
        lo, hi = min(s for s, _, _ in spans), max(e for _, e, _ in spans)
    window_ns = hi - lo

    busy, programs, ops = [], defaultdict(float), defaultdict(float)
    busy_intervals = []
    for lines in devices:
        op_events = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        iv = merged(_clip([(s, e) for s, e, _ in op_events], lo, hi))
        busy_intervals.append(iv)
        busy.append(sum(e - s for s, e in iv))
        for s, e, name in lines.get(MODULES_LINE, []):
            programs[name] += (min(e, hi) - max(s, lo)) if e > lo and s < hi \
                else 0.0
        for s, e, name in lines.get(OPS_LINE, []):
            if e > lo and s < hi:
                ops[name] += min(e, hi) - max(s, lo)

    gaps = defaultdict(float)
    labels = sorted((ev for ev in host if ev[2] != WINDOW),
                    key=lambda ev: ev[0])
    starts = [ev[0] for ev in labels]
    for iv in busy_intervals:
        edges = [lo] + [x for s, e in iv for x in (s, e)] + [hi]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 > g0:
                gaps[_label(labels, starts, (g0 + g1) / 2)] += g1 - g0
    n = len(devices)
    sec = 1e-9
    return {
        "busy_s": sum(busy) / n * sec,
        "window_s": window_ns * sec,
        "idle_share": 1.0 - (sum(busy) / n) / window_ns if window_ns else None,
        "devices": n,
        "programs": _top(programs, top, n),
        "ops": _top(ops, top, n),
        "gaps": _top(gaps, top, n),
    }


def _label(labels, starts, t) -> str:
    """The innermost (latest-starting) annotation covering time ``t``."""
    for j in range(bisect.bisect_right(starts, t) - 1, -1, -1):
        s, e, name = labels[j]
        if s <= t <= e:
            return name
    return "unannotated"


def short_name(name: str, width: int = 120) -> str:
    """An HLO op's trace name, cut to ``width`` characters: its
    instruction name and the start of its result shape."""
    return name if len(name) <= width else name[:width - 3] + "..."


def _top(d: dict, k: int, n: int) -> list:
    return [[short_name(name), ns * 1e-9 / n]
            for name, ns in sorted(d.items(), key=lambda kv: -kv[1])[:k]]


def reduce_file(path: str, annotations: Iterable[str] = (),
                top: int = 10) -> dict:
    """:func:`reduce_profile` of the trace at ``path`` (a file, or a
    directory a trace was written under)."""
    from jax.profiler import ProfileData
    if os.path.isdir(path):
        path = find_trace(path)
    return reduce_profile(ProfileData.from_file(path), annotations, top)
