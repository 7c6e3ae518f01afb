"""Process-global compile accounting (DESIGN.md §3.9).

Generalizes the ``scan_trace_count`` probe of ``repro.sim.batched`` into a
*named* counter registry: any site whose function body executes at jax
trace time (and therefore once per compilation, never per compiled call)
reports here via :func:`note_compile`.  Registered sites today:

  * ``comm_scan`` — the batched fleet engine's chunk-scan body
    (``repro.sim.batched._chunk_runner``);
  * ``schedule_slot`` — every retrace of the P4–P7 per-slot kernel
    (``repro.core.lyapunov.scheduler``; the oracle's per-cluster jit and
    the batched engine's vmapped scan body both land here).

The registry is intentionally dumb — a ``Counter`` plus a subscription to
the scheduler's trace hook — so importing it costs nothing and recording
is trace-time-only: a compiled steady-state fleet run never touches it.
Recorders snapshot the counters at construction and report the delta
(:meth:`~repro.telemetry.recorder.FleetRecorder.compile_delta`), turning
"how many recompiles did this sweep trigger?" into a first-class
telemetry quantity instead of a test-only probe.
"""
from __future__ import annotations

from collections import Counter
from typing import Dict

__all__ = ["note_compile", "compile_counts"]

_counts: Counter = Counter()


def note_compile(name: str) -> None:
    """Record one (re)trace of the named compilation site.  Call this
    from inside a to-be-jitted function body: it executes while jax
    traces — i.e. once per compilation — and never in compiled code."""
    _counts[str(name)] += 1


def compile_counts() -> Dict[str, int]:
    """Snapshot of all compile counters since process start."""
    return dict(_counts)


# Subscribe to the scheduler's trace hook so every schedule_slot retrace
# is accounted without the core layer importing telemetry.
from repro.core.lyapunov import scheduler as _scheduler  # noqa: E402

_scheduler.on_schedule_trace(note_compile)
