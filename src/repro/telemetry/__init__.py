"""Fleet telemetry subsystem (DESIGN.md §3.9).

Per-slot scheduler series, phase timing and compile accounting for the
co-simulated fleets, with a zero-cost off switch:

  * :func:`annotate` — the one span helper: a ``jax.profiler``
    annotation, on the device trace's clock (the coded training loop's
    spans and the recorder's phase spans);
  * :class:`TelemetryConfig` / :class:`FleetRecorder` — the recorder both
    engines thread through their epoch loops (``telemetry=`` on
    ``BatchedFleet`` / ``run_fleet``; attribute on ``EdgeCluster``);
  * :mod:`~repro.telemetry.metrics` — pure derived metrics (Jain
    fairness, queue-stability drift, straggler EWMA);
  * :mod:`~repro.telemetry.compilation` — named process-global compile
    counters generalizing ``scan_trace_count``;
  * :mod:`~repro.telemetry.sinks` — JSONL + in-memory event sinks;
  * :mod:`~repro.telemetry.trace` — Chrome/Perfetto trace export;
  * ``python -m repro.telemetry.report`` — fleet summary table CLI;
  * :func:`record_fleet` — the one-call "run a fleet with telemetry"
    entry point (lazily imported: it pulls in the simulator, which in
    turn imports this package).
"""
from repro.telemetry.annotation import annotate
from repro.telemetry.compilation import compile_counts, note_compile
from repro.telemetry.metrics import (fleet_fairness, jain_index,
                                     mean_queue_residual,
                                     queue_stability_drift,
                                     straggler_rate_ewma)
from repro.telemetry.recorder import (SERIES_FIELDS, FleetRecorder, Span,
                                      TelemetryConfig, phase_span)
from repro.telemetry.sinks import JsonlSink, MemorySink
from repro.telemetry.trace import chrome_trace_events, write_chrome_trace

__all__ = [
    "TelemetryConfig", "FleetRecorder", "Span", "SERIES_FIELDS",
    "phase_span", "annotate",
    "jain_index", "fleet_fairness", "mean_queue_residual",
    "queue_stability_drift", "straggler_rate_ewma",
    "note_compile", "compile_counts",
    "JsonlSink", "MemorySink",
    "chrome_trace_events", "write_chrome_trace",
    "record_fleet",
]


def record_fleet(*args, **kwargs):
    """See :func:`repro.telemetry.runner.record_fleet` (lazy import —
    keeps ``repro.sim ↔ repro.telemetry`` import order acyclic)."""
    from repro.telemetry.runner import record_fleet as _record_fleet
    return _record_fleet(*args, **kwargs)
