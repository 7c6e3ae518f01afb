"""The one span helper: a named interval on the profiler's clock.

:func:`annotate` enters ``jax.profiler.TraceAnnotation``.  While a
profiler trace runs (``jax.profiler.trace``), the interval lands on the
trace's host plane beside the device's ops, with its keyword arguments as
the event's stats; while none runs it costs one ``TraceMe`` check (about
a microsecond).  A count known only at the end of the interval is added
with ``set_metadata(**args)`` on the entered annotation.

The coded training loop (``repro.launch.train``) and the co-sim's phase
spans (:meth:`~repro.telemetry.recorder.FleetRecorder.span`) both go
through it.
"""
from __future__ import annotations

import jax

__all__ = ["annotate"]


def annotate(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A profiler span named ``name`` with ``args`` as its stats; with a
    ``step_num`` argument, a step marker
    (``jax.profiler.StepTraceAnnotation``)."""
    if "step_num" in args:
        return jax.profiler.StepTraceAnnotation(name, **args)
    return jax.profiler.TraceAnnotation(name, **args)
