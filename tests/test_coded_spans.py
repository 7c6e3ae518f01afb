"""Profiler spans of the coded training loop and of the co-sim's phases.

``train_coded`` opens its ``coded.*`` spans through
``repro.telemetry.annotate``; under ``jax.profiler.trace`` they land on
the trace's host plane, once a step, nested as the step runs, with the
slot counters on ``coded.batch``.  The co-sim recorder's phase spans go
through the same helper.  Nothing here changes what is computed: the
losses are bitwise the same with the profiler on and off.
"""
import glob
import math
import os

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.launch import train as T
from repro.launch.train import TINY, train_coded
from repro.models import transformer as tfm
from repro.optim import adamw
from repro.sim import build_cluster, scenario_spec
from repro.telemetry import FleetRecorder, annotate

STEPS = 3
WORKERS, SLOTS, BATCH, SEQ = 6, 15, 1, 16
INNER = ("coded.plan", "coded.batch", "coded.device_step",
         "coded.loss_fetch")
BATCH_PARTS = ("coded.batch.data", "coded.batch.layout", "coded.batch.h2d")


def host_spans(trace_dir, prefixes) -> list:
    """``(start_ns, end_ns, name, args)`` of the trace's host events whose
    name starts with one of ``prefixes``, in start order."""
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(prefixes):
                    s = float(e.start_ns)
                    out.append((s, s + float(e.duration_ns), e.name,
                                dict(e.stats)))
    return sorted(out, key=lambda ev: ev[:2])


def run_steps():
    opt = adamw(lr=1e-3)
    params = tfm.init_params(TINY, jax.random.PRNGKey(0))
    return list(train_coded(TINY, opt, params, opt.init(params),
                            steps=STEPS, batch=BATCH, seq=SEQ,
                            workers=WORKERS, n_slots=SLOTS))


def rows_recorded(mp, rows: list):
    """Wrap the trainer's step so that each call's ``aux["rows"]`` lands
    in ``rows``; what the step computes and returns is unchanged."""
    make = T.coded_step_fn

    def coded_step_fn(*a, **k):
        step = make(*a, **k)

        def recorded(*args):
            out = step(*args)
            rows.append(int(out[2]["rows"]))
            return out
        return recorded
    mp.setattr(T, "coded_step_fn", coded_step_fn)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """The same steps untraced, then under the profiler, with the rows
    each traced step computed."""
    plain = run_steps()
    trace_dir = tmp_path_factory.mktemp("coded-trace")
    rows = []
    with pytest.MonkeyPatch.context() as mp:
        rows_recorded(mp, rows)
        with jax.profiler.trace(str(trace_dir)):
            recs = run_steps()
    return plain, recs, host_spans(trace_dir, "coded."), rows


def by_name(spans, name):
    return [ev for ev in spans if ev[2] == name]


def inside(inner, outer) -> bool:
    return outer[0] <= inner[0] and inner[1] <= outer[1]


def test_each_span_once_a_step_and_nested(traced):
    _, recs, spans, _ = traced
    steps = by_name(spans, "coded.step")
    assert [ev[3]["step_num"] for ev in steps] == [r.step for r in recs]
    for name in INNER + BATCH_PARTS:
        assert len(by_name(spans, name)) == STEPS, name
    for i, step in enumerate(steps):
        phases = [by_name(spans, name)[i] for name in INNER]
        assert all(inside(ev, step) for ev in phases)
        # plan, batch, device step, loss fetch: one after another
        assert all(a[1] <= b[0] for a, b in zip(phases, phases[1:]))
        batch = phases[1]
        parts = [by_name(spans, name)[i] for name in BATCH_PARTS]
        assert all(inside(ev, batch) for ev in parts)
        assert all(a[1] <= b[0] for a, b in zip(parts, parts[1:]))


def test_batch_counters_follow_the_plan(traced):
    _, recs, spans, _ = traced
    batches = by_name(spans, "coded.batch")
    data = by_name(spans, "coded.batch.data")
    for rec, ev, data_ev in zip(recs, batches, data):
        args = ev[3]
        sp = rec.epoch.plan.slot_partition
        used = sp[sp >= 0]
        parts = len(np.unique(used))
        assert args["step"] == rec.step
        assert args["slots"] == sp.size == WORKERS * SLOTS
        assert args["used_slots"] == used.size
        assert args["used_slots"] + args["padding_slots"] == args["slots"]
        assert args["partitions"] == parts == data_ev[3]["partitions"]
        assert args["slot_tokens"] == math.prod(sp.shape) * BATCH * SEQ
        assert args["partition_tokens"] == parts * BATCH * SEQ
        assert args["stage2"] == int(rec.epoch.stage2_triggered)
        assert args["decode_ok"] == int(rec.epoch.decode_ok)


def test_batch_counts_the_rows_the_step_computed(traced):
    _, recs, spans, rows = traced
    batches = by_name(spans, "coded.batch")
    assert len(rows) == len(batches) == STEPS
    for rec, ev, step_rows in zip(recs, batches, rows):
        args = ev[3]
        nnz = int(np.count_nonzero(rec.epoch.weights))
        assert args["computed_rows"] == step_rows \
            == (1 if nnz <= SLOTS else WORKERS)
        assert args["computed_slots"] == step_rows * SLOTS
        assert args["used_slots"] >= nnz


def test_device_step_span_is_the_step_seconds(traced):
    _, recs, spans, _ = traced
    for rec, ev in zip(recs, by_name(spans, "coded.device_step")):
        assert abs((ev[1] - ev[0]) * 1e-9 - rec.seconds) < 1e-3


def test_losses_bitwise_equal_with_profiler_on_and_off(traced):
    plain, recs, _, _ = traced
    assert [r.loss for r in recs] == [r.loss for r in plain]
    assert all(math.isfinite(r.loss) for r in recs)


def test_annotate_marks_steps():
    assert isinstance(annotate("a", step_num=3),
                      jax.profiler.StepTraceAnnotation)
    plain = annotate("a", lane=1)
    assert isinstance(plain, jax.profiler.TraceAnnotation)
    assert not isinstance(plain, jax.profiler.StepTraceAnnotation)


PHASES = ("stage1", "stage2", "comm", "decode")


def cosim_trace(trace_dir, telemetry):
    spec = scenario_spec("homogeneous")
    with jax.profiler.trace(str(trace_dir)):
        for lane, seed in enumerate((0, 101)):
            c = build_cluster(spec, "two-stage", seed)
            if telemetry is not None:
                c.telemetry_lane = lane
                c.telemetry = telemetry
            c.run_epoch(0)
    return host_spans(trace_dir, PHASES)


def test_cosim_phase_spans_reach_the_profiler(tmp_path):
    rec = FleetRecorder()
    spans = cosim_trace(tmp_path, rec)
    for name in PHASES:
        found = by_name(spans, name)
        assert {(ev[3]["lane"], ev[3]["epoch"]) for ev in found} \
            == {(0, 0), (1, 0)}, name
    # the recorder's own wall-clock spans are the same phases
    assert sorted(s.name for s in rec.spans if s.name in PHASES) \
        == sorted(ev[2] for ev in spans)


def test_cosim_without_recorder_annotates_nothing(tmp_path):
    assert cosim_trace(tmp_path, None) == []
