"""The reduction of the program's own spans in a profiler trace
(``chipbench/program_spans.py``): durations, args, self time and the
device idle time inside each span, beside the trace reduction the
benchmark reports, which it leaves as it is.
"""
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import harness, program_spans, trace_reduce  # noqa: E402

TRACE = ROOT / "chipbench" / "testdata" / "small.xplane.pb"
REDUCED_KEYS = {"busy_s", "window_s", "idle_share", "devices", "programs",
                "ops", "gaps"}


def test_plan_spans_of_recorded_trace():
    """The recorded trace's five 20 ms host pauses (``plan``) hold the
    device's idle time that the reduction labels ``plan``: all of it but
    the slivers of those gaps that lie outside the pauses."""
    r = trace_reduce.reduce_file(str(TRACE), annotations=(
        "step", "plan", "device_step"))
    assert set(r) == REDUCED_KEYS
    gaps = dict(r["gaps"])
    assert max(gaps, key=gaps.get) == "plan"
    assert gaps["plan"] >= 5 * 0.019
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)

    plan = program_spans.read_file(str(TRACE), prefix="plan")["plan"]
    assert plan["count"] == 5 and plan["args"] == [{}] * 5
    assert all(s >= 0.019 for s in plan["seconds"])
    assert plan["self_s"] == plan["seconds"]
    total = sum(plan["seconds"])
    assert total - r["busy_s"] <= plan["idle_s"] <= total
    assert 0.95 * gaps["plan"] <= plan["idle_s"] <= gaps["plan"]


def _event(name, start, end, **stats):
    return NS(name=name, start_ns=start, duration_ns=end - start,
              stats=list(stats.items()))


def test_self_time_and_idle_on_a_made_up_trace():
    """Two steps on one thread; the device busy 30–50 and 130–140 of a
    window 0–200 (ns)."""
    host = [_event("window", 0, 200),
            _event("coded.step", 10, 90), _event("coded.plan", 10, 20),
            _event("coded.batch", 20, 40, slots=90, padding_slots=60),
            _event("coded.batch.data", 22, 30),
            _event("coded.device_step", 40, 80),
            _event("coded.step", 100, 190), _event("coded.plan", 100, 135),
            _event("coded.batch", 135, 150, slots=90, padding_slots=66),
            _event("coded.batch.data", 136, 148),
            _event("unrelated", 0, 200)]
    device = [NS(name="XLA Ops", events=[_event("op", 30, 50),
                                         _event("op", 130, 140)])]
    profile = NS(planes=[
        NS(name="/host:CPU", lines=[NS(name="python", events=host)]),
        NS(name="/device:TPU:0", lines=device)])
    r = program_spans.program_spans(profile)
    assert set(r) == {"coded.step", "coded.plan", "coded.batch",
                      "coded.batch.data", "coded.device_step"}
    ns = 1e-9
    assert r["coded.step"]["seconds"] == pytest.approx([80 * ns, 90 * ns])
    # step: 80 - plan 10 - batch 20 - device step 40; 90 - 35 - 15
    assert r["coded.step"]["self_s"] == pytest.approx([10 * ns, 40 * ns])
    assert r["coded.batch"]["self_s"] == pytest.approx([12 * ns, 3 * ns])
    assert r["coded.batch"]["args"] == [{"slots": 90, "padding_slots": 60},
                                        {"slots": 90, "padding_slots": 66}]
    # idle inside: plan 10 + 30 (130-135 busy); batch 10 (30-40 busy)
    # + 10 (135-140 busy); device step 30 (40-50 busy); step 170 - 30
    assert r["coded.plan"]["idle_s"] == pytest.approx(40 * ns)
    assert r["coded.batch"]["idle_s"] == pytest.approx(20 * ns)
    assert r["coded.device_step"]["idle_s"] == pytest.approx(30 * ns)
    assert r["coded.step"]["idle_s"] == pytest.approx(140 * ns)

    cpu = NS(planes=profile.planes[:1])
    assert program_spans.program_spans(cpu)["coded.plan"]["idle_s"] is None


def test_batch_counters_match_the_drivers_counts(tmp_path):
    """Over a traced window of the tiny training cell, the ``coded.batch``
    counters hold, step by step, the tokens the driver counts from what
    the program hands its step."""
    from chipbench import calibrate
    config = {**harness.load_json("configs", "stablelm-1.6b-l4"),
              **calibrate.TINY["config"]}
    traffic = {**harness.load_json("traffic", "coded-s128-w15"),
               **calibrate.TINY["traffic"]}
    driver = harness.load_module("drivers", "train")
    state = driver.setup(config, traffic, 7)
    part0 = state.counts["partition_tokens"]
    try:
        with jax.profiler.trace(str(tmp_path)):
            win = driver.window(state, 0.2)
    finally:
        driver.finish(state)
    batch = program_spans.read_file(str(tmp_path))["coded.batch"]
    steps = win["steps"]
    assert batch["count"] == len(steps) >= 1
    first = int(traffic["setup_steps"])
    for i, (args, s) in enumerate(zip(batch["args"], steps)):
        assert args["step"] == first + i
        assert args["slot_tokens"] == s["slot_tokens"]
        assert args["partition_tokens"] * args["decode_ok"] \
            == s["decoded_tokens"]
        assert args["used_slots"] + args["padding_slots"] == args["slots"]
    assert sum(a["partition_tokens"] for a in batch["args"]) \
        == state.counts["partition_tokens"] - part0
