"""The chip benchmark's CPU tests: the yardstick, without the chip.

Covers the trace reduction (on a small trace recorded on a TPU v5e), the
FLOP counts and the table of peaks, the discovery of configurations,
mixes, drivers, metric readers and limits by name, the command's refusal
off a TPU, a CPU rehearsal of the training cell at a tiny size, and the
comparison that decides ``correct``: the fp8 control and each fault of
the timed path must come out not correct.
"""
import json
import math
import os
import re
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import calibrate, flops, harness, trace_reduce  # noqa: E402
from chipbench.references import dense_lm  # noqa: E402

TRACE = ROOT / "chipbench" / "testdata" / "small.xplane.pb"
TRAIN = "train-stablelm-coded"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


@pytest.fixture
def no_cache(monkeypatch):
    """Rehearsals leave the process's compile cache as they found it."""
    monkeypatch.setattr(harness, "enable_cache", lambda: None)


def rehearse(seed, **kw):
    return harness.run(TRAIN, seed, 0.5, False, allow_cpu=True,
                       overrides=calibrate.TINY, **kw)


# --------------------------------------------------------------------- #
# trace reduction
# --------------------------------------------------------------------- #
def test_merge_intervals():
    iv = [(0, 2), (1, 3), (5, 6), (6, 7), (10, 10)]
    assert trace_reduce.merged(iv) == [(0, 3), (5, 7), (10, 10)]
    assert trace_reduce.merged([]) == []


def test_gap_label_is_innermost_annotation():
    labels = sorted([(0, 100, "step"), (10, 30, "plan"), (40, 90,
                                                          "device_step")])
    starts = [s for s, _, _ in labels]
    assert trace_reduce._label(labels, starts, 20) == "plan"
    assert trace_reduce._label(labels, starts, 35) == "step"
    assert trace_reduce._label(labels, starts, 200) == "unannotated"


def test_reduce_recorded_trace():
    r = trace_reduce.reduce_file(str(TRACE), annotations=(
        "step", "plan", "device_step"))
    assert r["devices"] == 1
    # five steps, each behind a 20 ms host pause: the window is > 100 ms
    assert r["window_s"] > 0.1
    assert 0 < r["busy_s"] < r["window_s"]
    assert 0 < r["idle_share"] < 1
    assert math.isclose(r["idle_share"], 1 - r["busy_s"] / r["window_s"])
    gaps = dict(r["gaps"])
    # the host's 20 ms pauses are the device's idle time, labelled "plan"
    assert max(gaps, key=gaps.get) == "plan"
    assert gaps["plan"] >= 5 * 0.019
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-6)
    assert r["ops"] and all(s > 0 for _, s in r["ops"])
    assert r["programs"] and any("lambda" in n for n, _ in r["programs"])


def test_reduce_requires_device_plane(tmp_path):
    class Plane:
        def __init__(self, name):
            self.name, self.lines = name, []

    class Profile:
        planes = [Plane("/host:CPU")]

    with pytest.raises(ValueError, match="no device plane"):
        trace_reduce.reduce_profile(Profile())


# --------------------------------------------------------------------- #
# FLOPs and peaks
# --------------------------------------------------------------------- #
def test_flops_against_roofline_model_flops(bench):
    import dataclasses

    from repro.analysis.roofline import model_flops
    from repro.configs.base import ShapeConfig, get_config
    lms = [harness.load_json("configs", c["name"]) for c in bench["configs"]]
    lms = [c for c in lms if "hidden_size" in c]
    assert lms
    for config in lms:
        s = dense_lm.sizes(config)
        prog = dataclasses.replace(get_config("stablelm-1.6b"),
                                   n_layers=s["layers"])
        seq = 128
        shape = ShapeConfig("t", seq, 1, "train")
        embed = s["vocab"] * s["d"]
        norms = (2 * s["layers"] + 1) * s["d"]
        six_nd = model_flops(prog, shape) / seq      # 6·N_total a token
        attention = 6 * s["layers"] * seq * s["d"]
        ours = flops.dense_lm_train_flops_per_token(config, seq)
        assert ours == pytest.approx(six_nd - 6 * (embed + norms)
                                     + attention, rel=1e-12)


def test_matmul_params_stablelm():
    config = harness.load_json("configs", "stablelm-1.6b-l4")
    # 4 layers × 51.4M + the 205.5M head; the 205.5M embedding is out
    assert flops.dense_lm_matmul_params(config) == 411_041_792


def test_peaks_lookup():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 1.97e14
    assert p["hbm_bytes_per_s"] == 8.19e11
    assert "TPU v5e" in p["source"]
    with pytest.raises(KeyError, match="no peaks"):
        flops.peaks("TPU v9 imaginary")


# --------------------------------------------------------------------- #
# BENCHMARK.json and discovery by name
# --------------------------------------------------------------------- #
def test_benchmark_file_shape(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:2] == ["python3", "chipbench/run.py"]
    assert all((ROOT / p).is_dir() for p in bench["paths"])
    assert 1 <= bench["run_seconds"] <= 51
    n = 24                               # the most cells a later PR may add
    total = 2 * (bench["run_seconds"] + 60) \
        + 14 * n * (bench["run_seconds"] + 60) + n * 2 * 90 + 1200
    assert total <= 43200
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in bench["per_layer"]:
        assert m["moves"] in e2e and m["moves"] != "setup_s"
    for w in bench["workloads"]:
        assert NAME.match(w["name"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        reported = [m["name"] for m in harness.cell_metrics(
            bench, w["name"], False)]
        assert "setup_s" in reported and len(reported) >= 2
        assert harness.cell_metrics(bench, w["name"], True)


def test_configs_are_files_under_paths(bench):
    for c in bench["configs"]:
        config = json.loads((ROOT / c["file"]).read_text())
        assert c["file"] == f"chipbench/configs/{c['name']}.json"
        assert config["name"] == c["name"] and config["source"] == c["source"]
        assert sorted(config["reduced"]) == sorted(c["reduced"])
        for key, published in config["reduced"].items():
            assert config[key] != published


def test_every_cell_finds_its_files_by_name(bench):
    for w in bench["workloads"]:
        config = harness.load_json("configs", w["config"])
        traffic = harness.load_json("traffic", w["traffic"])
        driver = harness.load_module("drivers", traffic["driver"])
        for fn in ("setup", "window", "finish", "check"):
            assert callable(getattr(driver, fn))
        limits = harness.load_json("limits", w["name"])
        assert limits and config["name"] == w["config"]
        for m in harness.cell_metrics(bench, w["name"], False) \
                + harness.cell_metrics(bench, w["name"], True):
            if m["name"] != "setup_s":
                assert callable(harness.load_module("metrics",
                                                    m["name"]).read)


def test_new_files_are_found_by_name(tmp_path, monkeypatch):
    """Adding a config, a mix, a driver or a metric is adding files."""
    for kind in ("configs", "traffic", "drivers", "metrics", "limits"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "new-model.json").write_text('{"n": 1}')
    (tmp_path / "traffic" / "new-mix.json").write_text('{"driver": "d"}')
    (tmp_path / "drivers" / "d.py").write_text("ANNOTATIONS = ()\n")
    (tmp_path / "metrics" / "new.metric.py").write_text(
        "def read(ctx):\n    return 7.0\n")
    monkeypatch.setattr(harness, "HERE", tmp_path)
    assert harness.load_json("configs", "new-model") == {"n": 1}
    assert harness.load_json("traffic", "new-mix")["driver"] == "d"
    assert harness.load_module("drivers", "d").ANNOTATIONS == ()
    assert harness.load_module("metrics", "new.metric").read(None) == 7.0
    with pytest.raises(FileNotFoundError):
        harness.load_json("configs", "absent")
    with pytest.raises(FileNotFoundError):
        harness.load_module("metrics", "absent")


def test_cell_metrics_selects_by_workloads():
    bench = {"end_to_end": [
        {"name": "a", "moves": None},
        {"name": "setup_s"},
        {"name": "b", "workloads": ["other"]}],
        "per_layer": [
            {"name": "x", "moves": "a"},
            {"name": "y", "moves": "b"},
            {"name": "z", "moves": "b", "workloads": ["cell"]}]}
    assert [m["name"] for m in harness.cell_metrics(bench, "cell", False)] \
        == ["a", "setup_s"]
    assert [m["name"] for m in harness.cell_metrics(bench, "cell", True)] \
        == ["x", "z"]


def test_readers_return_nothing_when_there_is_nothing(bench):
    from types import SimpleNamespace
    ctx = SimpleNamespace(window={"steps": []}, trace=None, setup_s=1.0,
                          device_kind="TPU v5 lite", config={}, traffic={})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] != "setup_s":
            assert harness.load_module("metrics", m["name"]).read(ctx) is None


# --------------------------------------------------------------------- #
# the command off a TPU
# --------------------------------------------------------------------- #
def test_command_refuses_cpu():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", TRAIN,
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    assert "needs a TPU" in p.stderr


def test_command_refuses_without_the_program(tmp_path):
    """A checkout that holds only BENCHMARK.json and the benchmark."""
    import shutil
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "chipbench", tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", TRAIN,
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


# --------------------------------------------------------------------- #
# the training cell rehearsed on the CPU, and what decides correct
# --------------------------------------------------------------------- #
def test_rehearsal_reports_counts_not_timings(no_cache):
    r = rehearse(2 ** 31 + 12345)
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics",
                           "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True, r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert r["metrics"] == {}           # no CPU number under a device name
    assert r["device"]["platform"] == "cpu"
    assert r["window_compiles"] == 0
    assert set(r["checks"]) == {"loss_gap", "grad_norm_gap",
                                "update_norm_gap", "embed_rows_differ",
                                "undecoded_checked_steps",
                                "stage_kinds_unchecked"}
    limits = harness.load_json("limits", TRAIN)
    assert all(c["limit"] == limits[k] for k, c in r["checks"].items())


def tiny_cell():
    over = calibrate.TINY
    config = {**harness.load_json("configs", "stablelm-1.6b-l4"),
              **over["config"]}
    traffic = {**harness.load_json("traffic", "coded-s128-w15"),
               **over["traffic"]}
    return config, traffic


def test_token_counts_come_from_the_program(no_cache):
    """Each step's slot and decoded tokens are read off what the program
    hands its step: the slot batch's shape and the plan's partitions."""
    config, traffic = tiny_cell()
    driver = harness.load_module("drivers", "train")
    state = driver.setup(config, traffic, 3)
    try:
        win = driver.window(state, 0.2)
    finally:
        driver.finish(state)
    W, n = traffic["workers"], traffic["slots_per_worker"]
    S = traffic["seq_len"] * traffic["sequences_per_partition"]
    assert win["steps"]
    for s in win["steps"]:
        assert s["slot_tokens"] == W * n * S
        assert s["decoded_tokens"] == (2 * W * S if s["decode_ok"] else 0)


def test_slot_fill_follows_the_slots_computed():
    from types import SimpleNamespace
    read = harness.load_module("metrics", "slot_fill.train").read

    def fill(slot_tokens):
        steps = [{"slot_tokens": t, "decoded_tokens": 192, "decode_ok": True}
                 for t in slot_tokens]
        return read(SimpleNamespace(window={"steps": steps}))

    assert fill([1440, 1440]) == pytest.approx(100 * 192 / 1440)
    # a step that computed fewer slots reads a higher fill
    assert fill([1440, 720]) == pytest.approx(100 * 384 / 2160)


def test_checks_cover_both_kinds_of_plan(no_cache):
    """Checked steps that the runtime's stage 2 re-plans every one of
    (the first seven) leave stage 1's decode weights uncompared: the run
    is not correct."""
    r = rehearse(19)
    assert r["checks"]["stage_kinds_unchecked"]["value"] == 0.0
    short = {"config": calibrate.TINY["config"],
             "traffic": {**calibrate.TINY["traffic"], "setup_steps": 3,
                         "checked_steps": 3}}
    r = harness.run(TRAIN, 19, 0.5, False, allow_cpu=True, overrides=short)
    assert r["checks"]["stage_kinds_unchecked"]["value"] == 1.0
    assert r["correct"] is False


def test_same_seed_same_weights_and_data():
    config = {**harness.load_json("configs", "stablelm-1.6b-l4"),
              **calibrate.TINY["config"]}
    a = dense_lm.init_params(config, 2 ** 33 + 5)
    b = dense_lm.init_params(config, 2 ** 33 + 5)
    c = dense_lm.init_params(config, 5)
    assert all(np.array_equal(x, y) for x, y in zip(
        jax_leaves(a), jax_leaves(b)))
    assert not np.array_equal(a["embed"], c["embed"])
    p = dense_lm.lm_partition(4096, 16, 1, 0, 3, 2)
    q = dense_lm.lm_partition(4096, 16, 1, 0, 3, 2)
    assert all(np.array_equal(p[k], q[k]) for k in p)


def jax_leaves(tree):
    import jax
    return [np.asarray(x) for x in jax.tree.leaves(tree)]


def test_reference_data_is_the_trainers():
    from repro.data.pipeline import SyntheticLMDataset
    ds = SyntheticLMDataset(12, examples_per_partition=1, seq_len=16,
                            vocab=4096)
    for epoch, k in ((0, 0), (3, 11)):
        ours = dense_lm.lm_partition(4096, 16, 1, 0, epoch, k)
        theirs = ds.partition(epoch, k)
        assert np.array_equal(ours["tokens"], np.asarray(theirs["tokens"]))
        assert np.array_equal(ours["labels"], np.asarray(theirs["labels"]))
        assert np.array_equal(ours["weights"] > 0,
                              np.asarray(theirs["weights"]) > 0)


def test_control_is_not_correct():
    """The reference in fp8, put in the program's place, fails a limit."""
    config, traffic = tiny_cell()
    driver = harness.load_module("drivers", "train")
    limits = harness.load_json("limits", TRAIN)
    batches = driver.reference_batches(config, traffic)
    for seed in (1, 2, 3):
        ref = dense_lm.train_readings(config, traffic, seed, batches)
        ctl = dense_lm.train_readings(config, traffic, seed, batches,
                                      precision="fp8")
        numbers = driver.compare(ctl, ref)
        failed = [k for k, v in numbers.items() if v > limits[k]]
        assert failed, numbers


@pytest.mark.parametrize("mode", ["unchanged", "half", "token"])
def test_broken_timed_path_is_not_correct(mode, no_cache):
    """The whole run, with the trainer broken underneath, reads false.
    (One chip: no exchange between chips to leave out.)"""
    config = {**harness.load_json("configs", "stablelm-1.6b-l4"),
              **calibrate.TINY["config"]}
    K = 2 * harness.load_json("traffic", "coded-s128-w15")["workers"]
    with calibrate.fault(mode, K, int(config["vocab_size"])):
        r = rehearse(17)
    assert r["correct"] is False, (mode, r["checks"])


def test_reference_is_the_programs_layer_in_f32():
    """At f32 compute the trainer's per-slot loss and its gradient equal
    the plain reference's to f32 rounding: the reference restates the
    layer the configuration runs, not a neighbour of it."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.launch.train import per_slot_lm_loss
    config = {**harness.load_json("configs", "stablelm-1.6b-l4"),
              **calibrate.TINY["config"]}
    driver = harness.load_module("drivers", "train")
    cfg = dataclasses.replace(driver.program_config(config),
                              compute_dtype="float32")
    s = dense_lm.sizes(config)
    parts = dense_lm.lm_step_batch(s["vocab"], 16, 1, 0, 0, 12)
    params = dense_lm.init_params(config, 7)
    slots = {k: v[None] for k, v in parts.items()}      # (1, K, b, S)

    def program(p):
        return per_slot_lm_loss(cfg)(p, slots).sum()

    def reference(p):
        return dense_lm.sequence_losses(
            p, parts["tokens"][:, 0], parts["labels"][:, 0],
            parts["weights"][:, 0], s).sum()

    with jax.default_matmul_precision("highest"):
        lp, gp = jax.value_and_grad(program)(params)
        lr, gr = jax.value_and_grad(reference)(params)
    # CPU: loss equal, worst leaf 6e-7 in relative L2
    assert float(lp) == pytest.approx(float(lr), rel=1e-6)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        assert float(jnp.linalg.norm(a - b)) <= 1e-5 * float(
            jnp.linalg.norm(b))
