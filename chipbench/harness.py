"""The benchmark's harness: one cell, one run, one result.

Everything that belongs to one configuration, traffic mix, metric or cell
is a file of its own, found by the name ``BENCHMARK.json`` gives it:

    chipbench/configs/<config>.json     sizes, source, cuts, precision
    chipbench/traffic/<traffic>.json    the mix, and the driver that runs it
    chipbench/drivers/<driver>.py       set-up, window, finish, check
    chipbench/metrics/<metric>.py       read(ctx) -> number or None
    chipbench/limits/<workload>.json    the limit of each number compared

A run: set-up (imports, device, weights from the seed, warm-up of every
shape the window uses) → the window, ``--seconds`` long, under the
profiler with ``--trace 1`` → the device's peak memory → the program's
state freed → the comparison with the plain reference → the metrics.
"""
from __future__ import annotations

import contextlib
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from types import SimpleNamespace
from typing import Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
SRC = ROOT / "src"
COMPILE_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class NoChip(SystemExit):
    """Raised when JAX finds no TPU, or fewer chips than the cell asks."""


def load_benchmark() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; it has "
                   f"{[w['name'] for w in bench['workloads']]}")


def load_json(kind: str, name: str) -> dict:
    """``chipbench/<kind>/<name>.json``."""
    path = HERE / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} file for {name!r}: {path}")
    return json.loads(path.read_text())


def load_module(kind: str, name: str):
    """``chipbench/<kind>/<name>.py`` as a module (names may hold dots)."""
    path = HERE / kind / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind} module for {name!r}: {path}")
    key = f"chipbench_{kind}_{name}".replace(".", "_").replace("-", "_")
    if key in sys.modules:
        return sys.modules[key]
    spec = importlib.util.spec_from_file_location(key, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[key] = mod
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell_metrics(bench: dict, cell: str, trace: bool) -> list:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with ``trace`` its per-layer ones (those listing the cell, and those
    with no list whose end-to-end metric the cell reports)."""
    e2e = [m for m in bench["end_to_end"] if _applies(m, cell)]
    if not trace:
        return e2e
    moved = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", ())
            or ("workloads" not in m and m["moves"] in moved)]


def require_chip(chips: int):
    """The TPU devices of this process; :class:`NoChip` off a TPU or with
    fewer chips than ``chips``."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"chipbench: JAX found no accelerator ({e})") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"chipbench: needs a TPU, JAX found "
                     f"{devices[0].platform!r}; no CPU fallback")
    if len(devices) < chips:
        raise NoChip(f"chipbench: the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def device_record(devices) -> dict:
    peak = []
    for d in devices:
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peak.append(int(stats["peak_bytes_in_use"]))
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "memory_peak_bytes": max(peak) if peak else None}


class CompileCounter:
    """Counts the jit lowerings (each compile, whether or not the
    persistent cache then holds it) while it is open."""

    def __init__(self):
        self.count = 0
        self._on = False

    def _listen(self, event, *args, **kwargs):
        if self._on and event == COMPILE_EVENT:
            self.count += 1

    def __enter__(self):
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        self._on = True
        return self

    def __exit__(self, *exc):
        self._on = False
        return False


def enable_cache() -> str:
    """The program's own persistent compile cache, with every program
    cached so that only a cell's first run in a checkout compiles."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return where


def judge(checks: dict) -> bool:
    return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
               for c in checks.values())


def run(name: str, seed: int, seconds: float, trace: bool, *,
        t_start: Optional[float] = None, allow_cpu: bool = False,
        overrides: Optional[dict] = None, log=sys.stderr) -> dict:
    """One run of the cell ``name``; returns the result line's object.

    ``allow_cpu`` is the CPU rehearsal: it skips the look for a chip and
    reports counts and checks with no metric, since a number from a CPU
    run is never written under a device metric's name.  ``overrides``
    maps ``"config"``/``"traffic"``/``"limits"`` to keys that replace the
    files' own (tiny sizes for a rehearsal).
    """
    t_start = time.perf_counter() if t_start is None else t_start
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    bench = load_benchmark()
    cell = workload(bench, name)
    overrides = overrides or {}
    config = {**load_json("configs", cell["config"]),
              **overrides.get("config", {})}
    traffic = {**load_json("traffic", cell["traffic"]),
               **overrides.get("traffic", {})}
    limits = {**load_json("limits", name), **overrides.get("limits", {})}

    marks = {"imports": time.perf_counter() - t_start}
    import jax
    devices = (jax.devices()[:cell["chips"]] if allow_cpu
               else require_chip(cell["chips"]))
    marks["devices"] = time.perf_counter() - t_start
    enable_cache()
    driver = load_module("drivers", traffic["driver"])

    state = driver.setup(config, traffic, seed)
    setup_s = time.perf_counter() - t_start
    marks.update({k: v - t_start for k, v in getattr(state, "marks",
                                                     {}).items()})
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    try:
        with CompileCounter() as compiles:
            if trace:
                jax.profiler.start_trace(trace_dir)
                try:
                    with jax.profiler.TraceAnnotation("window"):
                        win = driver.window(state, seconds)
                finally:
                    jax.profiler.stop_trace()
            else:
                win = driver.window(state, seconds)
        device = device_record(devices)
        driver.finish(state)
        gc.collect()
        checks = driver.check(state, win, limits)
        summary = (_reduce_trace(trace_dir, driver.ANNOTATIONS)
                   if trace else None)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    ctx = SimpleNamespace(window=win, setup_s=setup_s, trace=summary,
                          config=config, traffic=traffic,
                          device_kind=device["kind"], seconds=seconds)
    metrics, read = {}, []
    for m in cell_metrics(bench, name, trace):
        value = (setup_s if m["name"] == "setup_s"
                 else load_module("metrics", m["name"]).read(ctx))
        if value is None:
            continue
        read.append(m["name"])
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    if allow_cpu and device["platform"] != "tpu":
        metrics = {}

    if summary is not None:
        device["busy_s"] = summary["busy_s"]
        device["window_s"] = summary["window_s"]
    result = {"correct": judge(checks), "attempted": win["attempted"],
              "failed": win["failed"], "metrics": metrics, "device": device,
              "window_compiles": compiles.count, "readers": read}
    if summary is not None:
        result["device_programs"] = summary["programs"]
        result["breakdown"] = {"device_ops": summary["ops"],
                               "idle_gaps": summary["gaps"]}
    if "summary" in win:
        result["window"] = win["summary"]
    result["setup_marks_s"] = marks
    result["checks"] = checks
    print(f"chipbench: {name} seed={seed} setup_s={setup_s:.3f} "
          f"attempted={win['attempted']} failed={win['failed']} "
          f"window_compiles={compiles.count}", file=log)
    for k, c in checks.items():
        ok = "ok" if judge({k: c}) else "FAIL"
        print(f"check {k} = {c['value']!r} limit {c['limit']!r} {ok}",
              file=log)
    return result


def _reduce_trace(trace_dir: str, annotations) -> dict:
    from chipbench import trace_reduce
    return trace_reduce.reduce_file(trace_dir, annotations)


@contextlib.contextmanager
def patched(module, **attrs):
    """Set module attributes for the life of the block, then restore."""
    old = {k: getattr(module, k) for k in attrs}
    for k, v in attrs.items():
        setattr(module, k, v)
    try:
        yield
    finally:
        for k, v in old.items():
            setattr(module, k, v)
