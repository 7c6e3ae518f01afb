"""Device-resident epoch tail (PR 9, DESIGN.md §3.11): unit contracts.

The differential matrices live in ``tests/test_batched_compute.py``
(device vs oracle and vs host tail, every scenario × scheme) and
``tests/test_chunking.py`` (chunk invariance).  Here we pin the pieces
the tentpole's bit-identity rests on:

  * :func:`~repro.sim.device_epoch._pairwise_last` replicates numpy's
    pairwise summation bitwise at every size regime;
  * the stacked count/mask decode gates equal each job's exact
    ``is_decodable`` closure on random arrival masks;
  * missing gates and bad meshes fail loudly, not silently;
  * ``shard_map`` over a 2-device CPU mesh is bit-identical to the
    unsharded scan (subprocess — host device count is fixed at jax
    import time);
  * the ``Fleet`` facade's ``engine="device"`` row equals
    ``engine="batched"`` bitwise, and a series-collecting recorder falls
    back to the host tail without changing results.
"""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.sim import (BatchedFleet, Fleet, available_scenarios,
                       build_cluster, scenario_spec)
from repro.sim.cluster import SCHEMES
from repro.sim.device_epoch import _pairwise_last, _stack_gates, device_comm
from repro.telemetry.recorder import FleetRecorder, TelemetryConfig

SEEDS = [0, 101, 1002]
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# --------------------------------------------------------------------- #
# numpy-bitwise pairwise summation
# --------------------------------------------------------------------- #
@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize(
    "n", [0, 1, 2, 7, 8, 9, 15, 16, 17, 100, 127, 128, 129, 200, 300, 1000])
def test_pairwise_last_is_bitwise_numpy_sum(n, dtype):
    """Across the algorithm's three size regimes (sequential < 8,
    blocked ≤ 128, recursive above) the device fold must equal
    ``ndarray.sum`` bit for bit — values span 12 orders of magnitude so
    any association-order difference shows up in the low mantissa bits."""
    rng = np.random.default_rng(n + (0 if dtype is np.float64 else 1))
    x = (rng.uniform(-1.0, 1.0, (3, n))
         * 10.0 ** rng.integers(-6, 6, (3, n))).astype(dtype)
    with jax.enable_x64(True):
        got = np.asarray(_pairwise_last(jnp.asarray(x)))
    want = x.sum(axis=-1)
    assert got.dtype == want.dtype
    np.testing.assert_array_equal(got, want)


# --------------------------------------------------------------------- #
# stacked decode gates ≡ the exact per-job gate
# --------------------------------------------------------------------- #
def _gate_fires(g, i, mask):
    """The scan's per-slot predicate, evaluated in numpy for one lane."""
    ok = (bool(g.has_work[i]) and bool((mask | ~g.must[i]).all())
          and int((mask & g.cnt[i]).sum()) >= int(g.need[i]))
    if g.G:
        grp = (g.member[i] & mask).any(-1) | ~g.gvalid[i]
        ok = ok and bool(grp.all())
    return ok


@pytest.mark.parametrize("scheme", SCHEMES)
@pytest.mark.parametrize("scenario", available_scenarios())
def test_stacked_gate_matches_exact_gate_on_random_masks(scenario, scheme):
    spec = scenario_spec(scenario)
    clusters = [build_cluster(spec, scheme, s) for s in SEEDS]
    rng = np.random.default_rng(7)
    for epoch in range(2):          # epoch 1 exercises stage-2 variety
        jobs = [c.comm_job(epoch) for c in clusters]
        g = _stack_gates(jobs, clusters[0].M)
        for i, job in enumerate(jobs):
            for _ in range(200):
                mask = rng.random(clusters[0].M) < rng.uniform(0.1, 0.9)
                assert _gate_fires(g, i, mask) == job.is_decodable(mask), (
                    f"{scenario}/{scheme} epoch={epoch} lane={i} "
                    f"mask={mask.astype(int)}")


def test_stack_gates_rejects_missing_gates():
    spec = scenario_spec("homogeneous")
    clusters = [build_cluster(spec, "two-stage", s) for s in SEEDS]
    jobs = [c.comm_job(0) for c in clusters]
    jobs[1] = dataclasses.replace(jobs[1], gate=None)
    with pytest.raises(ValueError, match=r"lanes \[1\]"):
        _stack_gates(jobs, clusters[0].M)
    with pytest.raises(ValueError, match="gate"):
        device_comm(clusters, jobs)


# --------------------------------------------------------------------- #
# mesh validation fails loudly
# --------------------------------------------------------------------- #
def test_device_comm_rejects_mesh_without_seed_axis():
    spec = scenario_spec("homogeneous")
    clusters = [build_cluster(spec, "two-stage", s) for s in SEEDS]
    jobs = [c.comm_job(0) for c in clusters]
    mesh = jax.make_mesh((1,), ("model",))
    with pytest.raises(ValueError, match="'seeds' axis"):
        device_comm(clusters, jobs, mesh=mesh)


def test_batched_fleet_rejects_mesh_with_host_tail():
    spec = scenario_spec("homogeneous")
    with pytest.raises(ValueError, match="mesh= requires tail='device'"):
        BatchedFleet(spec, "two-stage", SEEDS,
                     mesh=jax.make_mesh((1,), ("seeds",)))


# --------------------------------------------------------------------- #
# shard_map bit-identity (2 virtual CPU devices — subprocess because the
# host platform device count is frozen when jax first imports)
# --------------------------------------------------------------------- #
_SHARD_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=2 "
                           + os.environ.get("XLA_FLAGS", ""))
import numpy as np
import jax
assert len(jax.devices()) == 2, jax.devices()
from repro.sim import BatchedFleet, scenario_spec
from repro.launch.mesh import fleet_mesh

spec = scenario_spec("heterogeneous-rates")
seeds = [0, 1, 2, 3]
a = BatchedFleet(spec, "two-stage", seeds, tail="device")
b = BatchedFleet(spec, "two-stage", seeds, tail="device",
                 mesh=fleet_mesh())
ra, rb = a.run(2), b.run(2)
for e in range(2):
    for i in range(len(seeds)):
        x, y = ra[e][i], rb[e][i]
        assert y.time == x.time
        assert y.decode_ok == x.decode_ok
        assert y.comm.n_slots == x.comm.n_slots
        assert y.comm.min_energy == x.comm.min_energy
        np.testing.assert_array_equal(y.weights, x.weights)
        for f in ("arrived", "bytes_offered", "bytes_admitted",
                  "bytes_transmitted", "queue_residual",
                  "pending_residual", "final_energy"):
            np.testing.assert_array_equal(getattr(y.comm, f),
                                          getattr(x.comm, f), err_msg=f)

# mesh="auto" builds the same mesh over every visible device
c = BatchedFleet(spec, "two-stage", seeds, tail="device", mesh="auto")
rc = c.run(1)
for i in range(len(seeds)):
    assert rc[0][i].time == ra[0][i].time

# a fleet that does not divide over the shards fails loudly
try:
    BatchedFleet(spec, "two-stage", [0, 1, 2], tail="device",
                 mesh=fleet_mesh()).run(1)
except ValueError as e:
    assert "shards" in str(e), e
else:
    raise SystemExit("expected ValueError for 3 lanes over 2 shards")
print("SHARD-OK")
"""


def test_shard_map_is_bit_identical_to_unsharded():
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(REPO, "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    proc = subprocess.run([sys.executable, "-c", _SHARD_SCRIPT],
                          capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=420)
    assert proc.returncode == 0, proc.stderr
    assert "SHARD-OK" in proc.stdout


# --------------------------------------------------------------------- #
# the facade's device engine + the series-telemetry fallback
# --------------------------------------------------------------------- #
def test_fleet_device_engine_summary_matches_batched():
    spec = scenario_spec("fading-uplink")
    a = Fleet(spec).run("two-stage", SEEDS, n_epochs=2, engine="batched")
    b = Fleet(spec).run("two-stage", SEEDS, n_epochs=2, engine="device")
    assert a.summary() == b.summary()      # dataclass == ⟹ bitwise floats


def test_series_telemetry_falls_back_to_host_tail():
    """Per-slot series need the chunk outputs the device tail never
    materializes: with a series-collecting recorder attached the engine
    must take the host tail — same results, series recorded."""
    spec = scenario_spec("homogeneous")
    rec = FleetRecorder(TelemetryConfig(series=True))
    a = BatchedFleet(spec, "two-stage", SEEDS, tail="device",
                     telemetry=rec)
    b = BatchedFleet(spec, "two-stage", SEEDS, tail="device")
    ra, rb = a.run(1), b.run(1)
    for x, y in zip(ra[0], rb[0]):
        assert x.time == y.time
        assert x.comm.n_slots == y.comm.n_slots
    assert rec.series_keys()   # the fallback actually recorded the slots
    # a series-free recorder keeps the device tail and still records spans
    rec2 = FleetRecorder(TelemetryConfig(series=False))
    c = BatchedFleet(spec, "two-stage", SEEDS, tail="device",
                     telemetry=rec2)
    rc = c.run(1)
    for x, y in zip(rc[0], rb[0]):
        assert x.time == y.time
    assert not rec2.series_keys()
