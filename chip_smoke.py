#!/usr/bin/env python3
"""Bring-up smoke test: the system's two hot paths on one TPU chip.

    python chip_smoke.py             # one chip: phases (a)-(e) below
    python chip_smoke.py --chips 4   # four chips: the sharded fleet only

One process drives every phase, in order, and each phase prints its own
lines.  Any failed check raises, so the script exits non-zero; nothing
falls back to the CPU, runs a Pallas kernel in interpret mode, or swallows
an exception.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

  (a) device   — platform, kind and count; anything but a TPU stops here.
  (b) kernels  — each kernel's ``ops.py`` entry at real widths, checked
                 against its ``ref.py`` and for ``tpu_custom_call`` in the
                 lowered program (a compiled kernel, not the interpreter).
  (c) trainer  — the coded train step (``launch/train.py --coded``) on
                 stablelm-1.6b at its published widths, cut to 4 layers,
                 random weights from a seed: gradient checks in f32 and
                 bf16, then steps, then the compiled step's memory.
  (d) co-sim   — ``Fleet`` on ``saturated-uplink`` with the device and
                 batched engines at 1,000 lanes against the oracle.
  (e) soak     — ``run_soak`` for 1M slots over a V grid against the
                 O(V) backlog bound (DESIGN.md §3.12).

``--chips 4`` runs the device engine on 10,000 lanes sharded over every
chip (``mesh="auto"``) and unsharded on one, and checks they are equal
bit for bit.  The compile cache goes where
:func:`repro.launch.compile_cache.enable_compile_cache` says.
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import math
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs.base import get_config  # noqa: E402
from repro.core.coded_step import coded_value_and_grad  # noqa: E402
from repro.data.pipeline import SyntheticLMDataset  # noqa: E402
from repro.kernels.coded_reduce.ops import (coded_reduce_op,  # noqa: E402
                                            coded_reduce_ref)
from repro.kernels.flash_attention.ops import (attention_ref,  # noqa: E402
                                               flash_attention_op)
from repro.kernels.rglru_scan.ops import rglru_ref, rglru_scan_op  # noqa: E402
from repro.kernels.rwkv6_wkv.ops import wkv_op  # noqa: E402
from repro.kernels.rwkv6_wkv.ref import wkv_ref  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import fleet_mesh  # noqa: E402
from repro.launch.train import (coded_runtime,  # noqa: E402
                                coded_step_fn, per_slot_lm_loss,
                                slot_batch, train_coded)
from repro.models import transformer as tfm  # noqa: E402
from repro.optim import adamw  # noqa: E402
from repro.sim import Fleet, SoakLane, run_soak, scenario_spec  # noqa: E402
from repro.sim.device_epoch import SEED_AXIS  # noqa: E402
from repro.sim.fleet import FleetRun  # noqa: E402

GiB = 2 ** 30
SEED = 0

#: Trainer cell: stablelm-1.6b at published widths, 4 layers; 6 workers
#: over K=12 partitions, 5 slots each, one 384-token sequence per slot —
#: 11,520 tokens a step.  A v5e compile of this step (params and Adam
#: state donated) needs 13.41 GiB of the chip's 15.75 GiB.
TRAIN = dict(arch="stablelm-1.6b", layers=4, workers=6, slots=5, batch=1,
             seq=384, steps=5)
#: Gradient checks, relative L2 on epoch CHECK_EPOCH's plan.  Three
#: gradients per dtype: the coded step's (``coded``); the same coded loss
#: over the identity layout, partition k alone in slot k with weight 1
#: (``identity``: same shapes and arithmetic, no decode); and a separately
#: jitted Σ_k ∇ℓ_k over the K partitions (``reference``).  A wrong decode
#: shows large: dropping one slot of the plan moved the gradient by 26%
#: in f32 and 20% in bf16 (CPU, reduced config); 1/K ≈ 8% would be the
#: floor if every partition's gradient agreed.  The random-init gradient
#: is ill-conditioned: on the CPU (reduced config) a 2^-9 relative change
#: of the parameters moves it by 105%, so any change of arithmetic is
#: amplified.  Coded vs reference
#: therefore mixes the decode with the change of layout (30 slot rows and
#: full-logit CE against 12 rows and chunked CE); identity vs reference
#: is that layout alone, and coded vs identity is the decode alone.
#:   float32, full matmul precision: coded vs reference.  A v5e read
#:   2.033e-4, and 2.035e-4 for the layout alone; the decode alone read
#:   1.9e-6.  The bound is 5x above, 80x below the 1/K floor.
#:   bfloat16, the timed dtype: coded vs identity.  Coded vs reference
#:   reads 0.24 on a v5e (0.24 for the layout alone), which cannot tell a
#:   wrong decode from the layout, so it is printed, not bounded.  The
#:   decode alone, where the weighted slot copies round on their own,
#:   read 5.0e-3 on a v5e and 7.3e-3 on the CPU, both at reduced widths;
#:   the bound is 4x above that and 7x below the dropped slot.
GRAD_CHECK = {"float32": ("reference", 1e-3), "bfloat16": ("identity", 3e-2)}
#: Epoch 1's plan splits partitions between workers with fractional
#: weights (0.375/0.625, 0.512/0.488), so the check sees a real decode.
CHECK_EPOCH = 1

#: Kernel widths: flash attention at stablelm-1.6b's 32 heads x 64 with
#: S=4096 (B, S, H, D); the RG-LRU scan at recurrentgemma-2b's d_rnn=2560
#: (B, S, D); WKV at rwkv6-1.6b's 32 heads x 64 and its chunk of 64
#: (B, H, S, K); the decode-reduce over 6 slots of one stablelm-1.6b
#: layer's gradient (its width is read off the config).
KERNEL_SHAPES = dict(flash=(1, 4096, 32, 64), rglru=(4, 2048, 2560),
                     wkv=(1, 32, 2048, 64), wkv_chunk=64, reduce_slots=6)
#: The max error allowed per kernel, relative
#: to max |ref|.  flash: bf16 output rounding; rglru: f32 VPU arithmetic
#: in a different association order; wkv: f32 chunked form vs sequential
#: recurrence over 2,048 steps (its inner dots run at Mosaic's default
#: MXU precision); coded_reduce: an f32 contraction at fp32 precision.
KERNEL_TOL = {"flash_attention": 2e-2, "rglru_scan": 1e-4,
              "rwkv6_wkv": 1e-2, "coded_reduce": 1e-5}

#: Co-sim cell and the statistical contract used where lanes differ from
#: the oracle bitwise: decode-failure rate within 2 lane-epochs of 32,
#: mean epoch time within 2%, Jain index within 0.02.
COSIM = dict(scenario="saturated-uplink", scheme="two-stage", lanes=1000,
             oracle_lanes=16, epochs=2)
STAT_TOL = dict(fail=2 / 32, time_rel=0.02, jain=0.02)

#: Soak cell: DESIGN.md §3.12's backlog bound mean_qtot <= 50 + 25·V.
SOAK = dict(scenario="heterogeneous-rates", slots=1_000_000,
            V=(2.0, 8.0, 32.0, 128.0))

#: Four-chip cell: the sharded device engine at 10,000 lanes.
MESH = dict(scenario="saturated-uplink", scheme="two-stage", lanes=10_000,
            epochs=2)


def check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(f"check failed: {msg}")


def say(phase: str, **kv) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def device_memory() -> dict:
    """The chip's allocator stats: ``bytes_limit``, and
    ``peak_bytes_in_use``, the most the allocator had handed out at once
    over the process's life.  It is not the step's peak: the compiled
    program's temporaries are not counted in it (see ``step_memory``)."""
    return jax.devices()[0].memory_stats()


# --------------------------------------------------------------------- #
# (a) device
# --------------------------------------------------------------------- #
def device_info(chips: int) -> dict:
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke needs a TPU; JAX found "
                         f"{d.platform!r} ({d.device_kind})")
    if len(devs) < chips:
        raise SystemExit(f"--chips {chips} needs {chips} devices, JAX "
                         f"found {len(devs)}")
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    say("device", **info)
    return info


# --------------------------------------------------------------------- #
# (b) kernels
# --------------------------------------------------------------------- #
def _kernel(name, op, args, kwargs, out, ref):
    """Check one kernel call: compiled (tpu_custom_call), finite, close."""
    hlo = op.lower(*args, **kwargs).as_text()
    check("tpu_custom_call" in hlo, f"{name}: no tpu_custom_call in the "
          f"lowered program (interpret mode?)")
    out = jnp.asarray(out, jnp.float32)
    ref = jnp.asarray(ref, jnp.float32)
    check(bool(jnp.isfinite(out).all()), f"{name}: non-finite output")
    err = float(jnp.max(jnp.abs(out - ref)) / jnp.max(jnp.abs(ref)))
    t0 = time.perf_counter()
    jax.block_until_ready(op(*args, **kwargs))
    dt = time.perf_counter() - t0
    say("kernels", kernel=name, shape=tuple(args[0].shape),
        dtype=args[0].dtype, rel_max_err=f"{err:.3e}",
        tol=KERNEL_TOL[name], call_s=f"{dt:.6f}")
    check(err <= KERNEL_TOL[name], f"{name}: error {err:.3e} > "
          f"{KERNEL_TOL[name]}")


def layer_payload(cfg) -> int:
    """Parameters in one layer of ``cfg`` — one decode-reduce payload."""
    one = dataclasses.replace(cfg, n_layers=1)
    shapes = jax.eval_shape(lambda: tfm.init_params(one,
                                                    jax.random.PRNGKey(0)))
    return sum(a.size for a in jax.tree.leaves(shapes["groups"]))


def phase_kernels() -> None:
    ks = iter(jax.random.split(jax.random.PRNGKey(SEED), 16))
    normal = lambda shape, dt=jnp.float32, s=1.0: (  # noqa: E731
        s * jax.random.normal(next(ks), shape)).astype(dt)
    uniform = lambda shape, lo, hi: jax.random.uniform(  # noqa: E731
        next(ks), shape, minval=lo, maxval=hi)
    hp = jax.default_matmul_precision("highest")

    shp = KERNEL_SHAPES
    B, S, H, D = shp["flash"]
    q = normal((B, S, H, 1, D), jnp.bfloat16)
    k, v = normal((B, S, H, D), jnp.bfloat16), normal((B, S, H, D),
                                                      jnp.bfloat16)
    out = flash_attention_op(q, k, v, causal=True)
    with hp:
        ref = attention_ref(q[:, :, :, 0].transpose(0, 2, 1, 3),
                            k.transpose(0, 2, 1, 3),
                            v.transpose(0, 2, 1, 3), causal=True)
    _kernel("flash_attention", flash_attention_op, (q, k, v),
            {"causal": True}, out[:, :, :, 0].transpose(0, 2, 1, 3), ref)
    del q, k, v, out, ref

    a = uniform(shp["rglru"], 0.5, 0.999)
    b = normal(shp["rglru"], s=0.1)
    out, _ = rglru_scan_op(a, b)
    ref, _ = rglru_ref(a, b)
    _kernel("rglru_scan", rglru_scan_op, (a, b), {}, out, ref)
    del a, b, out, ref

    r, k, v = (normal(shp["wkv"], s=0.5) for _ in range(3))
    w = uniform(shp["wkv"], 0.5, 0.999)
    u = normal(shp["wkv"][1:2] + shp["wkv"][3:], s=0.5)
    chunk = shp["wkv_chunk"]
    out, _ = wkv_op(r, k, v, w, u, chunk=chunk)
    with hp:
        ref, _ = wkv_ref(r, k, v, w, u)
    _kernel("rwkv6_wkv", wkv_op, (r, k, v, w, u), {"chunk": chunk}, out,
            ref)
    del r, k, v, w, u, out, ref

    n = shp["reduce_slots"]
    g = normal((n, layer_payload(get_config(TRAIN["arch"]))))
    wts = normal((n,))
    out = coded_reduce_op(g, wts)
    with hp:
        ref = coded_reduce_ref(g, wts)
    _kernel("coded_reduce", coded_reduce_op, (g, wts), {}, out, ref)


# --------------------------------------------------------------------- #
# (c) coded trainer
# --------------------------------------------------------------------- #
@jax.jit
def _sq_errors(a, b):
    """Per leaf: (‖a - b‖², ‖b‖²)."""
    return jax.tree.map(lambda x, y: jnp.stack(
        [jnp.sum(jnp.square(x - y)), jnp.sum(jnp.square(y))]), a, b)


def rel_l2(a, b) -> tuple[float, str, float]:
    """Relative L2 error of pytree ``a`` against ``b``, and the leaf whose
    own relative error is largest, with that error."""
    leaves = [(jax.tree_util.keystr(path), np.asarray(e, np.float64))
              for path, e in jax.tree_util.tree_leaves_with_path(
                  _sq_errors(a, b))]
    num, den = (sum(e[i] for _, e in leaves) for i in (0, 1))
    name, e = max(leaves, key=lambda kv: kv[1][0] / kv[1][1])
    return math.sqrt(num / den), name, math.sqrt(e[0] / e[1])


def check_inputs(cfg, p: dict):
    """Epoch ``CHECK_EPOCH`` of the trainer's runtime: its slot batch and
    weights, and the K partitions in order, for the gradient checks."""
    runtime = coded_runtime(p["workers"], n_slots=p["slots"])
    res = [runtime.run_epoch(e) for e in range(CHECK_EPOCH + 1)][-1]
    check(res.decode_ok, f"epoch {CHECK_EPOCH} did not decode")
    ds = SyntheticLMDataset(runtime.K, examples_per_partition=p["batch"],
                            seq_len=p["seq"], vocab=cfg.vocab)
    parts = [ds.partition(CHECK_EPOCH, k) for k in range(runtime.K)]
    return (slot_batch(ds, res.plan, CHECK_EPOCH),
            jnp.asarray(res.weights, jnp.float32), parts)


def identity_layout(parts, shape):
    """The K partitions in the slot layout ``shape`` = (M, n_slots):
    partition k alone in slot k (row-major), weight 1; the rest empty."""
    n, K = math.prod(shape), len(parts)
    batch = {}
    for key, first in parts[0].items():
        rows = [parts[k][key] if k < K else jnp.zeros_like(first)
                for k in range(n)]
        batch[key] = jnp.stack(rows).reshape(shape + first.shape)
    return batch, (jnp.arange(n) < K).astype(jnp.float32).reshape(shape)


def grad_check(cfg, params, sb, w, parts) -> None:
    """Per GRAD_CHECK dtype: the coded, identity-layout and reference
    gradients, their three relative L2 errors, and the dtype's check."""
    ref_batch = {key: jnp.concatenate([q[key] for q in parts])
                 for key in parts[0]}
    id_batch, id_w = identity_layout(parts, w.shape)
    for dtype, (against, tol) in GRAD_CHECK.items():
        c = dataclasses.replace(cfg, compute_dtype=dtype)
        with (jax.default_matmul_precision("highest") if dtype == "float32"
              else contextlib.nullcontext()):
            coded = jax.jit(coded_value_and_grad(per_slot_lm_loss(c)))
            ref = jax.jit(jax.grad(lambda q, bt: tfm.loss_fn(q, bt, c)))
            # each gradient waits on the host: the f32 coded program
            # (13.92 GiB on a v5e) does not fit beside one held on chip
            g = {"coded": jax.device_get(coded(params, sb, w)[1]),
                 "identity": jax.device_get(coded(params, id_batch, id_w)[1]),
                 "reference": jax.device_get(ref(params, ref_batch))}
        errs = {}
        for a, b in (("coded", "reference"), ("identity", "reference"),
                     ("coded", "identity")):
            errs[a, b], leaf, leaf_err = rel_l2(g[a], g[b])
            say("trainer", grad=dtype, pair=f"{a}_vs_{b}",
                rel_l2=f"{errs[a, b]:.3e}",
                tol=tol if b == against and a == "coded" else "none",
                worst_leaf=leaf.replace(" ", ""),
                worst_leaf_rel_l2=f"{leaf_err:.3e}")
        del g
        err = errs["coded", against]
        check(err <= tol, f"{dtype} coded gradient vs {against}: rel L2 "
              f"{err:.3e} > {tol}")


def step_memory(cfg, opt, state_shapes, sb, w) -> dict:
    """The compiled coded step's ``memory_analysis`` in bytes, and
    ``total``: arguments + outputs - aliased + temporaries + code."""
    ma = coded_step_fn(cfg, opt).lower(*state_shapes, sb, w).compile() \
        .memory_analysis()
    mem = {f: int(getattr(ma, f"{f}_size_in_bytes")) for f in
           ("argument", "output", "alias", "temp", "generated_code")}
    mem["total"] = (mem["argument"] + mem["output"] - mem["alias"]
                    + mem["temp"] + mem["generated_code"])
    return mem


def phase_trainer(p: dict = TRAIN) -> None:
    cfg = dataclasses.replace(get_config(p["arch"]), n_layers=p["layers"])
    opt = adamw(lr=3e-4, state_dtype=cfg.opt_state_dtype)
    params = tfm.init_params(cfg, jax.random.PRNGKey(SEED))
    n_params = sum(a.size for a in jax.tree.leaves(params))
    tokens = p["workers"] * p["slots"] * p["batch"] * p["seq"]
    say("trainer", arch=cfg.name, layers=cfg.n_layers, d_model=cfg.d_model,
        heads=f"{cfg.n_heads}x{cfg.head_dim}", d_ff=cfg.d_ff,
        vocab=cfg.vocab, params=n_params, tokens_per_step=tokens)

    sb, w, parts = check_inputs(cfg, p)
    grad_check(cfg, params, sb, w, parts)

    opt_state = opt.init(params)
    state_shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (params, opt_state))
    times = []
    for rec in train_coded(cfg, opt, params, opt_state, steps=p["steps"],
                           batch=p["batch"], seq=p["seq"],
                           workers=p["workers"], n_slots=p["slots"]):
        times.append(rec.seconds)
        say("trainer", step=rec.step, loss=f"{rec.loss:.6f}",
            step_s=f"{rec.seconds:.6f}", decode_ok=rec.epoch.decode_ok,
            slots=rec.epoch.plan.n_slots,
            allocator_peak_bytes_in_use_process_lifetime=
            device_memory()["peak_bytes_in_use"])
        check(math.isfinite(rec.loss), f"step {rec.step}: loss {rec.loss}")
        check(rec.epoch.plan.n_slots == p["slots"],
              f"step {rec.step}: slot width {rec.epoch.plan.n_slots} "
              f"recompiled the step")
    del params, opt_state
    steady = float(np.median(times[1:]))
    say("trainer", steps=len(times), first_step_s_with_compile=
        f"{times[0]:.3f}", median_step_s=f"{steady:.6f}",
        tokens_per_s=f"{tokens / steady:.1f}")

    mem = step_memory(cfg, opt, state_shapes, sb, w)
    stats = device_memory()
    say("trainer", compiled_step_bytes=mem["total"],
        compiled_step_gib=f"{mem['total'] / GiB:.3f}",
        device_limit_gib=f"{stats['bytes_limit'] / GiB:.3f}",
        **{f"{k}_bytes": v for k, v in mem.items() if k != "total"})
    check(mem["total"] <= stats["bytes_limit"],
          f"compiled step needs {mem['total']} B, device holds "
          f"{stats['bytes_limit']} B")


# --------------------------------------------------------------------- #
# (d) co-simulator
# --------------------------------------------------------------------- #
def lanes_equal(x, y) -> bool:
    """Per-lane bitwise equality: decode_ok, epoch time, weights and every
    CommStats field."""
    if (x.decode_ok != y.decode_ok or x.time != y.time
            or not np.array_equal(x.weights, y.weights)):
        return False
    return all(np.array_equal(getattr(x.comm, f.name),
                              getattr(y.comm, f.name))
               for f in dataclasses.fields(x.comm))


def mismatched_lanes(a: FleetRun, b: FleetRun, n: int) -> int:
    return sum(not lanes_equal(a.results[e][i], b.results[e][i])
               for e in range(a.n_epochs) for i in range(n))


def head(run: FleetRun, n: int) -> FleetRun:
    """The first ``n`` lanes of a fleet run."""
    return dataclasses.replace(run, seeds=run.seeds[:n],
                               results=[r[:n] for r in run.results])


def phase_cosim(p: dict = COSIM) -> None:
    fleet = Fleet(scenario_spec(p["scenario"]))
    n = p["oracle_lanes"]
    t0 = time.perf_counter()
    oracle = fleet.run(p["scheme"], range(n), n_epochs=p["epochs"],
                       engine="oracle")
    say("cosim", engine="oracle", lanes=n, epochs=p["epochs"],
        seconds=f"{time.perf_counter() - t0:.3f}")
    want = oracle.summary()
    runs = {}
    for engine in ("device", "batched"):
        t0 = time.perf_counter()
        run = fleet.run(p["scheme"], range(p["lanes"]), n_epochs=p["epochs"],
                        engine=engine)
        dt = time.perf_counter() - t0
        runs[engine] = run
        bad = mismatched_lanes(run, oracle, n)
        got = head(run, n).summary()
        stat = dict(
            fail=abs(got.decode_failure_rate - want.decode_failure_rate),
            time_rel=abs(got.mean_time - want.mean_time) / want.mean_time,
            jain=abs(got.jain_fairness - want.jain_fairness))
        contract = ("bitwise" if bad == 0 else
                    "statistical" if all(stat[k] <= STAT_TOL[k]
                                         for k in stat) else "none")
        s = run.summary()
        say("cosim", engine=engine, lanes=p["lanes"], epochs=p["epochs"],
            seconds_with_compile=f"{dt:.3f}",
            oracle_lanes=n, lanes_not_bitwise=f"{bad}/{n * p['epochs']}",
            d_fail=f"{stat['fail']:.4f}", d_time_rel=f"{stat['time_rel']:.2e}",
            d_jain=f"{stat['jain']:.2e}", contract=contract,
            fail_rate=f"{s.decode_failure_rate:.4f}",
            mean_time=f"{s.mean_time:.6f}", jain=f"{s.jain_fairness:.6f}")
        check(contract != "none", f"{engine} engine disagrees with the "
              f"oracle beyond {STAT_TOL}")
    bad = mismatched_lanes(runs["device"], runs["batched"], p["lanes"])
    say("cosim", device_vs_batched_lanes_not_bitwise=
        f"{bad}/{p['lanes'] * p['epochs']}")


# --------------------------------------------------------------------- #
# (e) soak
# --------------------------------------------------------------------- #
def phase_soak(p: dict = SOAK) -> None:
    spec = scenario_spec(p["scenario"])
    lanes = [SoakLane(scenario=spec.with_overrides(V=v)) for v in p["V"]]
    t0 = time.perf_counter()
    res = run_soak(lanes, p["slots"])
    dt = time.perf_counter() - t0
    for j, v in enumerate(p["V"]):
        q, ceiling = float(res.mean_qtot[j]), 50.0 + 25.0 * v
        say("soak", scenario=spec.name, V=v, slots=p["slots"],
            mean_qtot=f"{q:.4f}", ceiling=ceiling,
            throughput=f"{float(res.throughput[j]):.6f}",
            jain=f"{float(res.jain[j]):.6f}")
        check(math.isfinite(q) and q <= ceiling,
              f"soak V={v}: mean backlog {q} > {ceiling}")
    say("soak", lanes=len(lanes), seconds_with_compile=f"{dt:.3f}",
        lane_slots_per_s=f"{len(lanes) * p['slots'] / dt:.1f}")


# --------------------------------------------------------------------- #
# --chips 4: the sharded fleet
# --------------------------------------------------------------------- #
def phase_mesh(p: dict = MESH) -> None:
    # where the device engine's seed-axis shard_map puts each lane on the
    # mesh that mesh="auto" builds
    placement = NamedSharding(fleet_mesh(), PartitionSpec(SEED_AXIS)) \
        .addressable_devices_indices_map((p["lanes"],))
    for dev, (rows,) in placement.items():
        say("mesh", shard_device=dev, lanes=f"{rows.start}:{rows.stop}")
    check(len(placement) == len(jax.devices()),
          f"lanes sit on {len(placement)} devices, not on all "
          f"{len(jax.devices())}")
    fleet = Fleet(scenario_spec(p["scenario"]))
    seeds = range(p["lanes"])
    t0 = time.perf_counter()
    sharded = fleet.run(p["scheme"], seeds, n_epochs=p["epochs"],
                        engine="device", mesh="auto")
    t_sharded = time.perf_counter() - t0
    t0 = time.perf_counter()
    single = fleet.run(p["scheme"], seeds, n_epochs=p["epochs"],
                       engine="device")
    t_single = time.perf_counter() - t0
    bad = mismatched_lanes(sharded, single, p["lanes"])
    say("mesh", lanes=p["lanes"], epochs=p["epochs"],
        sharded_seconds_with_compile=f"{t_sharded:.3f}",
        unsharded_seconds_with_compile=f"{t_single:.3f}",
        lanes_not_bitwise=f"{bad}/{p['lanes'] * p['epochs']}")
    check(bad == 0, "sharded and unsharded fleets differ")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the seed-sharded fleet over four chips")
    args = ap.parse_args(argv)
    info = device_info(args.chips)
    say("cache", dir=enable_compile_cache())
    phases = ([phase_mesh] if args.chips == 4 else
              [phase_kernels, phase_trainer, phase_cosim, phase_soak])
    for phase in phases:
        t0 = time.perf_counter()
        phase()
        say(phase.__name__[len("phase_"):], ok=True,
            seconds=f"{time.perf_counter() - t0:.3f}")
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
