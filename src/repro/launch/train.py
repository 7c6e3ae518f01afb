"""End-to-end training driver.

Runs the full stack: synthetic partitioned data pipeline → (optionally)
two-stage coded gradient runtime → train step → checkpointing/resume.
``--reduced`` (the default) picks an architecture's small config,
``--no-reduced`` its published one.  The coded path donates params and
optimizer state to its step, so a model whose state alone takes half the
device's memory still fits.

Examples:
  python -m repro.launch.train --arch tiny --steps 50
  python -m repro.launch.train --arch qwen3-14b --reduced --steps 20 --coded
  python -m repro.launch.train --preset 100m --steps 300 --ckpt-dir ck
"""
from __future__ import annotations

import argparse
import dataclasses
import math
import time
from typing import Iterator

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import Checkpointer
from repro.configs.base import ModelConfig, get_config
from repro.core.coded_step import (computed_rows, make_coded_train_step,
                                   make_train_step)
from repro.core.runtime import EpochResult, TwoStageRuntime
from repro.data.pipeline import SyntheticLMDataset
from repro.launch.compile_cache import enable_compile_cache
from repro.models import transformer as tfm
from repro.optim import adamw
from repro.telemetry.annotation import annotate

TINY = ModelConfig(name="tiny", family="dense", n_layers=2, d_model=128,
                   n_heads=4, n_kv_heads=2, head_dim=32, d_ff=256,
                   vocab=512)
PRESET_100M = ModelConfig(name="preset-100m", family="dense", n_layers=12,
                          d_model=768, n_heads=12, n_kv_heads=4, head_dim=64,
                          d_ff=3072, vocab=16384)


def _config(args) -> ModelConfig:
    if args.preset == "100m":
        cfg = PRESET_100M
    elif args.arch == "tiny":
        cfg = TINY
    else:
        cfg = get_config(args.arch, reduced=args.reduced)
    return cfg


def per_slot_lm_loss(cfg: ModelConfig):
    """(params, slot_batch) -> (M, n_slots) mean next-token CE per slot."""
    def fn(params, slot_batch):
        toks = slot_batch["tokens"]          # (M, n_slots, b, S)
        labs = slot_batch["labels"]
        w = slot_batch["weights"]            # (M, n_slots, b, S)
        M_, K_, b, S = toks.shape
        batch = {"tokens": toks.reshape(M_ * K_ * b, S),
                 "labels": labs.reshape(M_ * K_ * b, S),
                 "weights": jnp.ones((M_ * K_ * b, S), jnp.float32)}
        x, aux, _ = tfm.forward(params, batch, cfg)
        head = (params["embed"].T if cfg.tie_embeddings
                else params["lm_head"]).astype(x.dtype)
        logits = (x @ head).astype(jnp.float32)
        ll = jax.nn.log_softmax(logits)
        ce = -jnp.take_along_axis(ll, batch["labels"][..., None],
                                  axis=-1)[..., 0]
        ce = (ce * w.reshape(M_ * K_ * b, S)).sum(-1) \
            / jnp.maximum(w.reshape(M_ * K_ * b, S).sum(-1), 1e-9)
        return ce.reshape(M_, K_, b).mean(-1)
    return fn


def coded_runtime(workers: int, *, straggler_prob: float = 0.2,
                  n_slots: int = 0, seed: int = 0) -> TwoStageRuntime:
    """The coded path's epoch planner: ``workers`` heterogeneous simulated
    workers (rates 1..4) over K = 2·workers data partitions.  ``n_slots``
    pins the slot width so every epoch shares one step compile (0 sizes
    each epoch to its plan)."""
    return TwoStageRuntime(workers, 2 * workers, max(workers // 2, 2),
                           rates=np.linspace(1.0, 4.0, workers),
                           straggler_prob=straggler_prob, n_slots=n_slots,
                           seed=seed)


def slot_batch(ds, plan, step: int) -> dict:
    """Lay the epoch's partitions out in the plan's (M, n_slots, b, S)
    layout; unused slots (partition -1) get zeros, and zero weight.

    Profiler spans: ``coded.batch.data`` (the plan's distinct partitions,
    drawn on the host in one :meth:`~SyntheticLMDataset.host_partitions`
    call), ``coded.batch.layout`` (one gather per key into the slot
    layout, padding slots reading an appended zero row) and
    ``coded.batch.h2d`` (the slot batch to the device, one transfer).
    """
    sp = plan.slot_partition
    ks = np.unique(sp[sp >= 0])
    with annotate("coded.batch.data", partitions=len(ks)):
        parts = ds.host_partitions(step, ks)
    with annotate("coded.batch.layout"):
        idx = np.where(sp >= 0, np.searchsorted(ks, sp), len(ks))
        host = {key: np.concatenate(
            [v, np.zeros((1,) + v.shape[1:], v.dtype)])[idx]
            for key, v in parts.items()}
    with annotate("coded.batch.h2d"):
        return jax.device_put(host)


def slot_counts(plan, weights, batch_shape) -> dict:
    """The ``coded.batch`` span's counters for one step, from what the
    step is handed: the plan's ``slot_partition`` (M, n_slots), the decode
    weights (M, n_slots) and the slot batch's shape (M, n_slots, b, S).
    Padding slots hold partition -1 and zero weight; ``partition_tokens``
    are the tokens of the distinct partitions the plan holds;
    ``computed_rows`` and ``computed_slots`` are the rows of the layout,
    and their slots, that the step computes, by the step's own rule."""
    sp = plan.slot_partition
    used = sp[sp >= 0]
    partitions = len(np.unique(used))
    rows = int(computed_rows(np.asarray(weights, np.float32), np))
    return {"slots": int(sp.size), "used_slots": int(used.size),
            "padding_slots": int(sp.size - used.size),
            "computed_rows": rows, "computed_slots": rows * sp.shape[1],
            "partitions": partitions,
            "slot_tokens": math.prod(batch_shape),
            "partition_tokens": partitions * math.prod(batch_shape[2:])}


def coded_step_fn(cfg: ModelConfig, opt):
    """The jitted coded train step ``(params, opt_state, slot_batch,
    weights)``.  Params and optimizer state are donated: the update writes
    over them."""
    return jax.jit(make_coded_train_step(per_slot_lm_loss(cfg), opt),
                   donate_argnums=(0, 1))


@dataclasses.dataclass
class CodedStep:
    """One coded training step: its loss, its device time (dispatch to
    ``block_until_ready``, the ``coded.device_step`` span; the first
    step's includes compilation) and the runtime's epoch result (plan,
    weights, simulated epoch time)."""
    step: int
    loss: float
    seconds: float
    epoch: EpochResult


def train_coded(cfg: ModelConfig, opt, params, opt_state, *, steps: int,
                batch: int, seq: int, workers: int = 6,
                straggler_prob: float = 0.2, n_slots: int = 0,
                start_step: int = 0, ckpt=None, ckpt_every: int = 25
                ) -> Iterator[CodedStep]:
    """Run the two-stage coded loop, yielding one :class:`CodedStep` per
    step.  ``params``/``opt_state`` are donated to the first step: the
    caller's arrays are consumed.

    Each step is a ``coded.step`` profiler span (a step marker, closed
    before the yield, so the consumer's time lies outside it) holding
    ``coded.plan`` (the runtime's epoch plan), ``coded.batch`` (the slot
    batch, see :func:`slot_batch`, and the decode weights' transfer; its
    args are :func:`slot_counts` with ``step``, ``stage2`` and
    ``decode_ok``), ``coded.device_step`` (dispatch to
    ``block_until_ready``, the interval ``CodedStep.seconds`` times) and
    ``coded.loss_fetch``."""
    runtime = coded_runtime(workers, straggler_prob=straggler_prob,
                            n_slots=n_slots)
    ds = SyntheticLMDataset(runtime.K, examples_per_partition=batch,
                            seq_len=seq, vocab=cfg.vocab)
    step_fn = coded_step_fn(cfg, opt)
    for step in range(start_step, steps):
        with annotate("coded.step", step_num=step):
            with annotate("coded.plan"):
                res = runtime.run_epoch(step)
            with annotate("coded.batch", step=step) as span:
                sb = slot_batch(ds, res.plan, step)
                w = jnp.asarray(res.weights, jnp.float32)
                if span.is_enabled():       # a profiler trace is running
                    span.set_metadata(
                        **slot_counts(res.plan, res.weights,
                                      sb["tokens"].shape),
                        stage2=bool(res.stage2_triggered),
                        decode_ok=bool(res.decode_ok))
            with annotate("coded.device_step"):
                t0 = time.perf_counter()
                params, opt_state, aux = jax.block_until_ready(
                    step_fn(params, opt_state, sb, w))
                dt = time.perf_counter() - t0
            if ckpt and step and step % ckpt_every == 0:
                ckpt.async_save(step, {"params": params, "opt": opt_state})
            with annotate("coded.loss_fetch"):
                loss = float(aux["loss"])
        yield CodedStep(step=step, loss=loss, seconds=dt, epoch=res)
    if ckpt:
        ckpt.wait()


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--preset", default=None)
    ap.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="the architecture's small config (--no-reduced: "
                         "its published widths)")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--coded", action="store_true",
                    help="two-stage coded gradient runtime (simulated "
                         "heterogeneous workers)")
    ap.add_argument("--workers", type=int, default=6)
    ap.add_argument("--straggler-prob", type=float, default=0.2)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args(argv)

    cfg = _config(args)
    if cfg.family in ("vlm", "audio"):
        raise SystemExit("train driver covers LM families; use the smoke "
                         "tests for frontend-stub archs")
    enable_compile_cache()
    opt = adamw(lr=args.lr, state_dtype=cfg.opt_state_dtype)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"coded={args.coded} steps={args.steps}")

    start_step = 0
    opt_state = opt.init(params)
    ck = Checkpointer(args.ckpt_dir) if args.ckpt_dir else None
    if ck and ck.latest_step() is not None:
        start_step, t = ck.restore({"params": params, "opt": opt_state})
        params, opt_state = t["params"], t["opt"]
        print(f"resumed from step {start_step}")
    t0 = time.time()

    if args.coded:
        for rec in train_coded(cfg, opt, params, opt_state,
                               steps=args.steps, batch=args.batch,
                               seq=args.seq, workers=args.workers,
                               straggler_prob=args.straggler_prob,
                               start_step=start_step,
                               ckpt=ck, ckpt_every=args.ckpt_every):
            if rec.step % args.log_every == 0:
                res = rec.epoch
                print(f"step {rec.step:4d} loss={rec.loss:.4f} "
                      f"step_s={rec.seconds:.3f} "
                      f"sim_epoch_time={res.time:.3f} "
                      f"util={res.utilization:.2f} "
                      f"stragglers={res.n_stragglers}")
        print(f"done in {time.time()-t0:.1f}s")
        return

    # plain data-parallel training
    ds = SyntheticLMDataset(1, examples_per_partition=args.batch,
                            seq_len=args.seq, vocab=cfg.vocab)

    def loss_fn(params, batch):
        return tfm.loss_fn(params, batch, cfg)

    step_fn = jax.jit(make_train_step(loss_fn, opt, clip_norm=1.0))
    for step in range(start_step, args.steps):
        part = ds.partition(step, 0)
        batch = {"tokens": part["tokens"], "labels": part["labels"],
                 "weights": part["weights"]}
        params, opt_state, aux = step_fn(params, opt_state, batch)
        if step % args.log_every == 0:
            dt = (time.time() - t0) / max(step - start_step + 1, 1)
            print(f"step {step:4d} loss={float(aux['loss']):.4f} "
                  f"gnorm={float(aux['grad_norm']):.2f} {dt:.2f}s/step")
        if ck and step and step % args.ckpt_every == 0:
            ck.async_save(step, {"params": params, "opt": opt_state})
    if ck:
        ck.wait()
    print(f"done in {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
