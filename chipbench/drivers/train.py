"""Driver ``train``: the two-stage coded training loop users run,
``repro.launch.train.train_coded``, driven as one generator.

Set-up makes the weights on the device from the seed (the reference's own
initializer), builds the generator once, and pulls its first
``setup_steps`` steps through it: the first compiles (or loads from the
compile cache); the first ``checked_steps`` are the ones the reference
follows, and they have to hold a step that the runtime's stage 2 re-plans
and one that it does not.  While the generator is suspended after step 1
and after step ``checked_steps``, the driver reads the optimizer's first
moment and the parameters out of its frame (they are donated to the next
step).  The window goes on pulling steps from the same generator.

Each step's tokens are counted from what the program hands its step: the
slot tokens are the slot batch's whole shape, and the decoded tokens the
tokens of the partitions its plan holds, on a step whose decode succeeds.

A step's wall time is the host clock between two yields: the runtime's
epoch plan, the slot batch, the device step and the loss fetch together.
``CodedStep.seconds`` is the device step alone (dispatch to
``block_until_ready``).
"""
from __future__ import annotations

import dataclasses
import math
import time
from contextlib import ExitStack

import jax
import numpy as np

from chipbench import harness
from chipbench.flops import dense_lm_train_flops_per_token
from chipbench.references import dense_lm

#: The harness's own host spans; the trace reduction labels idle device
#: time with the innermost one that covers it.
ANNOTATIONS = ("step", "plan", "batch", "device_step")


@dataclasses.dataclass
class State:
    config: dict
    traffic: dict
    seed: int
    gen: object
    patches: ExitStack
    readings: dict
    counts: dict
    marks: dict


def program_config(config: dict):
    """The system's ``ModelConfig`` for a configuration file."""
    from repro.configs.base import ModelConfig
    s = dense_lm.sizes(config)
    layer = (config["architecture"]["family"], config["norm"],
             config["partial_rotary_factor"], config["use_qkv_bias"],
             config["architecture"]["ffn"])
    if layer != ("dense", "rms-one-plus-gain", 1.0, False, "gated-silu"):
        raise ValueError(f"the train driver runs the dense layer of "
                         f"dense_lm.py only, got {layer}")
    prec = config["precision"]
    return ModelConfig(
        name=config["name"], family="dense", n_layers=s["layers"],
        d_model=s["d"], n_heads=s["heads"], n_kv_heads=s["kv_heads"],
        head_dim=s["head_dim"], d_ff=s["ffn"], vocab=s["vocab"],
        rope_theta=s["theta"], norm_eps=s["eps"], act="silu", norm="rms",
        gated_ffn=True, tie_embeddings=bool(config["tie_word_embeddings"]),
        param_dtype=prec["params"], compute_dtype=prec["compute"],
        opt_state_dtype=prec["optimizer_state"])


def optimizer(traffic: dict, state_dtype: str):
    from repro.optim import adamw
    o = traffic["optimizer"]
    if o["name"] != "adamw":
        raise ValueError(f"the train driver runs adamw, got {o['name']!r}")
    return adamw(lr=float(o["lr"]), b1=float(o["b1"]), b2=float(o["b2"]),
                 eps=float(o["eps"]), weight_decay=float(o["weight_decay"]),
                 state_dtype=state_dtype)


def annotated(stack: ExitStack, counts: dict):
    """Wrap the trainer's plan, slot batch and step dispatch in the
    harness's ``TraceAnnotation`` spans (module attributes restored when
    ``stack`` closes), and add each slot batch's tokens to ``counts``:
    ``slot_tokens``, all that the step computes, and ``partition_tokens``,
    those of the distinct partitions its plan holds.  The arithmetic is
    untouched."""
    from repro.launch import train as T
    ann = jax.profiler.TraceAnnotation
    make_runtime, make_batch, make_step = (T.coded_runtime, T.slot_batch,
                                           T.coded_step_fn)

    def coded_runtime(*a, **k):
        rt = make_runtime(*a, **k)
        run_epoch = rt.run_epoch

        def planned(epoch):
            with ann("plan"):
                return run_epoch(epoch)
        rt.run_epoch = planned
        return rt

    def slot_batch(ds, plan, step):
        with ann("batch"):
            sb = make_batch(ds, plan, step)
        shape = sb["tokens"].shape             # (M, n_slots, b, S)
        used = plan.slot_partition[plan.slot_partition >= 0]
        counts["slot_tokens"] += math.prod(shape)
        counts["partition_tokens"] += len(np.unique(used)) * math.prod(
            shape[2:])
        return sb

    def coded_step_fn(*a, **k):
        step = make_step(*a, **k)

        def dispatched(*args):
            with ann("device_step"):
                return step(*args)
        return dispatched

    stack.enter_context(harness.patched(
        T, coded_runtime=coded_runtime, slot_batch=slot_batch,
        coded_step_fn=coded_step_fn))


def setup(config: dict, traffic: dict, seed: int) -> State:
    from repro.launch.train import train_coded
    cfg = program_config(config)
    opt = optimizer(traffic, cfg.opt_state_dtype)
    b1 = float(traffic["optimizer"]["b1"])
    W, slots = int(traffic["workers"]), int(traffic["slots_per_worker"])
    b, S = int(traffic["sequences_per_partition"]), int(traffic["seq_len"])
    n_setup, n_checked = int(traffic["setup_steps"]), \
        int(traffic["checked_steps"])
    if not 1 <= n_checked <= n_setup:
        raise ValueError("need 1 <= checked_steps <= setup_steps")

    marks = {"driver": time.perf_counter()}
    patches = ExitStack()
    counts = {"slot_tokens": 0, "partition_tokens": 0}
    annotated(patches, counts)
    params = dense_lm.init_params(config, seed)
    opt_state = jax.block_until_ready(jax.jit(opt.init)(params))
    marks["weights"] = time.perf_counter()
    gen = train_coded(cfg, opt, params, opt_state, steps=2 ** 62, batch=b,
                      seq=S, workers=W,
                      straggler_prob=float(traffic["straggler_prob"]),
                      n_slots=slots)
    del params, opt_state           # donated to the first step

    readings = {"loss": [], "decode_ok": [], "stage2": []}
    for i in range(n_setup):
        rec = next(gen)
        marks[f"step{i}"] = time.perf_counter()
        if i < n_checked:
            readings["loss"].append(rec.loss)
            readings["decode_ok"].append(bool(rec.epoch.decode_ok))
            readings["stage2"].append(bool(rec.epoch.stage2_triggered))
        if i == 0:
            frame = gen.gi_frame.f_locals
            m = frame["opt_state"].m
            readings["grad_norm"] = np.asarray(dense_lm.leaf_norms(m),
                                               np.float64) / (1.0 - b1)
            readings["embed_rows"] = np.asarray(
                dense_lm.row_support(m["embed"]))
            del frame, m
        if i == n_checked - 1:
            frame = gen.gi_frame.f_locals
            start = dense_lm.init_params(config, seed)
            readings["change_norm"] = np.asarray(
                dense_lm.leaf_diff_norms(frame["params"], start), np.float64)
            del frame, start
    return State(config=config, traffic=traffic, seed=seed, gen=gen,
                 patches=patches, readings=readings, counts=counts,
                 marks=marks)


def window(state: State, seconds: float) -> dict:
    steps = []
    ann = jax.profiler.TraceAnnotation
    counts = state.counts
    t0 = t_prev = time.perf_counter()
    while True:
        slot0, part0 = counts["slot_tokens"], counts["partition_tokens"]
        with ann("step"):
            rec = next(state.gen)
        t = time.perf_counter()
        ok = bool(rec.epoch.decode_ok)
        steps.append({"wall_s": t - t_prev, "device_s": rec.seconds,
                      "decode_ok": ok,
                      "stage2": bool(rec.epoch.stage2_triggered),
                      "loss_finite": math.isfinite(rec.loss),
                      "slot_tokens": counts["slot_tokens"] - slot0,
                      "decoded_tokens": (counts["partition_tokens"] - part0
                                         if ok else 0)})
        t_prev = t
        if t - t0 >= seconds:
            break
    failed = sum(not (s["decode_ok"] and s["loss_finite"]) for s in steps)
    walls = np.asarray([s["wall_s"] for s in steps])
    dev = np.asarray([s["device_s"] for s in steps])
    summary = {"steps": len(steps), "stage2_steps": sum(s["stage2"]
                                                        for s in steps),
               "wall_median_s": float(np.median(walls)),
               "wall_max_s": float(walls.max()),
               "device_median_s": float(np.median(dev)),
               "first_wall_s": float(walls[0])}
    return {"steps": steps, "t0": t0, "t1": t_prev, "summary": summary,
            "attempted": len(steps), "failed": failed,
            "flops_per_token": dense_lm_train_flops_per_token(
                state.config, int(state.traffic["seq_len"]))}


def finish(state: State) -> None:
    """Free the program's state: closing the generator drops its frame."""
    state.gen.close()
    state.gen = None
    state.patches.close()


def reference_batches(config: dict, traffic: dict) -> list:
    s = dense_lm.sizes(config)
    return [dense_lm.lm_step_batch(
        s["vocab"], int(traffic["seq_len"]),
        int(traffic["sequences_per_partition"]), int(traffic["data_seed"]),
        t, 2 * int(traffic["workers"]))
        for t in range(int(traffic["checked_steps"]))]


def compare(prog: dict, ref: dict) -> dict:
    """The numbers compared, from the program's readings and the
    reference's (see PERF.md, "How correct is decided").

    loss_gap           max over the checked steps of |L − L_ref| / |L_ref|
    grad_norm_gap      worst leaf of |‖g‖ − ‖g_ref‖| / max(‖g_ref‖, median)
    update_norm_gap    the same for the parameters' change over the
                       checked steps, over the leaves whose reference
                       gradient is at least 1e-3 of the median leaf's
    embed_rows_differ  embedding rows with a nonzero first gradient in
                       one of the two and not the other
    """
    lp, lr = np.asarray(prog["loss"]), np.asarray(ref["loss"])
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    gr, gp = ref["grad_norm"], prog["grad_norm"]
    g_med = float(np.median(gr))
    grad_gap = float(np.max(np.abs(gp - gr) / np.maximum(gr, g_med)))
    moved = gr >= 1e-3 * g_med
    cr, cp = ref["change_norm"][moved], prog["change_norm"][moved]
    c_med = float(np.median(cr))
    upd_gap = float(np.max(np.abs(cp - cr) / np.maximum(cr, c_med)))
    rows = int(np.sum(prog["embed_rows"] != ref["embed_rows"]))
    return {"loss_gap": loss_gap, "grad_norm_gap": grad_gap,
            "update_norm_gap": upd_gap, "embed_rows_differ": float(rows)}


def check(state: State, win: dict, limits: dict) -> dict:
    """The reference's gaps (:func:`compare`), and two counts over the
    checked steps, each with the limit 0: the steps whose decode failed,
    and the kinds of step (stage 1 alone; stage 1 re-planned by stage 2)
    that none of them was, so that both plans' decode weights are
    compared."""
    ref = dense_lm.train_readings(state.config, state.traffic, state.seed,
                                  reference_batches(state.config,
                                                    state.traffic))
    numbers = compare(state.readings, ref)
    numbers["undecoded_checked_steps"] = float(
        state.readings["decode_ok"].count(False))
    numbers["stage_kinds_unchecked"] = float(
        len({True, False} - set(state.readings["stage2"])))
    return {k: {"value": v, "limit": float(limits[k])}
            for k, v in numbers.items()}
