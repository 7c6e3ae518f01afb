"""Readings that a cell's limits are set from.

    python3 chipbench/calibrate.py --workload train-stablelm-coded \\
        --seeds 11,12,13 --modes program,control,half,token \\
        --out chiprun_out/calibrate.jsonl

For each seed it runs the plain reference once, then each mode, and
compares each with the reference by the cell's own ``compare``.  For a
training cell (driver ``train``):

    program   the system as the benchmark runs it (set-up's checked steps)
    control   the reference itself in the system's place, computed with
              float8 e4m3 matrix products, one step below the bfloat16 the
              configuration states
    half      the system with half of the batch left out: partitions
              K/2..K-1 get weight 0 and the rest twice theirs
    token     the system with one token altered where it is produced: the
              token at position 1 of partition 0, in every slot holding it
    unchanged the system with a step that returns its state unchanged

It is not part of a benchmark run.  Runs on the chip unless ``--cpu`` and
``--tiny`` (a size a test can hold) are given.
"""
import argparse
import contextlib
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

TINY = {"config": {"hidden_size": 128, "intermediate_size": 256,
                   "num_attention_heads": 4, "num_key_value_heads": 4,
                   "num_hidden_layers": 2, "vocab_size": 4096},
        "traffic": {"seq_len": 16}}


@contextlib.contextmanager
def fault(mode: str, K: int, vocab: int):
    """Break the timed path underneath: the trainer's runtime, slot batch
    or step, as the mode says."""
    from chipbench.harness import patched
    from repro.launch import train as T
    if mode in ("program", "control"):
        yield
        return
    if mode == "half":
        make = T.coded_runtime

        def coded_runtime(*a, **k):
            rt = make(*a, **k)
            run_epoch = rt.run_epoch

            def halved(epoch):
                res = run_epoch(epoch)
                keep = (res.plan.slot_partition >= 0) \
                    & (res.plan.slot_partition < K // 2)
                return dataclasses.replace(
                    res, weights=res.weights * 2.0 * keep)
            rt.run_epoch = halved
            return rt
        with patched(T, coded_runtime=coded_runtime):
            yield
    elif mode == "token":
        make = T.slot_batch

        def slot_batch(ds, plan, step):
            sb = make(ds, plan, step)
            rows = plan.slot_partition == 0
            toks = sb["tokens"]
            new = (toks[..., 1] + vocab // 2) % vocab
            sb["tokens"] = toks.at[..., 1].set(
                T.jnp.where(T.jnp.asarray(rows)[..., None], new,
                            toks[..., 1]))
            return sb
        with patched(T, slot_batch=slot_batch):
            yield
    elif mode == "unchanged":
        make = T.make_coded_train_step

        def make_coded_train_step(loss_fn, opt):
            step = make(loss_fn, opt)

            def frozen(params, opt_state, slot_batch, weights):
                _, _, aux = step(params, opt_state, slot_batch, weights)
                return params, opt_state, aux
            return frozen
        with patched(T, make_coded_train_step=make_coded_train_step):
            yield
    else:
        raise ValueError(f"unknown mode {mode!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--modes", default="program,control,half,token")
    ap.add_argument("--out", default=None)
    ap.add_argument("--cpu", action="store_true")
    ap.add_argument("--tiny", action="store_true")
    args = ap.parse_args(argv)

    import jax
    from chipbench import harness
    from chipbench.references import dense_lm
    bench = harness.load_benchmark()
    cell = harness.workload(bench, args.workload)
    traffic = harness.load_json("traffic", cell["traffic"])
    over = TINY if args.tiny else {}
    config = {**harness.load_json("configs", cell["config"]),
              **over.get("config", {})}
    traffic = {**traffic, **over.get("traffic", {})}
    if not args.cpu:
        harness.require_chip(cell["chips"])
    harness.enable_cache()
    driver = harness.load_module("drivers", traffic["driver"])
    K = 2 * int(traffic["workers"])
    vocab = int(config["vocab_size"])
    batches = driver.reference_batches(config, traffic)
    out = open(args.out, "a") if args.out else None
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        ref = dense_lm.train_readings(config, traffic, seed, batches)
        print(f"seed {seed} reference {time.perf_counter() - t:.1f}s "
              f"loss {ref['loss']}", file=sys.stderr, flush=True)
        for mode in args.modes.split(","):
            t = time.perf_counter()
            if mode == "control":
                prog = dense_lm.train_readings(config, traffic, seed,
                                               batches, precision="fp8")
            else:
                with fault(mode, K, vocab):
                    state = driver.setup(config, traffic, seed)
                    driver.finish(state)
                prog = state.readings
                del state
            gc.collect()
            numbers = driver.compare(prog, ref)
            worst = {k: ref["leaves"][int(i)] for k, i in (
                ("grad", (abs(prog["grad_norm"] - ref["grad_norm"])).argmax()),
                ("update", (abs(prog["change_norm"] - ref["change_norm"])
                            ).argmax()))}
            row = {"workload": args.workload, "seed": seed, "mode": mode,
                   "seconds": round(time.perf_counter() - t, 3),
                   "device": jax.devices()[0].device_kind, **numbers,
                   "loss": list(prog["loss"]), "ref_loss": ref["loss"],
                   "stage2": prog.get("stage2"),
                   "worst_leaf": worst}
            print(json.dumps(row), flush=True)
            if out:
                out.write(json.dumps(row) + "\n")
                out.flush()
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
