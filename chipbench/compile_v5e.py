"""Compile a training cell's programs for a described TPU v5e, no chip.

    JAX_PLATFORMS=cpu python3 chipbench/compile_v5e.py \\
        --workload train-stablelm-coded

Compiles, at the cell's own sizes, the system's coded step (the program
the window drives) and the reference's gradient and AdamW programs, and
prints each one's ``memory_analysis`` against the chip's 16 GB.  What the
chip's compiler would refuse, it refuses here.  A compile that passes is
not a chip run.
"""
import argparse
import os
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def total_bytes(compiled) -> dict:
    ma = compiled.memory_analysis()
    mem = {f: int(getattr(ma, f"{f}_size_in_bytes")) for f in
           ("argument", "output", "alias", "temp", "generated_code")}
    mem["total"] = (mem["argument"] + mem["output"] - mem["alias"]
                    + mem["temp"] + mem["generated_code"])
    return mem


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)

    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import harness
    jax.config.update("jax_enable_compilation_cache", False)
    cell = harness.workload(harness.load_benchmark(), args.workload)
    config = harness.load_json("configs", cell["config"])
    traffic = harness.load_json("traffic", cell["traffic"])
    driver = harness.load_module("drivers", traffic["driver"])

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    def shaped(tree):
        return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
            a.shape, a.dtype, sharding=one), tree)

    report = train_programs(config, traffic, driver, shaped)
    for name, mem in report.items():
        print(f"{name}: total {mem['total'] / 2 ** 30:.3f} GiB "
              + " ".join(f"{k}={v}" for k, v in mem.items()))
    return 0


def train_programs(config, traffic, driver, shaped) -> dict:
    import jax
    import jax.numpy as jnp

    from chipbench.references import dense_lm
    from repro.launch.train import coded_step_fn
    cfg = driver.program_config(config)
    opt = driver.optimizer(traffic, cfg.opt_state_dtype)
    params = jax.eval_shape(lambda: dense_lm.init_params(config, 0))
    opt_state = jax.eval_shape(opt.init, params)
    W, n = int(traffic["workers"]), int(traffic["slots_per_worker"])
    b, S = int(traffic["sequences_per_partition"]), int(traffic["seq_len"])
    K = 2 * W
    sb = {"tokens": jax.ShapeDtypeStruct((W, n, b, S), jnp.int32),
          "labels": jax.ShapeDtypeStruct((W, n, b, S), jnp.int32),
          "weights": jax.ShapeDtypeStruct((W, n, b, S), jnp.float32)}
    w = jax.ShapeDtypeStruct((W, n), jnp.float32)
    step = coded_step_fn(cfg, opt).lower(
        shaped(params), shaped(opt_state), shaped(sb), shaped(w)).compile()
    report = {"coded_step": total_bytes(step)}

    parts = {"tokens": jax.ShapeDtypeStruct((K, b, S), jnp.int32),
             "labels": jax.ShapeDtypeStruct((K, b, S), jnp.int32),
             "weights": jax.ShapeDtypeStruct((K, b, S), jnp.float32)}
    s = dense_lm.sizes(config)
    grad = dense_lm._loss_and_grad_fn(tuple(sorted(s.items())), "f32") \
        .lower(shaped(params), shaped(parts)).compile()
    report["reference_grad"] = total_bytes(grad)
    return report


if __name__ == "__main__":
    sys.exit(main())
