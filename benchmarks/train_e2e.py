"""End-to-end coded-training benchmark: loss vs simulated wall-clock.

Runs a real jax model through the co-simulated uplink under all four
coding schemes (``repro.train.CodedTrainer``) on the paper's
``bursty-stragglers`` scenario and reports the Fig 5e/6e headline metric:
*time to target loss* per scheme, averaged over a small seed fleet (every
scheme replays the same seeds, so the comparison shares sampled straggler
and channel conditions).

Because every scheme recovers the exact full-batch gradient whenever its
decode succeeds, the parameter trajectory — and hence the loss at each
epoch — is identical across schemes; what differs is how much *simulated
wall-clock* each epoch burns (straggler waits, redundant compute, uplink
drain, wasted no-op epochs).  The target loss is the worst over schemes
of the best loss each achieved, so every scheme provably reached it, and
time-to-target isolates exactly the wall-clock claim.

Writes ``BENCH_train.json``; ``benchmarks.check_regression`` gates the
two-stage vs uncoded/cyclic speedups against an absolute floor
(``--train-floor``) and committed baselines.

    PYTHONPATH=src python -m benchmarks.train_e2e --smoke --out BENCH_train.json
"""
from __future__ import annotations

import argparse
import json
import math
import sys
import time

import jax
import numpy as np

from repro.configs.base import ModelConfig
from repro.data.pipeline import SyntheticLMDataset
from repro.models.transformer import init_params
from repro.optim.optimizers import adamw
from repro.sim.cluster import SCHEMES
from repro.sim.scenarios import scenario_spec
from repro.train import CodedTrainer, curve_dict, loss_curve, time_to_target

#: Tiny stablelm-shaped config for the CI smoke lane (2 layers, ~100k
#: params — the payload is still *measured* from the flattened gradient).
TINY = ModelConfig(
    name="train-e2e-tiny", family="dense",
    n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
    d_ff=128, vocab=128, remat="none", compute_dtype="float32")


def reduced_config() -> ModelConfig:
    """The stablelm-1.6b REDUCED config, f32 and unremat'd for CPU runs."""
    import dataclasses

    from repro.configs.stablelm_1_6b import REDUCED
    return dataclasses.replace(REDUCED, remat="none",
                               compute_dtype="float32")


def run_benchmark(cfg: ModelConfig, *, scenario: str = "bursty-stragglers",
                  n_seeds: int = 5, n_epochs: int = 2,
                  schemes=SCHEMES) -> dict:
    spec = scenario_spec(scenario)
    dataset = SyntheticLMDataset(K=spec.K, examples_per_partition=2,
                                 seq_len=32, vocab=cfg.vocab, seed=0)
    params0 = init_params(cfg, jax.random.PRNGKey(0))
    # one optimizer object: every trainer shares one compiled coded step
    optimizer = adamw(1e-2)

    t_host = time.perf_counter()
    runs: dict = {s: [] for s in schemes}
    trainers: dict = {}
    for scheme in schemes:
        for seed in range(n_seeds):
            tr = CodedTrainer(cfg, spec, scheme, dataset, optimizer,
                              params=params0, seed=seed)
            tr.run(n_epochs)
            runs[scheme].append(tr.logs)
            trainers[scheme] = tr
    wall = time.perf_counter() - t_host

    # worst-over-schemes best loss: a target every scheme reached
    bests = []
    for logs_list in runs.values():
        for logs in logs_list:
            finite = [v for _, v in zip(*loss_curve(logs))
                      if not math.isnan(v)]
            bests.append(min(finite) if finite else math.inf)
    target = max(bests)

    out = {
        "scenario": scenario,
        "model": cfg.name,
        "param_dim": trainers[schemes[0]].partition.D,
        "grad_bytes_units": trainers[schemes[0]].grad_bytes,
        "n_seeds": n_seeds,
        "n_epochs": n_epochs,
        "target_loss": float(target),
        "wall_seconds": wall,
        "schemes": {},
    }
    ttt = {}
    for scheme in schemes:
        per_seed = [time_to_target(logs, target) for logs in runs[scheme]]
        mean_ttt = (float(np.mean(per_seed))
                    if all(math.isfinite(t) for t in per_seed) else math.inf)
        ttt[scheme] = mean_ttt
        out["schemes"][scheme] = {
            "time_to_target": mean_ttt,
            "times_to_target": [t if math.isfinite(t) else None
                                for t in per_seed],
            "noop_epochs": sum(sum(1 for log in logs if not log.decode_ok)
                               for logs in runs[scheme]),
            "curves": [curve_dict(logs) for logs in runs[scheme]],
        }

    def speedup(base: str) -> float:
        ts = ttt.get("two-stage", math.inf)
        if not math.isfinite(ts) or ts <= 0:
            return 0.0
        return ttt.get(base, math.inf) / ts if math.isfinite(
            ttt.get(base, math.inf)) else math.inf
    if "two-stage" in schemes:
        if "uncoded" in schemes:
            out["speedup_vs_uncoded"] = speedup("uncoded")
        if "cyclic" in schemes:
            out["speedup_vs_cyclic"] = speedup("cyclic")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--smoke", action="store_true",
                    help="tiny 2-layer model (CI lane)")
    ap.add_argument("--seeds", type=int, default=None,
                    help="seed fleet size per scheme (default 5)")
    ap.add_argument("--epochs", type=int, default=None,
                    help="epochs per run (default: 2 smoke, 4 full)")
    ap.add_argument("--scenario", default="bursty-stragglers")
    ap.add_argument("--out", default=None, help="write JSON artifact here")
    args = ap.parse_args(argv)

    cfg = TINY if args.smoke else reduced_config()
    n_seeds = args.seeds if args.seeds is not None else 5
    n_epochs = args.epochs if args.epochs is not None else (
        2 if args.smoke else 4)
    result = run_benchmark(cfg, scenario=args.scenario, n_seeds=n_seeds,
                           n_epochs=n_epochs)

    print(f"train-e2e [{result['model']}] on {result['scenario']}: "
          f"D={result['param_dim']} "
          f"({result['grad_bytes_units']:.3f} payload units), "
          f"target loss {result['target_loss']:.4f}")
    for scheme, row in result["schemes"].items():
        print(f"  {scheme:<10s} time-to-target={row['time_to_target']:8.2f} "
              f"noop={row['noop_epochs']}")
    for key in ("speedup_vs_uncoded", "speedup_vs_cyclic"):
        if key in result:
            print(f"  two-stage {key.replace('_', ' ')}: "
                  f"{result[key]:.2f}x")
    if args.out:
        with open(args.out, "w") as f:
            json.dump(result, f, indent=2)
            f.write("\n")
        print(f"wrote {args.out}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
