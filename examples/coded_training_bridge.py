"""Coded training bridge walkthrough: a real model through the co-sim.

Trains a tiny transformer under every coding scheme with the full bridge
(DESIGN.md §3.10): the *measured* gradient payload drained through the
Lyapunov uplink, one coded step whose slot losses are weighted by the
epoch's decode weights (its gradient is the decoded full gradient), and
the paper's no-op step when decode fails — then prints the
loss-vs-simulated-wall-clock view and per-scheme time-to-target, the
paper's headline comparison.

    PYTHONPATH=src python examples/coded_training_bridge.py
    PYTHONPATH=src python examples/coded_training_bridge.py \
        --scenario flash-crowd --epochs 4 --trace /tmp/bridge-trace.json
"""
from __future__ import annotations

import argparse
import math


def main() -> None:
    import jax

    from repro.configs.base import ModelConfig
    from repro.data.pipeline import SyntheticLMDataset
    from repro.models.transformer import init_params
    from repro.optim.optimizers import adamw
    from repro.sim import available_scenarios, scenario_spec
    from repro.sim.cluster import SCHEMES
    from repro.telemetry import FleetRecorder, write_chrome_trace
    from repro.train import CodedTrainer, loss_curve, time_to_target

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--scenario", default="bursty-stragglers",
                    choices=available_scenarios())
    ap.add_argument("--epochs", type=int, default=3)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--trace", default=None,
                    help="write the two-stage run's phase spans here "
                         "(Chrome/Perfetto trace: bridge + engine phases)")
    args = ap.parse_args()

    cfg = ModelConfig(
        name="bridge-demo", family="dense",
        n_layers=2, d_model=64, n_heads=2, n_kv_heads=2, head_dim=32,
        d_ff=128, vocab=128, remat="none", compute_dtype="float32")
    spec = scenario_spec(args.scenario)
    dataset = SyntheticLMDataset(K=spec.K, examples_per_partition=2,
                                 seq_len=32, vocab=cfg.vocab, seed=0)
    params0 = init_params(cfg, jax.random.PRNGKey(0))
    optimizer = adamw(1e-2)      # one object: one shared compiled step

    print(f"== coded training bridge on {args.scenario} ==")
    trainers = {}
    for scheme in SCHEMES:
        rec = (FleetRecorder(scenario=args.scenario, scheme=scheme)
               if args.trace and scheme == "two-stage" else None)
        tr = CodedTrainer(cfg, spec, scheme, dataset, optimizer,
                          params=params0, seed=args.seed, telemetry=rec)
        tr.run(args.epochs)
        trainers[scheme] = tr
    first = trainers[SCHEMES[0]]
    print(f"model {cfg.name}: D={first.partition.D} flattened params, "
          f"measured payload {first.grad_bytes:.3f} units "
          f"(synthetic default was {spec.comm.grad_bytes:g})\n")

    # identical losses, different wall-clocks — the paper's core split
    print(f"{'scheme':<12s} {'wall-clock':>10s} {'final loss':>10s} "
          f"{'noop':>4s}  per-epoch times")
    bests = []
    for scheme, tr in trainers.items():
        times, losses = loss_curve(tr.logs)
        finite = [v for v in losses if not math.isnan(v)]
        bests.append(min(finite) if finite else math.inf)
        per_epoch = " ".join(f"{log.time:6.2f}" for log in tr.logs)
        final = f"{finite[-1]:10.4f}" if finite else " " * 10
        print(f"{scheme:<12s} {times[-1]:10.2f} {final} "
              f"{tr.noop_steps:>4d}  {per_epoch}")

    target = max(bests)
    print(f"\ntime to target loss {target:.4f} (worst-over-schemes best):")
    for scheme, tr in trainers.items():
        t = time_to_target(tr.logs, target)
        print(f"  {scheme:<12s} {t:8.2f}")

    if args.trace:
        path = write_chrome_trace(trainers["two-stage"].telemetry,
                                  args.trace)
        print(f"\nwrote {path} — bridge phases (slot_batch/coded_step) "
              f"alongside the engine's compute/comm/decode spans")


if __name__ == "__main__":
    main()
