"""Serving driver: batched prefill+decode with Lyapunov request admission.

The paper's transmission-phase scheduler (§4.3) applied to inference: each
client m has a request queue Q_m; per slot the drift-plus-penalty decisions
(P4/P5/P7) admit requests and allocate decode-batch slots, maximizing
Σ log(1+λ·throughput) — proportional fairness across clients — instead of
letting one hot client starve the rest.

  python -m repro.launch.serve --arch tiny --slots 40 --clients 6
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config
from repro.core.lyapunov import (Observation, SystemParams, init_queues,
                                 jain_index, schedule_slot)
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.train import TINY
from repro.models import transformer as tfm


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tiny")
    ap.add_argument("--clients", type=int, default=6)
    ap.add_argument("--slots", type=int, default=40)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen-len", type=int, default=8)
    ap.add_argument("--batch", type=int, default=4,
                    help="decode batch slots per scheduler slot")
    ap.add_argument("--V", type=float, default=30.0)
    args = ap.parse_args(argv)

    cfg = TINY if args.arch == "tiny" else get_config(args.arch,
                                                      reduced=True)
    enable_compile_cache()
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    Mc = args.clients
    rng = np.random.default_rng(0)

    sys_params = SystemParams(
        T=1.0, p=jnp.full((Mc,), 0.1), delta=jnp.full((Mc,), 1e-4),
        xi=jnp.full((Mc,), 0.01), f_max=jnp.full((Mc,), 100.0), F=500.0,
        E_cap=jnp.full((Mc,), 50.0), V=args.V, lam=jnp.ones((Mc,)))
    q_state = init_queues(Mc, E0=25.0)
    sched = jax.jit(lambda s, o: schedule_slot(s, sys_params, o))

    @jax.jit
    def prefill_and_decode(params, tokens):
        last, caches, pos = tfm.prefill(params, {"tokens": tokens}, cfg)
        caches = tfm.pad_cache(caches, cfg, extra=args.gen_len)
        outs = []
        tok = jnp.argmax(last, -1)[:, None]
        for i in range(args.gen_len):
            logits, caches = tfm.decode_step(params, tok, caches, pos + i,
                                             cfg)
            tok = jnp.argmax(logits, -1)[:, None]
            outs.append(tok)
        return jnp.concatenate(outs, axis=1)

    served = np.zeros(Mc)
    t0 = time.time()
    for slot in range(args.slots):
        # hot client 0 floods; others trickle (fairness stressor)
        arrivals = rng.poisson([6.0] + [1.0] * (Mc - 1)).astype(np.float32)
        obs = Observation(
            D=jnp.asarray(arrivals),
            r=jnp.full((Mc,), float(args.batch)),
            E_H=jnp.asarray(rng.uniform(1, 3, Mc), jnp.float32),
            L=jnp.asarray(1.0),
            new_cycles=jnp.zeros((Mc,)))
        q_state, dec = sched(q_state, obs)
        # transmitted data c_m = requests actually scheduled this slot
        n_serve = np.round(np.asarray(dec.c)).astype(int)
        total = int(n_serve.sum())
        if total > 0:
            n_run = min(total, args.batch)
            toks = jnp.asarray(
                rng.integers(0, cfg.vocab, (n_run, args.prompt_len)),
                jnp.int32)
            _ = prefill_and_decode(params, toks)
            served += n_serve * (n_run / max(total, 1))
        if slot % 10 == 0:
            print(f"slot {slot:3d} admitted={np.asarray(dec.d).sum():.1f} "
                  f"served={served.sum():.1f} "
                  f"jain={float(jain_index(jnp.asarray(served + 1e-9))):.3f} "
                  f"maxQ={float(q_state.Q.max()):.1f}")
    print(f"\nclients served: {np.round(served, 1)}")
    print(f"Jain fairness index: "
          f"{float(jain_index(jnp.asarray(served))):.3f} "
          f"({args.slots} slots, {time.time()-t0:.1f}s)")


if __name__ == "__main__":
    main()
