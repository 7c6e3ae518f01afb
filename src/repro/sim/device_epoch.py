"""Device-resident epoch tail: the stop state machine inside the scan.

The host-tail engine (``repro.sim.batched``) replays every chunk's stacked
outputs through the numpy :class:`~repro.sim.batched._StopTracker` — a
per-chunk device→host round-trip of ``(chunk, S, M)`` arrays that caps
fleet size at what host Python can chew.  This module folds that whole
state machine — float64 byte ledgers, arrival masks, decode gates
(:class:`~repro.sim.cluster.GateSpec` stacked per lane), the
provably-stuck rule, per-lane slot caps, energy extrema and stop-slot
snapshots — into the ``lax.scan`` carry, so the host sees one small
per-epoch result instead of per-chunk series (DESIGN.md §3.11).

Bit-identity contract (``tests/test_device_epoch.py``): the carry update
mirrors ``_StopTracker.consume`` operation for operation —

  * byte ledgers and energy extrema accumulate in float64 in the same
    per-slot order, under a scoped ``jax.enable_x64(True)`` (the f32 slot
    physics is untouched: its inputs stay f32 and every scalar literal is
    weakly typed);
  * the axis sums feeding the idle/stuck predicates replicate numpy's
    pairwise summation bitwise (:func:`_pairwise_last`), including the
    tracker's deliberate float32 fold over ``Q``;
  * decode gates are evaluated per slot from the stacked
    :class:`~repro.sim.cluster.GateSpec` predicates — equal to the host
    tracker's memoized exact gate because the gate is a pure function of
    the (monotone-per-lane) arrival mask;
  * the stop priority is the oracle's: decodable > provably-stuck > slot
    cap, latched per lane with its snapshots.

What stays on the host, by design: the per-epoch f64 control plane
(stage-2 planning, predictor EWMA, RS decode — already single stacked
passes per epoch) and randomness-tape drawing.  The chunk loop fetches
one ``(S,)`` stop mask per chunk so stopped seeds stop drawing tape
blocks — the RNG-stream-parity contract — which is the only per-chunk
host traffic left.

``mesh`` shards the seed axis across devices with ``shard_map`` over a
1-D ``("seeds",)`` mesh (:func:`repro.launch.mesh.fleet_mesh`): every
in-scan op is elementwise or per-lane, so lanes shard with no
collectives and sharded results are bit-identical to unsharded ones.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache
from typing import List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec

from repro.core.lyapunov import Observation, QueueState, batched_schedule_slot
from repro.sim.batched import (_chunk_xs, _draw_chunk_tapes, _StackedPhysics,
                               _visible_slots, stack_fleet_physics)
from repro.sim.channel import TAPE_BLOCK, CommTape
from repro.sim.cluster import (ARRIVAL_ATOL, ARRIVAL_RTOL, CommJob, CommStats,
                               EdgeCluster, stuck_tolerance)
from repro.telemetry.compilation import note_compile

__all__ = ["device_comm", "SEED_AXIS"]

#: Mesh axis name the fleet's seed dimension shards over.
SEED_AXIS = "seeds"


# --------------------------------------------------------------------- #
# numpy-bitwise pairwise summation
# --------------------------------------------------------------------- #
def _pairwise_last(x: jax.Array) -> jax.Array:
    """Sum over the last axis replicating numpy's pairwise algorithm
    bitwise (same dtype, same association order): sequential fold under 8
    elements, eight-accumulator blocks up to 128, recursive halving (cut
    rounded down to a multiple of 8) above.  The host stop tracker's
    idle/stuck predicates are numpy ``.sum(axis=1)`` calls; matching
    their rounding exactly is what makes the device tail bit-identical
    rather than merely close.
    """
    n = x.shape[-1]
    if n == 0:
        return jnp.zeros(x.shape[:-1], x.dtype)
    if n < 8:
        acc = x[..., 0]
        for i in range(1, n):
            acc = acc + x[..., i]
        return acc
    if n <= 128:
        r = [x[..., i] for i in range(8)]
        i = 8
        while i + 8 <= n:
            for j in range(8):
                r[j] = r[j] + x[..., i + j]
            i += 8
        acc = (((r[0] + r[1]) + (r[2] + r[3]))
               + ((r[4] + r[5]) + (r[6] + r[7])))
        while i < n:
            acc = acc + x[..., i]
            i += 1
        return acc
    n2 = (n // 2) // 8 * 8
    return _pairwise_last(x[..., :n2]) + _pairwise_last(x[..., n2:])


# --------------------------------------------------------------------- #
# stacked decode gates
# --------------------------------------------------------------------- #
@dataclasses.dataclass(frozen=True)
class _StackedGates:
    """Per-lane :class:`~repro.sim.cluster.GateSpec` predicates stacked
    into mask/count arrays the scan evaluates each slot:

        decodable ⟺ has_work ∧ (arrived ∨ ¬must).all()
                             ∧ count(arrived ∧ cnt) ≥ need
                             ∧ every valid FRS group has an arrival
    """
    must: np.ndarray        # (S, M) bool — workers that must all arrive
    cnt: np.ndarray         # (S, M) bool — workers the count applies to
    need: np.ndarray        # (S,)  int32 — arrivals needed among ``cnt``
    has_work: np.ndarray    # (S,)  bool
    member: np.ndarray      # (S, G, M) bool — FRS group membership
    gvalid: np.ndarray      # (S, G) bool — padded groups gate nothing
    G: int                  # group-axis length (0 ⟺ no group gates)


def _stack_gates(jobs: Sequence[CommJob], M: int) -> _StackedGates:
    gates = [j.gate for j in jobs]
    missing = [i for i, g in enumerate(gates) if g is None]
    if missing:
        raise ValueError(
            f"device tail needs CommJob.gate on every lane; lanes "
            f"{missing} have none (legacy job construction?)")
    S = len(gates)
    G = max((int(g.groups.max()) + 1 for g in gates
             if g.groups is not None), default=0)
    must = np.zeros((S, M), bool)
    cnt = np.zeros((S, M), bool)
    need = np.zeros(S, np.int32)
    has_work = np.zeros(S, bool)
    member = np.zeros((S, G, M), bool)
    gvalid = np.zeros((S, G), bool)
    for i, g in enumerate(gates):
        must[i, np.asarray(g.must, int)] = True
        cnt[i, np.asarray(g.count_over, int)] = True
        need[i] = g.need
        has_work[i] = g.has_work
        if G and g.groups is not None:
            member[i, np.asarray(g.groups, int), np.arange(M)] = True
            gvalid[i] = member[i].any(-1)
    return _StackedGates(must, cnt, need, has_work, member, gvalid, G)


# --------------------------------------------------------------------- #
# compiled device tail
# --------------------------------------------------------------------- #
@lru_cache(maxsize=64)
def _tail_runner(channel_step, S: int, M: int, G: int, mesh):
    """Jitted chunk scan carrying the full stop state machine.

    Cache key matches :func:`~repro.sim.batched._chunk_runner`'s
    structural signature plus the gate group count and the (hashable)
    mesh, so every fleet of one structure shares a compilation.  Traced
    under x64 so the float64 ledger arithmetic exists on device; the f32
    physics half is unchanged because its inputs keep their dtypes and
    all literals are weak Python scalars.
    """
    stateful = channel_step is not None

    def run(carry, xs, consts, gconsts):
        note_compile("device_comm_scan")     # executes only while tracing
        sysp, gb, L, visible, chp = consts
        (gb64, lastv, tiny, cap, must, cnt_m, need, has_work,
         member, gvalid) = gconsts

        def body(c, x):
            state, pending, ch_state, t = c
            k = x["k"]
            # ---- f32 slot physics, verbatim from the host-tail scan ----
            pending = pending + gb * (visible == k)
            if stateful:
                r, ch_state = channel_step(chp, ch_state, x["ch"], k)
                r = jnp.broadcast_to(r, pending.shape).astype(jnp.float32)
            else:
                r = jnp.broadcast_to(x["r"], pending.shape)
            obs = Observation(D=pending, r=r, E_H=x["h"], L=L,
                              new_cycles=jnp.zeros_like(pending))
            state, dec = batched_schedule_slot(state, sysp, obs)
            pending = pending - jnp.minimum(pending, dec.d)

            # ---- f64 stop state machine (= _StopTracker.consume) ----
            act = ~t["stopped"]
            actc = act[:, None]
            d64 = dec.d.astype(jnp.float64)
            c64 = dec.c.astype(jnp.float64)
            E64 = state.E.astype(jnp.float64)
            admitted = jnp.where(actc, t["admitted"] + d64, t["admitted"])
            delivered = jnp.where(actc, t["delivered"] + c64,
                                  t["delivered"])
            idle_now = ((_pairwise_last(d64) <= 0)
                        & (_pairwise_last(c64) <= 0))
            idle = t["idle"] + (act & idle_now).astype(jnp.int32)
            min_E = jnp.where(act, jnp.minimum(t["min_E"], E64.min(-1)),
                              t["min_E"])
            # float64 spend vs slot-start energy, as the oracle computes it
            od = (dec.e_up.astype(jnp.float64)
                  + dec.e_com.astype(jnp.float64) - t["E_prev"]).max(-1)
            max_od = jnp.where(act, jnp.maximum(t["max_od"], od),
                               t["max_od"])
            owed = gb64 * (visible <= k)
            arr_now = (owed > 0) & (delivered >= owed - ARRIVAL_RTOL * owed
                                    - ARRIVAL_ATOL)
            arrived = jnp.where(actc, arr_now, t["arrived"])
            # decode gate: pure function of the arrival mask, so per-slot
            # re-evaluation equals the host tracker's memoized gate
            count = (arrived & cnt_m).sum(-1)
            decod = (has_work & (arrived | ~must).all(-1)
                     & (count >= need))
            if G:
                grp_ok = (member & arrived[:, None, :]).any(-1)
                decod = decod & (grp_ok | ~gvalid).all(-1)
            # the tracker's deliberate dtype split: pending folds in f64,
            # Q in f32 (both then compare against the f64 tolerance)
            p_left = _pairwise_last(pending.astype(jnp.float64))
            q_left = _pairwise_last(state.Q)
            stuck = (k >= lastv) & (p_left <= tiny) & (q_left <= tiny)
            # oracle order per slot: decodable, then provably-stuck, then
            # the slot cap (the latter two never set decode_ok)
            stop = act & (decod | stuck | (k + 1 >= cap))
            stopc = stop[:, None]
            tail = {
                "stopped": t["stopped"] | stop,
                "ok": jnp.where(stop, decod, t["ok"]),
                "n_slots": jnp.where(stop, k + 1, t["n_slots"]),
                "admitted": admitted, "delivered": delivered,
                "idle": idle, "min_E": min_E, "max_od": max_od,
                "E_prev": E64, "arrived": arrived,
                "snap_Q": jnp.where(stopc, state.Q.astype(jnp.float64),
                                    t["snap_Q"]),
                "snap_E": jnp.where(stopc, E64, t["snap_E"]),
                "snap_pend": jnp.where(stopc,
                                       pending.astype(jnp.float64),
                                       t["snap_pend"]),
                "snap_owed": jnp.where(stopc, owed, t["snap_owed"]),
            }
            return (state, pending, ch_state, tail), None

        carry, _ = jax.lax.scan(body, carry, xs)
        return carry

    if mesh is None:
        return jax.jit(run)
    # seed-axis shard_map: per-lane data shards, the shared slot index
    # stays replicated; no in-scan op crosses lanes, so no collectives
    lanes = PartitionSpec(SEED_AXIS)
    xs_spec = {"k": PartitionSpec(),
               "h": PartitionSpec(None, SEED_AXIS)}
    xs_spec["ch" if stateful else "r"] = PartitionSpec(None, SEED_AXIS)
    sharded = jax.shard_map(run, mesh=mesh,
                            in_specs=(lanes, xs_spec, lanes, lanes),
                            out_specs=lanes, check_vma=False)
    return jax.jit(sharded)


# --------------------------------------------------------------------- #
# device-resident comm phase
# --------------------------------------------------------------------- #
def device_comm(clusters: Sequence[EdgeCluster],
                jobs: Sequence[CommJob],
                chunk: Optional[int] = None, *,
                physics: Optional[_StackedPhysics] = None,
                mesh=None) -> List[CommStats]:
    """Run one epoch's comm phase with the stop tracker in the scan carry.

    Drop-in replacement for ``repro.sim.batched._batched_comm`` (minus
    per-slot telemetry series, which need the chunk outputs this path
    deliberately never materializes).  ``mesh`` is a 1-D
    :class:`jax.sharding.Mesh` with a ``"seeds"`` axis (or ``"auto"`` for
    one over every visible device); the fleet size must divide evenly.
    """
    c0 = clusters[0]
    chunk = int(chunk or TAPE_BLOCK)
    S, M = len(clusters), c0.M
    if physics is None:
        physics = stack_fleet_physics(clusters)
    grid_len = physics.grid_len
    stateful = c0.channel.stateful

    if mesh == "auto":
        from repro.launch.mesh import fleet_mesh
        mesh = fleet_mesh()
    if mesh is not None:
        if SEED_AXIS not in mesh.axis_names:
            raise ValueError(f"fleet mesh needs a {SEED_AXIS!r} axis, got "
                             f"{mesh.axis_names}")
        n_shards = mesh.shape[SEED_AXIS]
        if S % n_shards != 0:
            raise ValueError(
                f"fleet size {S} does not divide over {n_shards} "
                f"{SEED_AXIS!r} shards; pad the seed list or drop the mesh")

    visible = _visible_slots(jobs, physics)
    tapes = [CommTape(c.channel, c.engine.rng, c.comm.harvest_mean,
                      c.comm.harvest_jitter) for c in clusters]
    gates = _stack_gates(jobs, M)
    runner = _tail_runner(
        type(c0.channel).step_batched if stateful else None,
        S, M, gates.G, mesh)
    consts = (physics.sysp, physics.gb, physics.L,
              jnp.asarray(visible, jnp.int32), physics.chp)

    # host-side rows the stop rules need, exactly as _StopTracker builds
    # them: last COMPUTE_DONE slot, per-lane stuck tolerance, f64 payloads
    ready = np.stack([j.ready_time for j in jobs])
    fin = np.isfinite(ready)
    last_visible = np.where(
        fin.any(1), np.max(np.where(fin, visible, -1), axis=1), -1)
    tiny = np.array([stuck_tolerance(c.grad_bytes) for c in clusters])
    gb64 = np.stack([c.grad_bytes for c in clusters])
    E0 = np.array([float(c.comm.E0) for c in clusters])

    z = jnp.zeros((S, M), jnp.float32)
    state = QueueState(Q=z, H=z, E=physics.E_init,
                       R=z, R_server=jnp.zeros((S,), jnp.float32))
    if stateful:
        ch_state = jnp.asarray(np.stack(
            [c.channel.init_state_np(t.u_init)
             for c, t in zip(clusters, tapes)]))
    else:
        ch_state = ()

    zero_rows = np.zeros((chunk, M))
    stopped = np.zeros(S, bool)
    n_chunks = -(-grid_len // chunk)
    # the f64 carry/constants only exist under x64; the jit cache is keyed
    # on the flag, so the traced program is stable across re-entries
    with jax.enable_x64(True):
        gconsts = (jnp.asarray(gb64, jnp.float64),
                   jnp.asarray(last_visible, jnp.int32),
                   jnp.asarray(tiny, jnp.float64),
                   jnp.asarray(physics.cap, jnp.int32),
                   jnp.asarray(gates.must), jnp.asarray(gates.cnt),
                   jnp.asarray(gates.need, jnp.int32),
                   jnp.asarray(gates.has_work),
                   jnp.asarray(gates.member), jnp.asarray(gates.gvalid))
        tail = {
            "stopped": jnp.zeros(S, bool),
            "ok": jnp.zeros(S, bool),
            "n_slots": jnp.zeros(S, jnp.int32),
            "admitted": jnp.zeros((S, M), jnp.float64),
            "delivered": jnp.zeros((S, M), jnp.float64),
            "idle": jnp.zeros(S, jnp.int32),
            "min_E": jnp.asarray(E0, jnp.float64),
            "max_od": jnp.zeros(S, jnp.float64),
            "E_prev": jnp.asarray(np.broadcast_to(E0[:, None], (S, M)),
                                  jnp.float64),
            "arrived": jnp.zeros((S, M), bool),
            "snap_Q": jnp.zeros((S, M), jnp.float64),
            "snap_E": jnp.zeros((S, M), jnp.float64),
            "snap_pend": jnp.zeros((S, M), jnp.float64),
            "snap_owed": jnp.zeros((S, M), jnp.float64),
        }
        carry = (state, z, ch_state, tail)
        for b in range(n_chunks):
            if stopped.all():
                break
            k0 = b * chunk
            # tape drawing stays host-owned: a stopped seed stops drawing
            # blocks, keeping its RNG stream aligned with the oracle's —
            # the one (S,)-sized fetch per chunk this path still makes
            _draw_chunk_tapes(tapes, stopped, k0, chunk)
            xs = _chunk_xs(clusters, tapes, k0, chunk, stateful, zero_rows)
            carry = runner(carry, xs, consts, gconsts)
            stopped = np.asarray(carry[3]["stopped"])

    t = {key: np.asarray(v) for key, v in carry[3].items()}
    assert t["stopped"].all(), "device comm scan ended with unstopped seeds"
    stats = []
    for i, job in enumerate(jobs):
        n = int(t["n_slots"][i])
        ok = bool(t["ok"][i])
        arrived = t["arrived"][i].copy()
        # guard the one corner where the count/mask gate can diverge from
        # the exact one (ill-conditioned LS decode): re-check on the final
        # mask — monotone arrivals make this sufficient — and refuse to
        # return silently different results
        if ok != bool(job.is_decodable(arrived)):
            raise RuntimeError(
                f"device decode gate diverged from the exact gate on lane "
                f"{i} (gate={ok}, exact={not ok}); this scheme needs the "
                f"host tail")
        stats.append(CommStats(
            n_slots=n,
            decode_time=float(n * physics.slot_T[i]),
            decode_ok=ok,
            arrived=arrived,
            bytes_offered=t["snap_owed"][i].copy(),
            bytes_admitted=t["admitted"][i].copy(),
            bytes_transmitted=t["delivered"][i].copy(),
            queue_residual=t["snap_Q"][i].copy(),
            pending_residual=t["snap_pend"][i].copy(),
            min_energy=float(t["min_E"][i]),
            max_overdraft=float(t["max_od"][i]),
            final_energy=t["snap_E"][i].copy(),
            idle_slots=int(t["idle"][i]),
        ))
    return stats
