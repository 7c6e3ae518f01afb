"""Steady-state Lyapunov soak harness (DESIGN.md §3.12).

Runs the P4–P7 drift-plus-penalty scheduler *alone* — no coded compute
phase, no epoch boundaries — for millions of slots per lane on the
batched comm scan, so the paper's steady-state claims (queue stability,
O(V) backlog, throughput–fairness trade-off) become measurable instead
of merely asserted over a handful of epochs.

Design (mirrors ``repro.sim.batched`` / ``repro.sim.device_epoch``):

  lanes
      A :class:`SoakLane` is a :class:`~repro.sim.spec.ScenarioSpec`
      plus the admission knobs the policy layer sweeps — the energy
      perturbation fraction ``theta_frac`` (θ = frac · E_cap, paper's
      P6/P7 perturbation) and the arrival-cap scale ``D_scale`` on top
      of a ``load`` factor.  Lane physics resolve through the same
      :func:`~repro.sim.spec.build_cluster` path the co-sim engines
      use, so a soaked scenario is *exactly* the scenario the fleets
      run: ``SystemParams`` (with the spec's ``V``), sub-channel budget,
      harvest physics and channel model all come from the cluster.

  open-loop offered load
      Arrivals are drawn per slot as ``D_m = D_scale · load ·
      r̄_m·T·L/M · U(0.5, 1.5)`` — mean offered load a ``load`` multiple
      of the lane's fair-share uplink capacity (``nominal_rates``), so
      with the default ``load = 1.2`` the admission control (P5) binds
      and stability is the scheduler's doing, not slack capacity's.

  chunked scan with a compact moments carry
      ``run_soak`` scans ``chunk`` slots per dispatch; the carry is the
      f32 :class:`~repro.core.lyapunov.queues.QueueState`, the (bool)
      Gilbert–Elliott channel state where the scenario needs one, and a
      float64 running-moments pytree — per-queue sums/maxima, admission
      and delivery totals, and the backlog-drift moments ``Σ qtot`` /
      ``Σ t·qtot`` (``t`` counted from the warmup boundary; ``Σt`` and
      ``Σt²`` are closed forms the host adds back).  Memory is O(S·M)
      regardless of horizon — no per-slot series is ever materialized.
      The f64 half lives under a scoped ``jax.enable_x64(True)``
      while the f32 slot physics is unchanged (inputs keep their dtypes,
      literals stay weak) — the ``device_epoch`` idiom.

  counter-based randomness
      Every slot's uniforms come from ``fold_in(key, k)`` on the
      *absolute* slot index, drawn once per slot and shared by all lanes
      (common random numbers: V-grid cells of one scenario see identical
      arrivals/harvest/fading, so frontier comparisons are paired).
      Draws depend only on ``k``, never on the chunk split — together
      with the strictly sequential carry this makes the soak bitwise
      chunk-invariant, which ``tests/test_soak_stability.py`` pins at
      {1k, 10k, 100k}-slot chunks.

Compile sharing: lanes group by :func:`soak_compat_key` — worker count
plus channel *family* (``"table"`` for static/trace, both run as a
padded per-lane rate table; ``"ge"`` for Gilbert–Elliott, whose state
rides the carry) — so a whole scenario × V × θ × D grid typically runs
as one or two compiled scans (see ``repro.sim.policy``).
"""
from __future__ import annotations

import dataclasses
import math
from functools import lru_cache
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.lyapunov import (Observation, QueueState,
                                 batched_schedule_slot_theta,
                                 stack_system_params)
from repro.sim.channel import (GilbertElliottChannel, StaticChannel,
                               TraceChannel)
from repro.sim.spec import ScenarioSpec, build_cluster
from repro.telemetry.metrics import jain_index, slope_from_moments

__all__ = ["SoakLane", "SoakResult", "soak_compat_key", "run_soak",
           "soak_observations", "DEFAULT_CHUNK"]

#: Default scan-chunk length (slots per device dispatch).  Larger than
#: the co-sim's TAPE_BLOCK because the soak draws its randomness
#: counter-based in-scan — there is no host tape to stay aligned with.
DEFAULT_CHUNK = 10_000


@dataclasses.dataclass(frozen=True)
class SoakLane:
    """One soak lane: a scenario plus the swept admission knobs.

    The Lyapunov ``V`` penalty is read from ``scenario.comm.V`` — sweep
    it with ``spec.with_overrides(V=...)`` (the policy layer does).
    ``theta_frac`` sets the P6/P7 energy perturbation θ = frac · E_cap;
    ``load`` and ``D_scale`` scale the offered arrival mean (see module
    docstring) — ``load`` is the scenario's operating point, ``D_scale``
    the knob the policy search perturbs around it.
    """
    scenario: ScenarioSpec
    theta_frac: float = 0.5
    D_scale: float = 1.0
    load: float = 1.2

    def __post_init__(self):
        if not isinstance(self.scenario, ScenarioSpec):
            raise TypeError(f"SoakLane.scenario wants a ScenarioSpec, got "
                            f"{type(self.scenario).__name__}")
        if not 0.0 <= self.theta_frac <= 1.0:
            raise ValueError(f"theta_frac must be in [0, 1], got "
                             f"{self.theta_frac}")
        if self.D_scale <= 0.0 or self.load <= 0.0:
            raise ValueError("D_scale and load must be positive")

    @property
    def V(self) -> float:
        return float(self.scenario.comm.V)


def soak_compat_key(lane: SoakLane) -> Tuple:
    """Structural signature: lanes with equal keys share one compiled
    soak scan.  Static and trace channels collapse into one ``"table"``
    family (a static channel is a 1-row table; tables pad to the group
    maximum and loop/hold per lane as data), so a registry-wide grid
    typically needs one table compile plus one per Gilbert–Elliott
    worker count."""
    ch = lane.scenario.channel
    kind = "ge" if ch.kind == "gilbert-elliott" else "table"
    return (lane.scenario.M, kind)


@dataclasses.dataclass(frozen=True)
class SoakResult:
    """Per-lane steady-state estimates (post-warmup unless noted).

    Arrays are numpy, lane-major: (S,) or (S, M).  ``throughput`` is
    delivered bytes per slot summed over workers; ``jain`` is the Jain
    index of cumulative per-worker delivered bytes (the running-estimate
    reduction of the moments carry); ``drift_ratio`` is the dimensionless
    stability criterion ``|slope| · n / (mean_qtot + 1)`` — the backlog
    change the fitted drift projects over the whole measured window,
    relative to the mean backlog (≈ 0 for a stable queue system).
    """
    lanes: Tuple[SoakLane, ...]
    n_slots: int
    warmup: int
    chunk: int
    mean_Q: np.ndarray          # (S, M) time-averaged data backlog
    max_Q: np.ndarray           # (S, M) peak data backlog
    mean_H: np.ndarray          # (S, M) time-averaged virtual queue
    mean_E: np.ndarray          # (S, M) time-averaged battery level
    admitted: np.ndarray        # (S, M) total bytes admitted
    delivered: np.ndarray       # (S, M) total bytes delivered
    mean_y: np.ndarray          # (S, M) time-averaged auxiliary rate
    drift_slope: np.ndarray     # (S,) backlog LS slope, bytes/slot
    drift_ratio: np.ndarray     # (S,) |slope|·n / (mean backlog + 1)
    throughput: np.ndarray      # (S,) delivered bytes/slot (all workers)
    jain: np.ndarray            # (S,) fairness of per-worker delivery
    utility: np.ndarray         # (S,) Σ_m log(1 + ȳ_m), the P4 objective

    @property
    def mean_qtot(self) -> np.ndarray:
        return self.mean_Q.sum(axis=1)


# --------------------------------------------------------------------- #
# lane physics -> stacked group arrays
# --------------------------------------------------------------------- #
def _lane_physics(lane: SoakLane) -> dict:
    """Host-side numpy physics of one lane, via the co-sim's own
    ``build_cluster`` resolver (so soak physics == fleet physics)."""
    spec = lane.scenario
    cl = build_cluster(spec, "uncoded", seed=0)
    ch, cp, M = cl.channel, cl.comm, spec.M
    r_nom = ch.nominal_rates()
    if r_nom is None:                       # custom model: flat fallback
        r_nom = np.ones(M)
    # steady-state arrival sizing: a non-looping trace holds its last
    # row forever, so the long-run service rate is that row — the trace
    # mean would size arrivals to a transient
    if isinstance(ch, TraceChannel) and not ch.loop:
        r_nom = ch.trace[-1]
    # hard throughput envelope: Σ_m ν_m·r_m ≤ (Σν)·max r ≤ T·L·max r —
    # the *peak* rate, not the mean: on a fading channel P7 transmits
    # opportunistically in good states and beats every mean-rate bound
    if isinstance(ch, GilbertElliottChannel):
        peak = max(float(ch.rate_good.max()), float(ch.rate_bad.max()))
    elif isinstance(ch, TraceChannel):
        peak = float(ch.trace.max())
    else:
        peak = float(np.max(r_nom))
    T, L = float(cp.slot_T), float(cp.n_subchannels)
    jit_h = float(cp.harvest_jitter)
    lo = max(1.0 - jit_h, 0.0)
    out = {
        "sys": cl.sys_params,
        "L": L,
        "E0": float(cp.E0),
        "theta": lane.theta_frac * float(cp.E_cap) * np.ones(M),
        "D_base": (lane.load * lane.D_scale * np.asarray(r_nom, np.float64)
                   * T * L / M),
        "h_lo": float(cp.harvest_mean) * lo * np.ones(M),
        "h_span": float(cp.harvest_mean) * ((1.0 + jit_h) - lo) * np.ones(M),
        "capacity": peak * T * L,          # bytes/slot hard envelope
        "offered": (lane.load * lane.D_scale
                    * float(np.sum(r_nom)) * T * L / M),
    }
    if isinstance(ch, GilbertElliottChannel):
        out.update(kind="ge", rate_good=ch.rate_good, rate_bad=ch.rate_bad,
                   p_gb=ch.p_gb, p_bg=ch.p_bg, start_good=ch._start_good)
    elif isinstance(ch, (StaticChannel, TraceChannel)):
        if isinstance(ch, StaticChannel):
            table, loop = ch.rates_for_slots(np.arange(1)), True
        else:
            table, loop = ch.trace, ch.loop
        out.update(kind="table", table=np.asarray(table, np.float64),
                   loop=loop)
    else:
        raise ValueError(f"soak supports static/trace/gilbert-elliott "
                         f"channels, got {type(ch).__name__}")
    return out


def _stack_group(lanes: Sequence[SoakLane]) -> dict:
    """Stack per-lane physics into the (S, …) arrays one compiled scan
    consumes.  All lanes must share :func:`soak_compat_key`."""
    phys = [_lane_physics(ln) for ln in lanes]
    kinds = {p["kind"] for p in phys}
    Ms = {ln.scenario.M for ln in lanes}
    if len(kinds) != 1 or len(Ms) != 1:
        raise ValueError(f"soak group mixes structures: kinds={kinds}, "
                         f"M={Ms}; group lanes by soak_compat_key first")
    kind, (M,) = kinds.pop(), Ms
    f32 = lambda rows: jnp.asarray(np.stack(rows), jnp.float32)  # noqa: E731
    g = {
        "kind": kind, "S": len(lanes), "M": M,
        "params": stack_system_params([p["sys"] for p in phys]),
        "L": f32([p["L"] for p in phys]),
        "theta": f32([p["theta"] for p in phys]),
        "D_base": f32([p["D_base"] for p in phys]),
        "h_lo": f32([p["h_lo"] for p in phys]),
        "h_span": f32([p["h_span"] for p in phys]),
        "E0": np.asarray([p["E0"] for p in phys], np.float64),
        "capacity": np.asarray([p["capacity"] for p in phys], np.float64),
    }
    if kind == "table":
        R = max(p["table"].shape[0] for p in phys)
        tables, n_rows = [], []
        for p in phys:
            t = p["table"]
            n_rows.append(t.shape[0])
            if t.shape[0] < R:              # pad: padding rows are never
                t = np.concatenate(        # indexed (idx < n_rows per lane)
                    [t, np.repeat(t[-1:], R - t.shape[0], axis=0)])
            tables.append(t)
        g["table"] = f32(tables)                              # (S, R, M)
        g["n_rows"] = jnp.asarray(n_rows, jnp.int32)          # (S,)
        g["loop"] = jnp.asarray([p["loop"] for p in phys], bool)
    else:
        g["rate_good"] = f32([p["rate_good"] for p in phys])
        g["rate_bad"] = f32([p["rate_bad"] for p in phys])
        g["p_gb"] = f32([[p["p_gb"]] for p in phys])          # (S, 1)
        g["p_bg"] = f32([[p["p_bg"]] for p in phys])
        g["good0"] = jnp.asarray(
            np.stack([np.full(M, p["start_good"], bool) for p in phys]))
    return g


# --------------------------------------------------------------------- #
# compiled chunk runner
# --------------------------------------------------------------------- #
def _slot_uniforms(key: jax.Array, k: jax.Array, M: int) -> jax.Array:
    """(3, M) f32 uniforms for absolute slot ``k`` — arrivals, harvest,
    channel — a pure function of (key, k), shared by every lane (common
    random numbers) and independent of the chunk split.  The dtype is
    explicit: under the scoped x64 the default would silently widen."""
    return jax.random.uniform(jax.random.fold_in(key, k), (3, M),
                              dtype=jnp.float32)


@lru_cache(maxsize=64)
def _soak_runner(kind: str, chunk_len: int):
    """Jitted ``chunk_len``-slot scan for one channel family.

    The cache key is the python-static part only; shapes (S, M, table
    rows) key jax's own jit cache, and tracing under the scoped x64
    keeps this entry distinct from any non-x64 trace of the same code.
    """
    def run(carry, g, k0, warmup, key):
        M = g["D_base"].shape[1]
        zeros = jnp.zeros_like(g["D_base"])

        def body(c, i):
            state, good, mom = c
            k = k0 + i
            u = _slot_uniforms(key, k, M)
            D = g["D_base"] * (0.5 + u[0])
            E_H = g["h_lo"] + g["h_span"] * u[1]
            if kind == "table":
                idx = jnp.where(g["loop"], k % g["n_rows"],
                                jnp.minimum(k, g["n_rows"] - 1))
                r = jnp.take_along_axis(
                    g["table"], idx[:, None, None].astype(jnp.int32),
                    axis=1)[:, 0, :]
            else:
                r = jnp.where(good, g["rate_good"], g["rate_bad"])
                good = jnp.where(good, u[2][None, :] >= g["p_gb"],
                                 u[2][None, :] < g["p_bg"])
            obs = Observation(D=D, r=r, E_H=E_H, L=g["L"],
                              new_cycles=zeros)
            state, dec = batched_schedule_slot_theta(
                state, g["params"], obs, g["theta"])

            # ---- f64 running moments (post-warmup slots only) ----
            w = (k >= warmup).astype(jnp.float64)
            t = jnp.maximum(k - warmup, 0).astype(jnp.float64)
            Q64 = state.Q.astype(jnp.float64)
            qtot = Q64.sum(-1)
            mom = {
                "s_q": mom["s_q"] + w * qtot,
                "s_tq": mom["s_tq"] + w * t * qtot,
                "sum_Q": mom["sum_Q"] + w * Q64,
                "max_Q": jnp.maximum(mom["max_Q"], w * Q64),
                "sum_H": mom["sum_H"] + w * state.H.astype(jnp.float64),
                "sum_E": mom["sum_E"] + w * state.E.astype(jnp.float64),
                "adm": mom["adm"] + w * dec.d.astype(jnp.float64),
                "dlv": mom["dlv"] + w * dec.c.astype(jnp.float64),
                "sum_y": mom["sum_y"] + w * dec.y.astype(jnp.float64),
            }
            return (state, good, mom), None

        carry, _ = jax.lax.scan(body, carry, jnp.arange(chunk_len))
        return carry

    return jax.jit(run)


def _init_carry(g: dict):
    S, M = g["S"], g["M"]
    z = jnp.zeros((S, M), jnp.float32)
    state = QueueState(
        Q=z, H=z, E=jnp.asarray(np.broadcast_to(g["E0"][:, None], (S, M)),
                                jnp.float32),
        R=z, R_server=jnp.zeros((S,), jnp.float32))
    good = g.get("good0")
    if good is None:                   # table family: placeholder leaf so
        good = jnp.zeros((), bool)     # both families share one carry shape
    zl = jnp.zeros((S,), jnp.float64)
    zm = jnp.zeros((S, M), jnp.float64)
    mom = {"s_q": zl, "s_tq": zl, "sum_Q": zm, "max_Q": zm, "sum_H": zm,
           "sum_E": zm, "adm": zm, "dlv": zm, "sum_y": zm}
    return state, good, mom


def run_soak(lanes: Sequence[SoakLane], n_slots: int, *,
             warmup: Optional[int] = None, chunk: int = DEFAULT_CHUNK,
             seed: int = 0) -> SoakResult:
    """Soak every lane for ``n_slots`` slots and reduce the moments.

    All lanes must share one :func:`soak_compat_key` (the policy layer
    groups arbitrary grids).  ``warmup`` (default ``n_slots // 5``)
    slots are simulated but excluded from every moment, so cold-start
    transients never pollute the drift fit.  Results are bitwise
    independent of ``chunk``.
    """
    lanes = tuple(lanes)
    if not lanes:
        raise ValueError("run_soak needs at least one lane")
    if len({soak_compat_key(ln) for ln in lanes}) != 1:
        raise ValueError("lanes span multiple soak groups; partition by "
                         "soak_compat_key (repro.sim.policy does)")
    if warmup is None:
        warmup = n_slots // 5
    if not 0 <= warmup < n_slots:
        raise ValueError(f"need 0 <= warmup < n_slots, got warmup="
                         f"{warmup}, n_slots={n_slots}")
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    g = _stack_group(lanes)
    key = jax.random.PRNGKey(seed)
    with jax.enable_x64(True):
        carry = _init_carry(g)
        w32, k0 = jnp.int32(warmup), 0
        consts = {k: v for k, v in g.items()
                  if k not in ("kind", "S", "M", "E0", "capacity")}
        for step in range(math.ceil(n_slots / chunk)):
            k0 = step * chunk
            n = min(chunk, n_slots - k0)
            runner = _soak_runner(g["kind"], n)
            carry = runner(carry, consts, jnp.int32(k0), w32, key)
        state, _, mom = jax.tree_util.tree_map(np.asarray, carry)

    n = float(n_slots - warmup)
    s_t = n * (n - 1.0) / 2.0                       # Σt, t = 0..n-1
    s_tt = (n - 1.0) * n * (2.0 * n - 1.0) / 6.0    # Σt²
    slope = slope_from_moments(n, s_t, s_tt, mom["s_q"], mom["s_tq"])
    slope = np.atleast_1d(slope)
    mean_qtot = mom["s_q"] / n
    delivered = mom["dlv"]
    return SoakResult(
        lanes=lanes, n_slots=int(n_slots), warmup=int(warmup),
        chunk=int(chunk),
        mean_Q=mom["sum_Q"] / n, max_Q=mom["max_Q"],
        mean_H=mom["sum_H"] / n, mean_E=mom["sum_E"] / n,
        admitted=mom["adm"], delivered=delivered,
        mean_y=mom["sum_y"] / n,
        drift_slope=slope,
        drift_ratio=np.abs(slope) * n / (mean_qtot + 1.0),
        throughput=delivered.sum(axis=1) / n,
        jain=np.asarray([jain_index(row) for row in delivered]),
        utility=np.log1p(mom["sum_y"] / n).sum(axis=1))


# --------------------------------------------------------------------- #
# observation materialization (test cross-checks)
# --------------------------------------------------------------------- #
def soak_observations(lane: SoakLane, n_slots: int, *,
                      seed: int = 0) -> Observation:
    """Materialize the exact per-slot observation sequence one soak lane
    sees, as ``(n_slots, …)`` arrays for ``run_horizon``.

    This is the bridge the long-horizon regression tests use: scanning
    ``run_horizon`` over these observations must reproduce the soak's
    f32 trajectory slot for slot (table channels only — a
    Gilbert–Elliott lane's rates depend on scheduler-independent carried
    state, which the chunk-invariance tests cover instead).
    """
    p = _lane_physics(lane)
    if p["kind"] != "table":
        raise ValueError("soak_observations supports table (static/trace) "
                         "channels only")
    M = lane.scenario.M
    key = jax.random.PRNGKey(seed)
    ks = jnp.arange(n_slots)
    u = jax.vmap(lambda k: _slot_uniforms(key, k, M))(ks)   # (n, 3, M)
    D_base = jnp.asarray(p["D_base"], jnp.float32)
    h_lo = jnp.asarray(p["h_lo"], jnp.float32)
    h_span = jnp.asarray(p["h_span"], jnp.float32)
    table = jnp.asarray(p["table"], jnp.float32)
    n_rows = table.shape[0]
    idx = (ks % n_rows if p["loop"]
           else jnp.minimum(ks, n_rows - 1))
    return Observation(
        D=D_base * (0.5 + u[:, 0]),
        r=table[idx],
        E_H=h_lo + h_span * u[:, 1],
        L=jnp.full((n_slots,), p["L"], jnp.float32),
        new_cycles=jnp.zeros((n_slots, M), jnp.float32))


def initial_state(lane: SoakLane) -> QueueState:
    """The (M,)-shaped initial :class:`QueueState` of one soak lane —
    zero queues, battery at the scenario's ``E0`` — for single-lane
    ``run_horizon`` cross-checks against the stacked scan."""
    from repro.core.lyapunov import init_queues
    return init_queues(lane.scenario.M, E0=_lane_physics(lane)["E0"])


def lane_theta(lane: SoakLane) -> jnp.ndarray:
    """The (M,) θ row of one lane (frac · E_cap), f32 — what the stacked
    scan passes to ``batched_schedule_slot_theta`` for this lane."""
    return jnp.asarray(_lane_physics(lane)["theta"], jnp.float32)


def lane_capacity(lanes: Sequence[SoakLane]) -> np.ndarray:
    """(S,) hard uplink throughput envelope, bytes/slot: ``max r·T·L``
    over every rate the channel can ever offer.  ``Σν_m·r_m ≤ (Σν)·max r
    ≤ T·L·max r`` per slot, so no schedule can beat it even
    opportunistically (a mean-rate bound would be violated on fading
    channels, where P7 concentrates airtime in good states); the
    frontier-envelope test bounds measured throughput by it."""
    return np.asarray([_lane_physics(ln)["capacity"] for ln in lanes])
