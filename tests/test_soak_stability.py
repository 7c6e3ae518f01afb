"""Statistical regression bounds for the Lyapunov soak harness (§3.12).

The soak (``repro.sim.soak``) turns the paper's steady-state claims into
measurable numbers; this module pins them with *statistical* bounds
calibrated against reference runs (tolerances documented per test, see
DESIGN.md §3.12 for the methodology):

  * queue stability — time-averaged backlog bounded by the O(V) ceiling
    and the fitted drift slope ≈ 0 relative to the mean backlog;
  * fairness monotone in V — larger V weighs the concave utility more,
    so the Jain index of delivered bytes must not decrease along the
    V grid (common random numbers make the grid a paired comparison);
  * throughput inside the envelope — never above the hard ``max r·T·L``
    capacity bound, and the grid's best point within a whisker of the
    committed 1M-slot frontier baseline;

plus the mechanical contracts the statistics rest on: bitwise
chunk-invariance of the scan carry at {1k, 10k, 100k}-slot chunks (table
*and* Gilbert–Elliott lanes), the ``run_horizon`` cross-check (the soak's
in-carry f64 moments == a materialized ``schedule_slot`` trajectory
reduced in numpy f64), f32-vs-f64 dtype stability of 10k-slot averages,
and deterministic twins of the P4–P7 property suites
(``tests/test_scheduler_properties.py`` widens them under hypothesis;
these always run).

The soak horizon is ``SOAK_SLOTS`` (default 50 000 — the CI smoke tier;
nightly exports ``SOAK_SLOTS=1000000`` for the full soak).  The V grid
tops out at 128 because the statistical fixture must *converge* inside
the smoke horizon: V = 320 needs ~100k slots to reach steady state
(the frontier benchmark, which runs longer, sweeps it).
"""
from __future__ import annotations

import itertools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.lyapunov import schedule_slot
from repro.core.lyapunov.scheduler import (_LN2, _p4_auxiliary,
                                           _p5_admission, _p6_energy,
                                           _p7_knapsack)
from repro.sim import (PolicyCell, SoakLane, policy_grid, policy_search,
                       run_soak, scenario_spec, soak_compat_key,
                       soak_observations)
from repro.sim.soak import _lane_physics, initial_state, lane_theta

jax.config.update("jax_enable_x64", False)

#: Soak horizon: 50k is the CI smoke tier; nightly sets SOAK_SLOTS=1000000.
SOAK_SLOTS = int(os.environ.get("SOAK_SLOTS", 50_000))

#: Scenarios with distinct soak physics whose V grid converges at 50k.
STAT_SCENARIOS = ("homogeneous", "heterogeneous-rates",
                  "energy-harvesting-constrained")
#: Converges within the smoke horizon (V=320 would need ~100k slots).
STAT_V_GRID = (2.0, 8.0, 32.0, 128.0)

#: O(V) backlog ceiling (mean total backlog <= BASE + PER_V * V): the
#: measured steady-state Q/V tops out around 7.7 across the registry, so
#: 25/V leaves a 3x margin; an unstable policy grows without bound and
#: punches through any linear-in-V ceiling.
QTOT_BASE, QTOT_PER_V = 50.0, 25.0
#: Fitted-drift criterion: |slope|*n/(mean+1) — the backlog change the
#: fitted drift projects over the whole window, relative to the mean.
#: Converged lanes measure <= 0.15; 0.5 leaves 3x headroom.
DRIFT_RATIO_MAX = 0.5

BASELINE = os.path.join(os.path.dirname(__file__), os.pardir, "benchmarks",
                        "baselines", "BENCH_lyapunov_frontier.json")


@pytest.fixture(scope="module")
def stat_points():
    """The statistical grid, soaked once per module: 3 scenarios x 4 V
    points, one compiled scan for the whole (static-channel) grid."""
    cells = policy_grid([scenario_spec(s) for s in STAT_SCENARIOS],
                        V_grid=STAT_V_GRID)
    return policy_search(cells, SOAK_SLOTS)


def _by_scenario(points):
    out = {}
    for p in points:
        out.setdefault(p.cell.scenario.name, []).append(p)
    return out


# --------------------------------------------------------------------- #
# statistical bounds
# --------------------------------------------------------------------- #
def test_queue_stability_bounds(stat_points):
    """Time-averaged backlog bounded by the O(V) ceiling and the fitted
    drift slope ≈ 0 — the strong-stability signature."""
    for p in stat_points:
        ceiling = QTOT_BASE + QTOT_PER_V * p.cell.V
        assert p.mean_qtot <= ceiling, \
            f"{p.cell.scenario.name} V={p.cell.V}: mean backlog " \
            f"{p.mean_qtot:.1f} > O(V) ceiling {ceiling:.1f}"
        assert p.drift_ratio <= DRIFT_RATIO_MAX, \
            f"{p.cell.scenario.name} V={p.cell.V}: projected drift " \
            f"{p.drift_ratio:.3f} of mean backlog (limit {DRIFT_RATIO_MAX})"
        assert np.isfinite([p.mean_qtot, p.drift_slope, p.throughput,
                            p.jain, p.utility]).all()


def test_fairness_monotone_in_V(stat_points):
    """Jain fairness of delivered bytes must not decrease along the V
    grid (paired comparison: all V cells share one random tape).  The
    1e-3 slack absorbs f32 accumulation noise — the measured grid is
    monotone to ~1e-4."""
    for name, pts in _by_scenario(stat_points).items():
        pts = sorted(pts, key=lambda p: p.cell.V)
        for lo, hi in zip(pts, pts[1:]):
            assert hi.jain >= lo.jain - 1e-3, \
                f"{name}: jain fell {lo.jain:.4f} -> {hi.jain:.4f} " \
                f"raising V {lo.cell.V:g} -> {hi.cell.V:g}"


def test_backlog_and_utility_grow_with_V(stat_points):
    """The O(V) trade-off: the virtual-queue backlog H grows with V
    (strictly, ends well above where it starts) while the admitted
    log-utility does not decrease."""
    for name, pts in _by_scenario(stat_points).items():
        pts = sorted(pts, key=lambda p: p.cell.V)
        for lo, hi in zip(pts, pts[1:]):
            assert hi.mean_H >= lo.mean_H - 1e-6, \
                f"{name}: H fell raising V {lo.cell.V:g} -> {hi.cell.V:g}"
            assert hi.utility >= lo.utility - 1e-3, \
                f"{name}: utility fell raising V " \
                f"{lo.cell.V:g} -> {hi.cell.V:g}"
        assert pts[-1].mean_H > 2.0 * pts[0].mean_H, \
            f"{name}: backlog not O(V) — H {pts[0].mean_H:.2f} at " \
            f"V={pts[0].cell.V:g} vs {pts[-1].mean_H:.2f} at " \
            f"V={pts[-1].cell.V:g}"


def test_throughput_within_frontier_envelope(stat_points):
    """Never above the hard ``max r·T·L`` capacity bound; the grid's best
    point within 10% of the committed 1M-slot frontier baseline (the
    measured smoke-vs-full gap is < 0.1% — the soak is deterministic, so
    the 10% only has to absorb horizon truncation, not machine noise)."""
    with open(BASELINE) as f:
        base = json.load(f)["metrics"]
    for name, pts in _by_scenario(stat_points).items():
        for p in pts:
            assert 0.0 < p.throughput <= p.capacity * (1.0 + 1e-6), \
                f"{name} V={p.cell.V}: throughput {p.throughput:.3f} " \
                f"outside (0, {p.capacity:.3f}]"
        best = max(p.throughput for p in pts)
        ref = base[f"frontier.{name}.max_throughput"]
        assert best >= 0.9 * ref, \
            f"{name}: best throughput {best:.3f} < 90% of committed " \
            f"frontier baseline {ref:.3f}"


def test_homogeneous_is_exactly_fair(stat_points):
    """Symmetric workers + common random numbers ⇒ Jain ≈ 1 at every V."""
    for p in _by_scenario(stat_points)["homogeneous"]:
        assert p.jain > 0.999


# --------------------------------------------------------------------- #
# mechanical contracts under the statistics
# --------------------------------------------------------------------- #
def test_soak_chunk_invariance():
    """The carry is strictly sequential and the randomness counter-based,
    so the chunk split must not change a single bit — {1k, 10k, 100k}
    chunks on a 100k-slot horizon, table and Gilbert–Elliott groups."""
    n = 100_000
    groups = {
        "table": [SoakLane(scenario=scenario_spec("homogeneous")
                           .with_overrides(V=8.0)),
                  SoakLane(scenario=scenario_spec("flash-crowd")
                           .with_overrides(V=8.0))],
        "ge": [SoakLane(scenario=scenario_spec("fading-uplink")
                        .with_overrides(V=8.0))],
    }
    fields = ("mean_Q", "max_Q", "mean_H", "mean_E", "admitted",
              "delivered", "mean_y", "drift_slope", "throughput", "jain",
              "utility")
    for tag, lanes in groups.items():
        ref = run_soak(lanes, n, chunk=10_000)
        for chunk in (1_000, 100_000):
            alt = run_soak(lanes, n, chunk=chunk)
            for f in fields:
                assert np.array_equal(np.asarray(getattr(ref, f)),
                                      np.asarray(getattr(alt, f))), \
                    f"{tag}: {f} differs between 10k and {chunk} chunks"


def test_soak_non_divisor_chunk():
    """A chunk that does not divide the horizon pads the tail; the padded
    slots must be fully masked out of every moment."""
    lanes = [SoakLane(scenario=scenario_spec("heterogeneous-rates")
                      .with_overrides(V=8.0))]
    ref = run_soak(lanes, 20_000, chunk=10_000)
    alt = run_soak(lanes, 20_000, chunk=7_777)
    for f in ("mean_Q", "max_Q", "admitted", "delivered", "throughput",
              "jain"):
        assert np.array_equal(np.asarray(getattr(ref, f)),
                              np.asarray(getattr(alt, f))), f


def test_run_horizon_cross_check():
    """The soak's in-carry f64 moments must equal a materialized
    ``schedule_slot`` trajectory over ``soak_observations`` reduced in
    numpy f64 — same slots, same physics, two independent reductions.
    (1e-9 relative: numpy's pairwise sums vs the carry's sequential
    sums differ only in the last ulps.)"""
    lane = SoakLane(scenario=scenario_spec("heterogeneous-rates")
                    .with_overrides(V=8.0))
    n = 10_000
    res = run_soak([lane], n, warmup=0, chunk=1_000)
    obs = soak_observations(lane, n)
    phys = _lane_physics(lane)
    theta = lane_theta(lane)

    def body(s, o):
        s2, dec = schedule_slot(s, phys["sys"], o, theta=theta)
        return s2, (s2.Q, s2.H, s2.E, dec.d, dec.c, dec.y)

    _, (Q, H, E, d, c, y) = jax.lax.scan(body, initial_state(lane), obs)
    Q, H, E, d, c, y = (np.asarray(a, np.float64) for a in (Q, H, E, d, c, y))
    got = {
        "mean_Q": (Q.mean(axis=0), res.mean_Q[0]),
        "max_Q": (Q.max(axis=0), res.max_Q[0]),
        "mean_H": (H.mean(axis=0), res.mean_H[0]),
        "mean_E": (E.mean(axis=0), res.mean_E[0]),
        "admitted": (d.sum(axis=0), res.admitted[0]),
        "delivered": (c.sum(axis=0), res.delivered[0]),
        "mean_y": (y.mean(axis=0), res.mean_y[0]),
        "throughput": (c.sum() / n, res.throughput[0]),
    }
    for name, (ref, soak) in got.items():
        np.testing.assert_allclose(np.asarray(soak), np.asarray(ref),
                                   rtol=1e-9, err_msg=name)
    # drift slope == polyfit over the materialized total-backlog series
    qtot = Q.sum(axis=1)
    slope = np.polyfit(np.arange(n, dtype=np.float64), qtot, 1)[0]
    assert abs(slope - float(res.drift_slope[0])) <= \
        1e-6 * (abs(slope) + 1.0)


def test_run_horizon_f64_reference():
    """Dtype stability over 10k slots: rerunning the same horizon with
    every float leaf cast to f64 must reproduce the f32 run's *averages*
    — individual slots may diverge after a threshold flips on a ~1e-7
    margin, but the time averages re-converge (measured gap < 0.5%;
    bound 5%, throughput 1%)."""
    lane = SoakLane(scenario=scenario_spec("heterogeneous-rates")
                    .with_overrides(V=8.0))
    n = 10_000
    obs = soak_observations(lane, n)
    phys = _lane_physics(lane)
    theta = lane_theta(lane)

    def reduce_run(dtype, x64):
        def cast(t):
            return jax.tree_util.tree_map(
                lambda a: (jnp.asarray(a, dtype)
                           if jnp.issubdtype(jnp.asarray(a).dtype,
                                             jnp.floating) else a), t)

        def body(s, o):
            s2, dec = schedule_slot(s, cast(phys["sys"]), o,
                                    theta=jnp.asarray(theta, dtype))
            return s2, (s2.Q, dec.d, dec.c)

        def go():
            return jax.lax.scan(body, cast(initial_state(lane)), cast(obs))

        if x64:
            with jax.enable_x64(True):
                _, out = go()
                return [np.asarray(a, np.float64) for a in out]
        _, out = go()
        return [np.asarray(a, np.float64) for a in out]

    Q32, d32, c32 = reduce_run(jnp.float32, False)
    Q64, d64, c64 = reduce_run(jnp.float64, True)
    assert np.all(np.isfinite(Q32)) and np.all(np.isfinite(Q64))
    np.testing.assert_allclose(Q32.mean(axis=0), Q64.mean(axis=0),
                               rtol=5e-2)
    np.testing.assert_allclose(d32.sum(axis=0), d64.sum(axis=0), rtol=5e-2)
    np.testing.assert_allclose(c32.sum() / n, c64.sum() / n, rtol=1e-2)


def test_soak_grouping_one_compile_per_family():
    """A registry-wide grid partitions into one table group per worker
    count plus one Gilbert–Elliott group — the compile-sharing contract
    the policy layer rides."""
    from repro.sim.sweep import plan_groups
    cells = policy_grid([scenario_spec(s) for s in
                         ("homogeneous", "heterogeneous-rates",
                          "flash-crowd", "fading-uplink")],
                        V_grid=(5.0, 50.0))
    lanes = [c.lane for c in cells]
    groups = plan_groups(lanes, key=soak_compat_key)
    assert len(groups) == 2                      # (6, table) and (6, ge)
    assert sorted(map(len, groups)) == [2, 6]
    assert sorted(i for g in groups for i in g) == list(range(len(lanes)))


def test_policy_search_marks_pareto():
    """Pareto flags: at least one per scenario, and no marked point is
    dominated by another grid point of the same scenario."""
    cells = policy_grid([scenario_spec("heterogeneous-rates")],
                        V_grid=(2.0, 8.0, 32.0))
    pts = policy_search(cells, 5_000)
    assert any(p.pareto for p in pts)
    for p in pts:
        dominated = any(q.throughput >= p.throughput and q.jain >= p.jain
                        and (q.throughput > p.throughput or q.jain > p.jain)
                        for q in pts)
        assert p.pareto == (not dominated)


def test_soak_lane_validation():
    sc = scenario_spec("homogeneous")
    with pytest.raises(TypeError):
        SoakLane(scenario="homogeneous")
    with pytest.raises(ValueError):
        SoakLane(scenario=sc, theta_frac=1.5)
    with pytest.raises(ValueError):
        SoakLane(scenario=sc, load=0.0)
    with pytest.raises(ValueError):
        PolicyCell(scenario=sc, V=-1.0)
    with pytest.raises(ValueError):        # mixed families in one group
        run_soak([SoakLane(scenario=sc),
                  SoakLane(scenario=scenario_spec("fading-uplink"))], 100)


# --------------------------------------------------------------------- #
# P4–P7 deterministic property twins (hypothesis widens these in
# tests/test_scheduler_properties.py; these always run)
# --------------------------------------------------------------------- #
def _rng_cases(n, seed=0):
    rng = np.random.default_rng(seed)
    for _ in range(n):
        yield rng


def test_p4_closed_form_is_argmax_deterministic():
    """y* maximizes V·log2(1+y) − H·y over [0, D] against a dense grid,
    and the paper's gate holds: y* > 0 ⟺ V/ln2 > H (off the knife
    edge)."""
    rng = np.random.default_rng(0)
    for _ in range(200):
        H = float(rng.uniform(1e-3, 50.0))
        D = float(rng.uniform(0.0, 10.0))
        V = float(rng.uniform(0.1, 300.0))
        y = float(_p4_auxiliary(jnp.asarray(H), jnp.asarray(D), V))
        assert 0.0 <= y <= D + 1e-6
        grid = np.linspace(0.0, D, 2001)
        obj = V * np.log2(1.0 + grid) - H * grid
        assert V * math.log2(1.0 + y) - H * y >= obj.max() - 1e-4 * (
            1.0 + abs(obj.max()))
        if abs(V / _LN2 - H) > 1e-6 * (1.0 + H) and D > 1e-6:
            assert (y > 0.0) == (V / _LN2 > H)


def test_p4_monotone_in_V():
    """For fixed (H, D), the auxiliary target never shrinks as V grows."""
    rng = np.random.default_rng(1)
    for _ in range(100):
        H = float(rng.uniform(1e-3, 50.0))
        D = float(rng.uniform(0.1, 10.0))
        Vs = np.sort(rng.uniform(0.1, 300.0, size=8))
        ys = [float(_p4_auxiliary(jnp.asarray(H), jnp.asarray(D), float(V)))
              for V in Vs]
        assert all(b >= a - 1e-6 for a, b in zip(ys, ys[1:]))


def test_p5_p6_thresholds_deterministic():
    """P5 admits everything strictly below the H threshold and nothing
    at/above it (the endpoint minimizer of the linear (Q−H)·d); P6 banks
    the full harvest strictly below θ and none at/above."""
    rng = np.random.default_rng(2)
    for _ in range(200):
        Q, H, D, E, E_H, th = np.float32(rng.uniform(0.0, 20.0, size=6))
        d = float(_p5_admission(jnp.asarray(Q), jnp.asarray(H),
                                jnp.asarray(D)))
        assert d == (float(D) if Q < H else 0.0)
        assert (Q - H) * d <= min(0.0, float(Q - H) * float(D)) + 1e-6
        e = float(_p6_energy(jnp.asarray(E), jnp.asarray(E_H),
                             jnp.asarray(th)))
        assert e == (float(E_H) if E < th else 0.0)


def _p7_case(rng, M):
    from repro.core.lyapunov import SystemParams
    Q = rng.uniform(0.0, 10.0, M)
    E = rng.uniform(0.0, 10.0, M)
    r = rng.uniform(0.1, 8.0, M)
    theta = rng.uniform(0.0, 10.0, M)
    R_server = rng.uniform(0.0, 5.0)
    T = float(rng.uniform(0.1, 2.0))
    L = float(rng.uniform(0.5, 3.0))
    params = SystemParams(
        T=T, p=jnp.asarray(rng.uniform(0.1, 2.0, M), jnp.float32),
        delta=jnp.full((M,), 1e-3), xi=jnp.full((M,), 0.1),
        f_max=jnp.full((M,), 100.0), F=200.0,
        E_cap=jnp.full((M,), 50.0), V=50.0, lam=jnp.ones((M,)))
    return (jnp.asarray(Q, jnp.float32), jnp.asarray(E, jnp.float32),
            jnp.asarray(R_server, jnp.float32), jnp.asarray(r, jnp.float32),
            jnp.asarray(L, jnp.float32), params,
            jnp.asarray(theta, jnp.float32))


def _p7_brute_force(Q, E, R_server, r, L, params, theta):
    """Optimal continuous-knapsack objective by maximizing over every
    priority-order greedy fill: each extreme point of the feasible
    polytope is some order's prefix fill, so the max over all M!
    orders is the exact optimum (M ≤ 6 keeps that enumerable)."""
    Q, E, r, theta = (np.asarray(a, np.float64) for a in (Q, E, r, theta))
    p = np.asarray(params.p, np.float64)
    T, budget = float(params.T), float(params.T) * float(L)
    w = Q * r + (E - theta) * p - float(R_server) * \
        np.asarray(params.xi, np.float64) * r
    cap = np.minimum(np.minimum(T, Q / np.maximum(r, 1e-12)),
                     E / np.maximum(p, 1e-12))
    cap = np.where((w > 0.0) & (Q > 0.0), np.maximum(cap, 0.0), 0.0)
    best = 0.0
    for order in itertools.permutations(range(len(w))):
        left, obj = budget, 0.0
        for m in order:
            take = min(cap[m], left)
            obj += w[m] * take
            left -= take
        best = max(best, obj)
    return best, w, cap, budget


@pytest.mark.parametrize("M", [1, 2, 4, 6])
def test_p7_greedy_matches_brute_force(M):
    """The vectorized greedy is feasible and attains the brute-force
    optimum of the continuous knapsack at every M ≤ 6."""
    rng = np.random.default_rng(3 + M)
    for _ in range(40):
        case = _p7_case(rng, M)
        nu = np.asarray(_p7_knapsack(*case), np.float64)
        best, w, cap, budget = _p7_brute_force(*case)
        assert (nu >= -1e-6).all() and (nu <= cap + 1e-5).all()
        assert nu.sum() <= budget + 1e-5
        assert nu[(w <= 0.0) | (np.asarray(case[0]) <= 0.0)].max(
            initial=0.0) <= 1e-6
        got = float((w * nu).sum())
        assert got >= best - 1e-4 * (1.0 + abs(best)), \
            f"greedy {got:.6f} < brute-force optimum {best:.6f}"


def test_jain_one_definition():
    """The scheduler's ``jain_index`` is the telemetry definition — same
    value on random inputs, same all-zero/empty convention, same
    negative-share rejection."""
    from repro.core.lyapunov import jain_index as core_jain
    from repro.telemetry.metrics import jain_index as tele_jain
    rng = np.random.default_rng(4)
    for _ in range(100):
        x = rng.uniform(0.0, 10.0, size=rng.integers(1, 12))
        a, b = core_jain(jnp.asarray(x, jnp.float32)), tele_jain(
            np.asarray(x, np.float32))
        assert a == b
        assert 0.0 < a <= 1.0 + 1e-12
    assert core_jain(jnp.zeros(5)) == tele_jain(np.zeros(5)) == 1.0
    assert core_jain(jnp.zeros(0)) == tele_jain(np.zeros(0)) == 1.0
    assert core_jain(jnp.full((4,), 3.25)) == 1.0
    assert abs(core_jain(jnp.asarray([1.0, 0, 0, 0])) - 0.25) < 1e-12
    for bad in (core_jain, tele_jain):
        with pytest.raises(ValueError):
            bad(np.asarray([1.0, -0.5]))


def test_slope_from_moments_matches_polyfit():
    """The O(1)-memory moment form equals numpy's polyfit slope."""
    from repro.telemetry.metrics import slope_from_moments
    rng = np.random.default_rng(5)
    for n in (2, 7, 1000):
        t = np.arange(n, dtype=np.float64)
        q = rng.uniform(0.0, 50.0, n) + 0.37 * t
        got = slope_from_moments(n, t.sum(), (t * t).sum(), q.sum(),
                                 (t * q).sum())
        assert abs(got - np.polyfit(t, q, 1)[0]) < 1e-8
    assert slope_from_moments(1, 0.0, 0.0, 3.0, 0.0) == 0.0
    assert slope_from_moments(0, 0.0, 0.0, 0.0, 0.0) == 0.0
    # broadcasting over lane rows
    rows = slope_from_moments(np.asarray([2.0, 2.0]),
                              np.asarray([1.0, 1.0]),
                              np.asarray([1.0, 1.0]),
                              np.asarray([3.0, 4.0]),
                              np.asarray([2.0, 3.0]))
    np.testing.assert_allclose(rows, [1.0, 2.0])
