"""Hypothesis property suites for the P4–P7 closed forms (paper §4.3).

Widened, generator-driven versions of the deterministic twins in
``tests/test_soak_stability.py`` (which always run — this module skips
when hypothesis is not installed, following the
``test_tail_properties.py`` convention):

  * P4 — the closed form is the numeric argmax of V·log2(1+y) − H·y on
    [0, D]; the paper's activation gate y* > 0 ⟺ V/ln2 > H; monotone
    in V;
  * P5/P6 — exact threshold semantics, and the P5 endpoint is the true
    minimizer of the linear objective;
  * P7 — the vectorized greedy fill is feasible and attains the
    brute-force optimum over all M! priority orders at M ≤ 6;
  * Jain — the core alias and the telemetry definition agree everywhere,
    including the all-zero convention and scale invariance.
"""
from __future__ import annotations

import itertools
import math

import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.core.lyapunov import SystemParams
from repro.core.lyapunov import jain_index as core_jain
from repro.core.lyapunov.scheduler import (_LN2, _p4_auxiliary,
                                           _p5_admission, _p6_energy,
                                           _p7_knapsack)
from repro.telemetry.metrics import jain_index as tele_jain

finite = dict(allow_nan=False, allow_infinity=False)


def _f32(v) -> float:
    """``v`` as XLA sees an f32 input: rounded, and flushed to zero where
    it is subnormal (H = 1.4e-45 compares as 0)."""
    x = float(np.float32(v))
    return 0.0 if abs(x) < np.finfo(np.float32).tiny else x


@settings(max_examples=200, deadline=None)
@given(H=st.floats(1e-3, 50.0, **finite),
       D=st.floats(0.0, 10.0, **finite),
       V=st.floats(0.1, 300.0, **finite))
def test_p4_closed_form_is_argmax(H, D, V):
    y = float(_p4_auxiliary(jnp.asarray(H, jnp.float32),
                            jnp.asarray(D, jnp.float32), V))
    assert 0.0 <= y <= D + 1e-5
    grid = np.linspace(0.0, D, 2001)
    obj = V * np.log2(1.0 + grid) - H * grid
    assert V * math.log2(1.0 + y) - H * y >= \
        obj.max() - 1e-4 * (1.0 + abs(obj.max()))


@settings(max_examples=200, deadline=None)
@given(H=st.floats(1e-3, 50.0, **finite),
       D=st.floats(1e-3, 10.0, **finite),
       V=st.floats(0.1, 300.0, **finite))
def test_p4_activation_gate(H, D, V):
    """y* > 0 ⟺ V/ln2 > H, off the f32 knife edge."""
    if abs(V / _LN2 - H) <= 1e-5 * (1.0 + H):
        return
    y = float(_p4_auxiliary(jnp.asarray(H, jnp.float32),
                            jnp.asarray(D, jnp.float32), V))
    assert (y > 0.0) == (V / _LN2 > H)


@settings(max_examples=100, deadline=None)
@given(H=st.floats(1e-3, 50.0, **finite),
       D=st.floats(0.1, 10.0, **finite),
       V_lo=st.floats(0.1, 300.0, **finite),
       V_hi=st.floats(0.1, 300.0, **finite))
def test_p4_monotone_in_V(H, D, V_lo, V_hi):
    V_lo, V_hi = sorted((V_lo, V_hi))
    y_lo = float(_p4_auxiliary(jnp.asarray(H, jnp.float32),
                               jnp.asarray(D, jnp.float32), V_lo))
    y_hi = float(_p4_auxiliary(jnp.asarray(H, jnp.float32),
                               jnp.asarray(D, jnp.float32), V_hi))
    assert y_hi >= y_lo - 1e-6


@settings(max_examples=200, deadline=None)
@given(Q=st.floats(0.0, 20.0, **finite), H=st.floats(0.0, 20.0, **finite),
       D=st.floats(0.0, 20.0, **finite))
def test_p5_threshold_minimizes(Q, H, D):
    Q, H, D = (_f32(v) for v in (Q, H, D))
    d = float(_p5_admission(jnp.asarray(Q, jnp.float32),
                            jnp.asarray(H, jnp.float32),
                            jnp.asarray(D, jnp.float32)))
    assert d == (D if Q < H else 0.0)
    # endpoint minimizer of the linear objective (Q − H)·d on [0, D]
    assert (Q - H) * d <= min(0.0, (Q - H) * D) + 1e-6


@settings(max_examples=200, deadline=None)
@given(E=st.floats(0.0, 20.0, **finite), E_H=st.floats(0.0, 20.0, **finite),
       theta=st.floats(0.0, 20.0, **finite))
def test_p6_threshold(E, E_H, theta):
    E, E_H, theta = (_f32(v) for v in (E, E_H, theta))
    e = float(_p6_energy(jnp.asarray(E, jnp.float32),
                         jnp.asarray(E_H, jnp.float32),
                         jnp.asarray(theta, jnp.float32)))
    assert e == (E_H if E < theta else 0.0)


def _params(M, T):
    return SystemParams(
        T=T, p=jnp.full((M,), 0.7), delta=jnp.full((M,), 1e-3),
        xi=jnp.full((M,), 0.1), f_max=jnp.full((M,), 100.0), F=200.0,
        E_cap=jnp.full((M,), 50.0), V=50.0, lam=jnp.ones((M,)))


@settings(max_examples=60, deadline=None)
@given(data=st.data(), M=st.integers(1, 6))
def test_p7_greedy_matches_brute_force(data, M):
    """Greedy == exact optimum over all M! priority-order fills (every
    extreme point of the knapsack polytope is some order's prefix fill)."""
    vec = st.lists(st.floats(0.0, 10.0, **finite), min_size=M, max_size=M)
    Q = np.asarray(data.draw(vec), np.float64)
    E = np.asarray(data.draw(vec), np.float64)
    theta = np.asarray(data.draw(vec), np.float64)
    r = np.asarray(data.draw(st.lists(st.floats(0.1, 8.0, **finite),
                                      min_size=M, max_size=M)), np.float64)
    R_server = data.draw(st.floats(0.0, 5.0, **finite))
    T = data.draw(st.floats(0.1, 2.0, **finite))
    L = data.draw(st.floats(0.5, 3.0, **finite))
    params = _params(M, T)
    nu = np.asarray(
        _p7_knapsack(jnp.asarray(Q, jnp.float32), jnp.asarray(E, jnp.float32),
                     jnp.asarray(R_server, jnp.float32),
                     jnp.asarray(r, jnp.float32), jnp.asarray(L, jnp.float32),
                     params, jnp.asarray(theta, jnp.float32)), np.float64)
    p = np.asarray(params.p, np.float64)
    w = Q * r + (E - theta) * p - R_server * 0.1 * r
    cap = np.minimum(np.minimum(T, Q / np.maximum(r, 1e-12)),
                     E / np.maximum(p, 1e-12))
    cap = np.where((w > 0.0) & (Q > 0.0), np.maximum(cap, 0.0), 0.0)
    budget = T * L
    # feasibility
    assert (nu >= -1e-6).all() and (nu <= cap + 1e-4).all()
    assert nu.sum() <= budget + 1e-4
    assert nu[(w <= 0.0) | (Q <= 0.0)].max(initial=0.0) <= 1e-6
    # optimality vs the permutation brute force
    best = 0.0
    for order in itertools.permutations(range(M)):
        left, obj = budget, 0.0
        for m in order:
            take = min(cap[m], left)
            obj += w[m] * take
            left -= take
        best = max(best, obj)
    got = float((w * nu).sum())
    assert got >= best - 1e-3 * (1.0 + abs(best))


@settings(max_examples=200, deadline=None)
@given(x=st.lists(st.floats(0.0, 100.0, **finite), min_size=0, max_size=16),
       scale=st.floats(0.1, 50.0, **finite))
def test_jain_definitions_agree(x, scale):
    x32 = np.asarray(x, np.float32)
    a = core_jain(jnp.asarray(x32))
    b = tele_jain(x32)
    assert a == b
    assert 0.0 < a <= 1.0 + 1e-12
    # scale invariance (exact in f64 after the cast)
    assert abs(tele_jain(np.asarray(x32, np.float64) * scale) - b) <= 1e-9
    if len(x) and all(v == 0.0 for v in x):
        assert a == 1.0


@settings(max_examples=100, deadline=None)
@given(x=st.lists(st.floats(0.0, 100.0, **finite), min_size=1, max_size=16))
def test_jain_range_and_extremes(x):
    n = len(x)
    assert tele_jain(np.full(n, 7.5)) == 1.0
    one_hot = np.zeros(n)
    one_hot[0] = 3.0
    assert abs(tele_jain(one_hot) - 1.0 / n) <= 1e-12
    v = tele_jain(np.asarray(x))
    if any(val > 0 for val in x):
        assert 1.0 / n - 1e-12 <= v <= 1.0 + 1e-12
