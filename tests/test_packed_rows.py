"""The coded step computes one packed row of the nonzero-weight slots
when they fit in one, else the whole layout, and gets the same loss and
gradient as the all-slots step either way.

The reference kept here is the step before packing: ``Σ per_slot · w``
over the whole (M, n_slots) layout under one ``value_and_grad``.  The
packed row sums the same terms in another order over fewer slots, so the
two agree to f32 rounding (compute in f32), not bitwise.
"""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.coded_step import coded_value_and_grad, computed_rows
from repro.data.pipeline import SyntheticLMDataset
from repro.launch.train import (TINY, coded_runtime, coded_step_fn,
                                per_slot_lm_loss, slot_batch)
from repro.models import transformer as tfm
from repro.optim import adamw

WORKERS, SLOTS, BATCH, SEQ = 6, 15, 1, 16
CFG = dataclasses.replace(TINY, compute_dtype="float32")
RTOL = 2e-5           # f32: ~1e-7 per op, sums over a few hundred terms


@pytest.fixture(scope="module")
def plans():
    """The benchmark cell's runtime (6 workers, 15 slots), epochs 0–8."""
    rt = coded_runtime(WORKERS, straggler_prob=0.2, n_slots=SLOTS)
    return [rt.run_epoch(e) for e in range(9)]


@pytest.fixture(scope="module")
def setting():
    params = tfm.init_params(CFG, jax.random.PRNGKey(0))
    ds = SyntheticLMDataset(2 * WORKERS, examples_per_partition=BATCH,
                            seq_len=SEQ, vocab=CFG.vocab)
    return params, ds


def random_batch(seed: int) -> dict:
    """Every slot holds its own random tokens, with token weight 1."""
    rng = np.random.default_rng(seed)
    shape = (WORKERS, SLOTS, BATCH, SEQ)
    toks = rng.integers(0, CFG.vocab, shape, dtype=np.int32)
    return {"tokens": jnp.asarray(toks),
            "labels": jnp.asarray(np.roll(toks, -1, axis=-1)),
            "weights": jnp.ones(shape, jnp.float32)}


def forced_weights(nnz: int, seed: int) -> np.ndarray:
    """``nnz`` nonzero weights at random places of the layout."""
    rng = np.random.default_rng(seed)
    w = np.zeros(WORKERS * SLOTS, np.float32)
    w[rng.choice(w.size, nnz, replace=False)] = rng.uniform(0.2, 1.5, nnz)
    return w.reshape(WORKERS, SLOTS)


def case(name, plans, ds):
    if name in ("epoch7", "epoch8"):
        e = int(name[-1])
        return (slot_batch(ds, plans[e].plan, e),
                np.asarray(plans[e].weights, np.float32))
    if name == "zero":
        return random_batch(1), np.zeros((WORKERS, SLOTS), np.float32)
    nnz = {"full_row": SLOTS, "two_rows": SLOTS + 5,
           "all_rows": WORKERS * SLOTS}[name]
    return random_batch(2), forced_weights(nnz, 3)


@jax.jit
def all_slots(params, sb, w):
    """The reference: every slot's loss under one value_and_grad."""
    per_slot = per_slot_lm_loss(CFG)
    return jax.value_and_grad(
        lambda p: jnp.sum(per_slot(p, sb) * w))(params)


packed = jax.jit(coded_value_and_grad(per_slot_lm_loss(CFG)))


def assert_same(got, want):
    (l_got, g_got), (l_want, g_want) = got, want
    np.testing.assert_allclose(float(l_got), float(l_want), rtol=RTOL,
                               atol=1e-6)
    for a, b in zip(jax.tree.leaves(g_got), jax.tree.leaves(g_want)):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_allclose(a, b, rtol=RTOL,
                                   atol=RTOL * float(np.abs(b).max()))


def expected_rows(w) -> int:
    """One row when the nonzero weights fit in one, else all M."""
    return 1 if np.count_nonzero(w) <= w.shape[1] else w.shape[0]


CASES = ("epoch7", "epoch8", "full_row", "two_rows", "all_rows", "zero")


@pytest.mark.parametrize("name", CASES)
def test_packed_equals_all_slots(name, plans, setting):
    params, ds = setting
    sb, w = case(name, plans, ds)
    got = packed(params, sb, jnp.asarray(w))
    assert_same(got, all_slots(params, sb, jnp.asarray(w)))
    if name == "zero":
        assert float(got[0]) == 0.0
        assert all(not np.any(np.asarray(g)) for g in jax.tree.leaves(got[1]))


@pytest.mark.parametrize("name,rows", [("epoch7", 1), ("epoch8", 1),
                                       ("full_row", 1),
                                       ("two_rows", WORKERS),
                                       ("all_rows", WORKERS), ("zero", 1)])
def test_rows_follow_the_weights(name, rows, plans, setting):
    _, ds = setting
    _, w = case(name, plans, ds)
    assert expected_rows(w) == rows
    assert int(computed_rows(jnp.asarray(w))) == rows
    assert int(computed_rows(w, np)) == rows


def test_garbage_in_zero_weight_slots_changes_nothing(plans, setting):
    """Zero-weight slots holding random (finite) tokens: the packed result
    is the clean batch's, and the reference's."""
    params, ds = setting
    sb, w = case("epoch8", plans, ds)
    junk = random_batch(4)
    zero = jnp.asarray(w == 0)[..., None, None]
    dirty = {k: jnp.where(zero, junk[k], sb[k]) for k in sb}
    assert any(bool(jnp.any(dirty[k] != sb[k])) for k in sb)
    clean = packed(params, sb, jnp.asarray(w))
    got = packed(params, dirty, jnp.asarray(w))
    assert_same(got, clean)
    assert_same(got, all_slots(params, dirty, jnp.asarray(w)))


def test_one_compile_across_row_counts(plans, setting):
    """Plans computed as one row and as the whole layout run through one
    executable, and ``aux["rows"]`` reports the rows each computed."""
    _, ds = setting
    opt = adamw(lr=1e-3)
    params = tfm.init_params(CFG, jax.random.PRNGKey(1))
    opt_state = opt.init(params)
    step = coded_step_fn(CFG, opt)
    for name in ("epoch8", "full_row", "two_rows", "all_rows", "zero"):
        sb, w = case(name, plans, ds)
        params, opt_state, aux = step(params, opt_state, sb, jnp.asarray(w))
        assert int(aux["rows"]) == expected_rows(w), name
        assert math.isfinite(float(aux["loss"]))
    assert step._cache_size() == 1
