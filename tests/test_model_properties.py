"""Property tests over the model config space (hypothesis)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.configs.base import ModelConfig
from repro.data.batches import synthetic_batch
from repro.models import transformer as tfm


@st.composite
def small_configs(draw):
    head_dim = draw(st.sampled_from([8, 16, 32]))
    n_kv = draw(st.integers(1, 4))
    g = draw(st.integers(1, 3))
    n_heads = n_kv * g
    d_model = draw(st.sampled_from([64, 96, 128]))
    pattern = draw(st.sampled_from([("attn",), ("local", "attn"),
                                    ("rec", "attn"), ("rwkv",)]))
    n_layers = draw(st.integers(1, 4))
    moe = draw(st.booleans()) and "rwkv" not in pattern
    rnn_heads = 2 if "rec" in pattern else 1
    return ModelConfig(
        name="prop", family="dense", n_layers=n_layers, d_model=d_model,
        n_heads=n_heads, n_kv_heads=n_kv, head_dim=head_dim,
        d_ff=draw(st.sampled_from([64, 128])), vocab=128,
        layer_pattern=pattern, window=16,
        n_experts=4 if moe else 0, top_k=2 if moe else 0,
        d_rnn=d_model, rnn_heads=rnn_heads,
        rwkv_head_dim=32 if d_model % 32 == 0 else 16, rwkv_chunk=8,
        qk_norm=draw(st.booleans()),
        gated_ffn=draw(st.booleans()),
        compute_dtype="float32",
    )


@settings(deadline=None, max_examples=8)
@given(cfg=small_configs(), seed=st.integers(0, 100))
def test_random_config_trains_finite(cfg, seed):
    params = tfm.init_params(cfg, jax.random.PRNGKey(seed))
    batch = synthetic_batch(cfg, 2, 32, "train", seed=seed)
    loss, grads = jax.value_and_grad(
        lambda p: tfm.loss_fn(p, batch, cfg))(params)
    assert np.isfinite(float(loss))
    for g in jax.tree.leaves(grads):
        assert np.isfinite(np.asarray(g)).all()


@settings(deadline=None, max_examples=6)
@given(cfg=small_configs())
def test_specs_axes_are_known(cfg):
    """Every logical axis in model_specs has a sharding rule."""
    from repro.launch.sharding import rules_for
    from jax.sharding import AbstractMesh
    mesh = AbstractMesh((4, 4), ("data", "model"))
    rules = rules_for(cfg, mesh)
    specs = tfm.model_specs(cfg)
    for s in jax.tree.leaves(specs,
                             is_leaf=lambda x: isinstance(x, tfm.Spec)):
        for ax in s.axes:
            assert ax is None or ax in rules, ax


def test_weighted_loss_linearity():
    """loss(w1 + w2) == loss(w1) + loss(w2) — the identity the coded
    gradient step relies on (encode/decode by loss weighting)."""
    cfg = ModelConfig(name="lin", family="dense", n_layers=2, d_model=64,
                      n_heads=2, n_kv_heads=2, head_dim=32, d_ff=128,
                      vocab=64, compute_dtype="float32")
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    batch = synthetic_batch(cfg, 4, 16, "train", seed=3)
    rng = np.random.default_rng(0)
    w1 = jnp.asarray(rng.random((4, 16)), jnp.float32)
    w2 = jnp.asarray(rng.random((4, 16)), jnp.float32)

    def loss_w(w):
        return tfm.loss_fn(params, dict(batch, weights=w), cfg)

    l12 = float(loss_w(w1 + w2))
    l1, l2 = float(loss_w(w1)), float(loss_w(w2))
    aux = float(loss_w(jnp.zeros_like(w1)))  # aux-loss constant offset
    np.testing.assert_allclose(l12 - aux, (l1 - aux) + (l2 - aux),
                               rtol=1e-5, atol=1e-5)
