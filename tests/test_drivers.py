"""End-to-end driver tests: train (plain + coded + resume) at tiny scale."""
import numpy as np
import pytest

from repro.launch.train import main as train_main


def test_train_driver_plain(tmp_path, capsys):
    train_main(["--arch", "tiny", "--steps", "6", "--log-every", "2",
                "--ckpt-dir", str(tmp_path)])
    out = capsys.readouterr().out
    assert "done in" in out
    assert "loss=" in out


def test_train_driver_coded(capsys):
    train_main(["--arch", "tiny", "--steps", "4", "--coded",
                "--log-every", "2", "--workers", "4"])
    out = capsys.readouterr().out
    assert "done in" in out
    assert "util=" in out


def test_train_driver_resume(tmp_path, capsys):
    train_main(["--arch", "tiny", "--steps", "4", "--ckpt-dir",
                str(tmp_path), "--ckpt-every", "2", "--log-every", "2"])
    capsys.readouterr()
    train_main(["--arch", "tiny", "--steps", "6", "--ckpt-dir",
                str(tmp_path), "--log-every", "2"])
    out = capsys.readouterr().out
    assert "resumed from step" in out


@pytest.mark.parametrize("dtype,epoch", [("float32", 0), ("float32", 1),
                                         ("bfloat16", 0)])
def test_coded_gradient_is_partition_sum(dtype, epoch):
    """The coded step's weighted-loss gradient equals Σ_k ∇ℓ_k over the K
    partitions (stablelm-1.6b's reduced config): to f32 roundoff in f32
    (measured ≤ 8e-7), and in bf16 too where every decode weight is 0 or
    1 (epoch 0; measured 2e-6), since each slot then repeats the
    reference's ops — as long as the embedding backward accumulates
    repeated tokens in f32 (in bf16 it was 3.7e-3)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.core.coded_step import coded_value_and_grad
    from repro.data.pipeline import SyntheticLMDataset
    from repro.launch.train import coded_runtime, per_slot_lm_loss, slot_batch
    from repro.models import transformer as tfm

    cfg = dataclasses.replace(get_config("stablelm-1.6b", reduced=True),
                              compute_dtype=dtype)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    runtime = coded_runtime(6, n_slots=5)
    res = [runtime.run_epoch(e) for e in range(epoch + 1)][-1]
    assert res.decode_ok
    ds = SyntheticLMDataset(runtime.K, examples_per_partition=1, seq_len=64,
                            vocab=cfg.vocab)
    parts = [ds.partition(epoch, k) for k in range(runtime.K)]
    batch = {key: jnp.concatenate([p[key] for p in parts]) for key in parts[0]}
    with jax.default_matmul_precision("highest"):
        _, g = jax.jit(coded_value_and_grad(per_slot_lm_loss(cfg)))(
            params, slot_batch(ds, res.plan, epoch),
            jnp.asarray(res.weights, jnp.float32))
        g_ref = jax.grad(lambda q: tfm.loss_fn(q, batch, cfg))(params)
    num = sum(float(jnp.sum(jnp.square(a - b)))
              for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)))
    den = sum(float(jnp.sum(jnp.square(b))) for b in jax.tree.leaves(g_ref))
    assert np.sqrt(num / den) < 1e-4


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_packed_coded_gradient_is_partition_sum(dtype):
    """The same check on a plan whose weighted slots fit in one row of 15
    (the runtime pins 15 slots a worker; epoch 7: stage 1 alone, K = 12
    slots weighted 0 or 1): the step computes only that packed row, and
    its gradient equals Σ_k ∇ℓ_k over the whole batch of K partitions
    under the bound of the all-slots step above, in bf16 too (measured
    1.4e-6 in f32, 1.2e-6 in bf16)."""
    import dataclasses

    import jax
    import jax.numpy as jnp

    from repro.configs.base import get_config
    from repro.core.coded_step import coded_value_and_grad, computed_rows
    from repro.data.pipeline import SyntheticLMDataset
    from repro.launch.train import coded_runtime, per_slot_lm_loss, slot_batch
    from repro.models import transformer as tfm

    epoch = 7
    cfg = dataclasses.replace(get_config("stablelm-1.6b", reduced=True),
                              compute_dtype=dtype)
    params = tfm.init_params(cfg, jax.random.PRNGKey(0))
    runtime = coded_runtime(6, n_slots=15)
    res = [runtime.run_epoch(e) for e in range(epoch + 1)][-1]
    w = np.asarray(res.weights, np.float32)
    assert res.decode_ok and int(computed_rows(w, np)) == 1
    assert set(w[w != 0]) == {1.0}
    ds = SyntheticLMDataset(runtime.K, examples_per_partition=1, seq_len=64,
                            vocab=cfg.vocab)
    parts = [ds.partition(epoch, k) for k in range(runtime.K)]
    batch = {key: jnp.concatenate([p[key] for p in parts]) for key in parts[0]}
    with jax.default_matmul_precision("highest"):
        _, g = jax.jit(coded_value_and_grad(per_slot_lm_loss(cfg)))(
            params, slot_batch(ds, res.plan, epoch), jnp.asarray(w))
        g_ref = jax.grad(lambda q: tfm.loss_fn(q, batch, cfg))(params)
    num = sum(float(jnp.sum(jnp.square(a - b)))
              for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(g_ref)))
    den = sum(float(jnp.sum(jnp.square(b))) for b in jax.tree.leaves(g_ref))
    assert np.sqrt(num / den) < 1e-4
