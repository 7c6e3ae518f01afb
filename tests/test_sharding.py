"""Sharding-rule unit tests on abstract production meshes (no devices)."""
import jax
import numpy as np
import pytest
from jax.sharding import AbstractMesh
from jax.sharding import PartitionSpec as P

from repro.configs.base import get_config, list_archs
from repro.launch.sharding import (_fit_spec_to_shape, batch_shardings,
                                   cache_shardings, param_shardings,
                                   rules_for)
from repro.models import transformer as tfm
from repro.models.common import Spec

MESH_1POD = AbstractMesh((16, 16), ("data", "model"))
MESH_2POD = AbstractMesh((2, 16, 16), ("pod", "data", "model"))


def _check_divisible(sharding, shape, mesh):
    spec = sharding.spec
    for dim, ax in zip(shape, tuple(spec) + (None,) * (len(shape)
                                                       - len(spec))):
        if ax is None:
            continue
        axs = ax if isinstance(ax, tuple) else (ax,)
        extent = int(np.prod([mesh.shape[a] for a in axs]))
        assert dim % extent == 0, (shape, spec)


@pytest.mark.parametrize("arch", list_archs())
@pytest.mark.parametrize("mesh", [MESH_1POD, MESH_2POD],
                         ids=["1pod", "2pod"])
def test_param_shardings_always_divide(arch, mesh):
    """Every param sharding divides its dim on both meshes (the invariant
    that broke odd-vocab archs before _fit_spec_to_shape)."""
    cfg = get_config(arch)
    specs = tfm.model_specs(cfg)
    shardings = param_shardings(cfg, mesh)
    flat_s = jax.tree.leaves(specs, is_leaf=lambda x: isinstance(x, Spec))
    flat_sh = jax.tree.leaves(shardings,
                              is_leaf=lambda x: hasattr(x, "spec"))
    assert len(flat_s) == len(flat_sh)
    for s, sh in zip(flat_s, flat_sh):
        _check_divisible(sh, s.shape, mesh)


@pytest.mark.parametrize("arch", list_archs())
def test_param_shardings_fsdp_layout(arch):
    cfg = get_config(arch)
    shardings = param_shardings(cfg, MESH_1POD, layout="fsdp")
    # fsdp keeps params 2-D sharded; nothing may use an axis twice
    for sh in jax.tree.leaves(shardings, is_leaf=lambda x: hasattr(x, "spec")):
        used = [a for part in sh.spec if part is not None
                for a in (part if isinstance(part, tuple) else (part,))]
        assert len(used) == len(set(used)), sh.spec


def test_fit_spec_drops_nondividing_axes():
    spec = _fit_spec_to_shape(P("model", "data"), (49155, 1536), MESH_1POD)
    assert spec == P(None, "data")
    spec2 = _fit_spec_to_shape(P(("data", "model"), None), (512, 8),
                               MESH_1POD)
    assert spec2 == P(("data", "model"), None)


@pytest.mark.parametrize("arch", ["deepseek-67b", "rwkv6-1.6b",
                                  "recurrentgemma-2b", "gemma3-12b"])
def test_cache_shardings_structure_matches_cache(arch):
    cfg = get_config(arch)
    B, cap = 128, 32768
    cache_shapes = jax.eval_shape(lambda: tfm.init_cache(cfg, B, cap))
    shardings = cache_shardings(cfg, MESH_1POD, B, cap)
    jax.tree.map(lambda s, sh: _check_divisible(sh, s.shape, MESH_1POD),
                 cache_shapes, shardings)


def test_long_context_cache_seq_sharded():
    cfg = get_config("gemma3-12b")
    B, cap = 1, 524288
    shardings = cache_shardings(cfg, MESH_1POD, B, cap)
    # global-attention layer k cache: (R, B, cap, KV, hd) — seq -> data
    k_spec = shardings[0]["l5"]["mix"]["k"].spec
    assert k_spec[2] == "data", k_spec
    # ring (local) caches stay unsharded in seq
    ring_spec = shardings[0]["l0"]["mix"]["k"].spec
    assert ring_spec[2] is None, ring_spec


def test_batch_shardings_multipod():
    cfg = get_config("qwen3-14b")
    from repro.data.batches import batch_shapes
    shapes = batch_shapes(cfg, 256, 4096, "train")
    sh = batch_shardings(cfg, MESH_2POD, shapes)
    assert sh["tokens"].spec[0] == ("pod", "data")


def test_rules_fsdp_batch_axes():
    cfg = get_config("deepseek-67b")
    assert rules_for(cfg, MESH_1POD, "fsdp")["batch"] == ("data", "model")
    assert rules_for(cfg, MESH_1POD, "tp")["batch"] == ("data",)
