"""The three Pallas kernels compile for a TPU v5e at real model widths.

No chip is needed: the TPU compiler compiles for a topology that is
described, not attached, and refuses what the chip's compiler would refuse
(unsupported Mosaic lowerings, tiles, VMEM and HBM budgets) — which the
interpret-mode tests in ``test_kernels.py`` cannot see.  The topology is
described inside a module fixture, so only the worker that runs this file
loads the TPU library; the persistent compile cache is off around the
compiles, since a described device cannot read its entries back.
"""
import functools

import jax
import jax.numpy as jnp
import pytest

from repro.kernels.flash_attention.flash_attention import \
    flash_attention_pallas
from repro.kernels.rglru_scan.rglru_scan import rglru_scan_pallas
from repro.kernels.rwkv6_wkv.rwkv6_wkv import wkv_pallas

HBM_BYTES = 16 * 2 ** 30


#: kernel -> (function, argument shapes and dtypes at real widths)
CASES = {
    # stablelm-1.6b attention: 32 heads x 64, S=4096, bf16
    "flash_attention": (flash_attention_pallas,
                        [((1, 32, 4096, 64), jnp.bfloat16)] * 3),
    # recurrentgemma-2b: d_rnn=2560, S=2048
    "rglru_scan": (rglru_scan_pallas, [((1, 2048, 2560), jnp.float32)] * 2),
    # rwkv6-1.6b: 32 heads x 64, chunk 64, S=2048
    "rwkv6_wkv": (functools.partial(wkv_pallas, chunk=64),
                  [((1, 32, 2048, 64), jnp.float32)] * 4
                  + [((32, 64), jnp.float32)]),
}


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:             # no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        try:
            yield SingleDeviceSharding(topo.devices[0])
        finally:
            jax.config.update("jax_enable_compilation_cache", cache_on)
            compilation_cache.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, specs = CASES[name]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < HBM_BYTES, f"{name} needs {used / 2 ** 30:.2f} GiB"
