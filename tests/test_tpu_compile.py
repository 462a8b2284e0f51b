"""Compile guard: every Pallas kernel compiles for a described TPU v5e at
published widths and lowers to a Mosaic ``tpu_custom_call``.

Nothing runs; the TPU compiler installed with jaxlib compiles for a chip
that is described, not attached, so a kernel the chip's compiler would
refuse (tiling, scalar stores, missing lowerings, VMEM overflow) fails
here on a CPU host.  The topology is described inside a fixture, never at
import, so every xdist worker collects the same tests.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_decode import flash_decode_fwd
from repro.kernels.mlstm import mlstm_chunkwise_fwd
from repro.kernels.rglru import rglru_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

#: kernel -> (fn, argument (shape, dtype)s) at the widths the models run:
#: stablelm-3b attention (H=32, D=80) at S=4096 and its decode cache
#: (B=4, 512), recurrentgemma-2b RG-LRU (d_rnn=2560), xlstm-125m mLSTM
#: (H=4, dh=384) at S=2048, rmsnorm at d=2560
CASES = {
    "flash_attention": (
        lambda q, k, v: flash_attention_fwd(q, k, v, interpret=False),
        [((1, 32, 4096, 80), BF16)] * 3),
    "flash_decode": (
        lambda q, k, v, pos: flash_decode_fwd(q, k, v, pos, interpret=False,
                                              return_lse=True),
        [((4, 32, 80), BF16), ((4, 32, 512, 80), BF16),
         ((4, 32, 512, 80), BF16), ((), I32)]),
    "rglru": (
        lambda la, b: rglru_fwd(la, b, interpret=False),
        [((1, 2048, 2560), F32)] * 2),
    "mlstm": (
        lambda q, k, v, li, lf: mlstm_chunkwise_fwd(q, k, v, li, lf,
                                                    interpret=False),
        [((1, 4, 2048, 384), F32)] * 3 + [((1, 4, 2048), F32)] * 2),
    "rmsnorm": (
        lambda x, s: rmsnorm_fwd(x, s, interpret=False),
        [((4096, 2560), BF16), ((2560,), BF16)]),
}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but can never be read back without one: keep the cache off."""
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.mark.parametrize("kernel", sorted(CASES))
def test_kernel_compiles_for_v5e(kernel, one_chip, no_persistent_cache):
    fn, arg_specs = CASES[kernel]
    args = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
            for shape, dtype in arg_specs]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
