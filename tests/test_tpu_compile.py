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


#: the serving cell's decode step: stablelm-3b widths, 4 of its layers,
#: capacity 16, cache 384
STEP_LAYERS, STEP_CAPACITY, STEP_CACHE = 4, 16, 384


def _instructions(text):
    """(name, opcode, result type) of every instruction in HLO text."""
    import re
    pat = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = (\(.*?\)|\S+) "
                     r"([\w\-]+)\(")
    for line in text.splitlines():
        m = pat.match(line)
        if m:
            yield m.group(1), m.group(3), m.group(2)


def test_decode_step_updates_the_kv_cache_in_place(one_chip,
                                                   no_persistent_cache):
    """The engine's decode step, compiled for a v5e, neither copies nor
    selects over a whole KV stack or a whole layer's slice of it, and
    returns the stacks in the donated parameters' buffers."""
    import dataclasses
    import re

    from repro.configs import get_arch
    from repro.core.plan import default_plan
    from repro.models.model import cache_specs, model_specs
    from repro.models.params import abstract_params
    from repro.serve import ServeEngine, serving_shape

    cfg = dataclasses.replace(get_arch("stablelm-3b"), num_layers=STEP_LAYERS)
    on_chip = lambda t: jax.tree.map(  # noqa: E731
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        t)
    params = on_chip(abstract_params(model_specs(cfg)))
    engine = ServeEngine(cfg, default_plan(cfg, serving_shape(
        STEP_CAPACITY, STEP_CACHE)), capacity=STEP_CAPACITY,
        cache_len=STEP_CACHE, params=params)
    caches = on_chip(cache_specs(cfg, STEP_CAPACITY, STEP_CACHE))
    vec = jax.ShapeDtypeStruct((STEP_CAPACITY,), I32, sharding=one_chip)
    text = engine._step.lower(params, caches, vec, vec).compile().as_text()

    stack = jax.tree.leaves(caches)[0].shape
    assert stack[0] == STEP_LAYERS
    whole = {",".join(map(str, s)) for s in
             (stack, stack[1:], (1,) + stack[1:])}
    offenders = [(name, op, ty) for name, op, ty in _instructions(text)
                 if (op in ("copy", "copy-start")
                     or (op == "fusion" and "select" in name))
                 and set(re.findall(r"\[([\d,]+)\]", ty)) & whole]
    assert not offenders, offenders

    header = text.splitlines()[0]
    aliased = {int(p) for p in re.findall(r"\}: \((\d+), \{\}", header)}
    cache_params = {int(m.group(1)) for m in re.finditer(
        r"%caches\S* = \S+ parameter\((\d+)\)", text)}
    assert len(cache_params) == len(jax.tree.leaves(caches))
    assert cache_params <= aliased, (cache_params, header[:300])


def test_decode_step_keeps_the_cache_local_under_tensor_par(
        topo, no_persistent_cache):
    """On a 1 x 4 (data x model) mesh of the described v5e:2x2 with the
    serving plan (tensor_par: KV heads, so the cache rows, split over the
    model axis), the decode step still updates its stacks in place and
    nothing that carries the cache's sequence dim crosses devices: no
    gather of the cache and no sum of attention scores."""
    import dataclasses
    import re

    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from repro.configs import get_arch
    from repro.core.plan import default_plan
    from repro.models.model import cache_specs, model_specs
    from repro.models.params import abstract_params
    from repro.serve import make_decode_step, serving_shape
    from repro.serve.step import cache_shardings

    cfg = dataclasses.replace(get_arch("stablelm-3b"), num_layers=STEP_LAYERS)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))
    shape = serving_shape(STEP_CAPACITY, STEP_CACHE)
    plan = default_plan(cfg, shape)
    step, shardings = make_decode_step(cfg, mesh, plan)
    place = lambda specs, sh: jax.tree.map(  # noqa: E731
        lambda s, h: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=h),
        specs, sh)
    params = place(abstract_params(model_specs(cfg)), shardings["params"])
    cspecs = cache_specs(cfg, STEP_CAPACITY, STEP_CACHE)
    caches = place(cspecs, cache_shardings(cfg, shape, mesh, plan))
    assert jax.tree.leaves(caches)[0].sharding.spec[-1] == "model"
    vec = jax.ShapeDtypeStruct((STEP_CAPACITY,), I32,
                               sharding=NamedSharding(mesh, PartitionSpec()))
    text = jax.jit(step, donate_argnums=(1,)).lower(
        params, caches, vec, vec).compile().as_text()

    collectives = [(name, ty) for name, op, ty in _instructions(text)
                   if re.match(r"(all-gather|all-reduce|reduce-scatter"
                               r"|all-to-all|collective-permute)", op)]
    assert collectives          # the projections' sums are there
    seq = [(name, ty) for name, ty in collectives
           if str(STEP_CACHE) in re.findall(r"\d+", ty.split("{")[0])]
    assert not seq, seq

    header = text.splitlines()[0]
    aliased = {int(p) for p in re.findall(r"\}: \((\d+), \{\}", header)}
    first = len(jax.tree.leaves(params))       # arguments in tree order
    cache_params = set(range(first, first + len(jax.tree.leaves(caches))))
    assert cache_params <= aliased, (cache_params, header[:300])
