"""Rules resolution: divisibility fallbacks, used-axis tracking, provider
mappings — pure pspec logic (no multi-device mesh needed)."""
from dataclasses import dataclass

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax.sharding import PartitionSpec as P

from repro.configs import get_arch
from repro.core.providers import all_providers
from repro.core.segment import fragment
from repro.runtime.sharding import Rules


@dataclass
class FakeDevices:
    shape: tuple

    @property
    def size(self):
        n = 1
        for d in self.shape:
            n *= d
        return n


@dataclass
class FakeMesh:
    axis_names: tuple
    devices: FakeDevices


def mk_mesh(**axes):
    return FakeMesh(tuple(axes), FakeDevices(tuple(axes.values())))


MESH = mk_mesh(data=16, model=16)
MESH3 = mk_mesh(pod=2, data=16, model=16)


def test_divisible_dim_shards():
    r = Rules({"heads": "model", "embed": None}, MESH)
    assert r.pspec(("embed", "heads", None), (4096, 32, 128)) == \
        P(None, "model")


def test_indivisible_dim_falls_back():
    r = Rules({"kv_heads": ["model", None]}, MESH)
    assert r.pspec(("kv_heads",), (2,)) == P()


def test_used_axis_not_reused():
    r = Rules({"embed": "model", "ffn": "model"}, MESH)
    ps = r.pspec(("embed", "ffn"), (4096, 14336))
    assert ps == P("model")          # second dim blocked, trailing None cut


def test_multi_axis_candidate():
    r = Rules({"batch": [("pod", "data"), None]}, MESH3)
    assert r.pspec(("batch", None), (256, 128)) == P(("pod", "data"))
    # pod axis missing on the single-pod mesh -> resolves to data only
    r2 = Rules({"batch": [("pod", "data"), None]}, MESH)
    assert r2.pspec(("batch", None), (256, 128)) == P("data")


def test_fallback_chain():
    r = Rules({"batch": [("pod", "data", "model"), ("pod", "data"), None]},
              MESH3)
    # 128 % 512 != 0 -> falls to (pod,data)=32
    assert r.pspec(("batch",), (128,)) == P(("pod", "data"))
    # 512-divisible batch uses all three
    assert r.pspec(("batch",), (512,)) == P(("pod", "data", "model"))


@given(st.integers(1, 64), st.integers(1, 64))
@settings(max_examples=30, deadline=None)
def test_pspec_never_shards_indivisible(heads, dim2):
    r = Rules({"heads": "model", "ffn": "data"}, MESH)
    ps = r.pspec(("heads", "ffn"), (heads, dim2))
    parts = list(ps) + [None] * (2 - len(ps))
    if parts[0] == "model":
        assert heads % 16 == 0
    if parts[1] == "data":
        assert dim2 % 16 == 0


@pytest.mark.parametrize("provider", sorted(all_providers()))
@pytest.mark.parametrize("arch", ["granite-8b", "qwen3-moe-30b-a3b",
                                  "xlstm-125m", "recurrentgemma-2b"])
def test_provider_mappings_resolve_for_all_params(provider, arch):
    """Every provider mapping must produce a valid PartitionSpec for every
    parameter of every arch (divisibility-safe by construction)."""
    from repro.models.model import model_specs
    from repro.models.params import param_pspecs
    cfg = get_arch(arch)
    p = all_providers()[provider]
    for seg in fragment(cfg):
        if not p.applicable(cfg, seg):
            continue
        mapping = p.mapping(cfg, {"data": 16, "model": 16},
                            frozenset(p.flags), seg)
        r = Rules(mapping, MESH)
        tree = model_specs(cfg)
        sub = tree.get(seg.name)
        if sub is None:
            continue
        pspecs = param_pspecs(sub, r)
        # every resolved axis must divide the dim
        import jax
        from repro.models.params import is_spec
        for spec, ps in zip(
                jax.tree.leaves(sub, is_leaf=is_spec),
                jax.tree.leaves(pspecs,
                                is_leaf=lambda x: isinstance(x, P))):
            parts = list(ps) + [None] * (len(spec.shape) - len(ps))
            for dim, part in zip(spec.shape, parts):
                if part is None:
                    continue
                axes = (part,) if isinstance(part, str) else part
                size = int(np.prod([dict(data=16, model=16)[a]
                                    for a in axes]))
                assert dim % size == 0, (provider, arch, spec.shape, ps)
