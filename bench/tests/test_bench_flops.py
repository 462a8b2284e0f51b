"""Operation and byte arithmetic against the program's own shapes (CPU)."""
import json
import math
from pathlib import Path

import jax
import pytest

from bench import config as C
from bench import flops

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = sorted((ROOT / "bench" / "configs").glob("*.json"))


def config(path):
    return json.loads(path.read_text())


def model(path):
    return C.model(config(path))


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_param_count_matches_the_program(path):
    from repro.models.model import model_specs
    from repro.models.params import abstract_params
    from bench.run import arch_config
    cfg = arch_config(config(path))
    leaves = jax.tree.leaves(abstract_params(model_specs(cfg)))
    assert flops.param_count(model(path)) == sum(math.prod(x.shape)
                                                 for x in leaves)


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_reference_leaves_match_the_program(path):
    """The reference draws the same tree of shapes and dtypes."""
    from repro.models.model import model_specs
    from repro.models.params import abstract_params
    from bench.run import arch_config
    prog = abstract_params(model_specs(arch_config(config(path))))
    ref = C.reference(config(path))
    mine = jax.tree.map(lambda l: (l[0], l[3]), ref.param_leaves(model(path)),
                        is_leaf=ref._is_leaf)
    got = jax.tree.map(lambda s: (tuple(s.shape), str(s.dtype)), prog)
    assert jax.tree_util.tree_structure(mine, is_leaf=lambda x: isinstance(
        x, tuple)) == jax.tree_util.tree_structure(got, is_leaf=lambda x:
                                                   isinstance(x, tuple))
    flat_mine = jax.tree.leaves(mine, is_leaf=lambda x: isinstance(x, tuple))
    flat_got = jax.tree.leaves(got, is_leaf=lambda x: isinstance(x, tuple))
    assert flat_mine == flat_got


def test_weight_params_and_per_token_operations():
    m = model(ROOT / "bench" / "configs" / "stablelm-3b.json")
    d, f, V, L = 2560, 6912, 50304, 32
    per_layer = 4 * d * d + 3 * d * f
    assert flops.weight_params(m) == L * per_layer + d * V
    # training: 3 x (2 N + causal attention over (S+1)/2 keys per query)
    want = 3 * (2 * (L * per_layer + d * V) + L * 4 * d * (1024 + 1) / 2)
    assert flops.train_flops_per_token(m, 1024) == pytest.approx(want)
    # decode: one row attending to 100 positions
    want = 2 * (L * per_layer + d * V) + L * 4 * d * 100
    assert flops.decode_flops(m, [100]) == pytest.approx(want)


def test_depth_cut_keeps_the_widths():
    """The training stage is the serving model cut to 4 layers: the same
    work per layer, the same embedding and head."""
    full = model(ROOT / "bench" / "configs" / "stablelm-3b.json")
    cut = model(ROOT / "bench" / "configs" / "stablelm-3b-4l.json")
    head = full["d_model"] * full["vocab_size"]
    per = (flops.weight_params(full) - head) / 32
    assert flops.weight_params(cut) == pytest.approx(4 * per + head)
    assert {k: v for k, v in cut.items() if k != "num_layers"} == \
        {k: v for k, v in full.items() if k != "num_layers"}


def test_peaks_known_and_unknown_kinds():
    p = flops.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
    with pytest.raises(KeyError):
        flops.peaks("cpu")
