"""Beyond-paper optimization clauses must be numerics-preserving (the
black-box-validation property, applied to each §Perf mechanism)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.launch.mesh import make_test_mesh
from repro.models import attention as A
from repro.models.context import ModelContext, SegmentClause
from repro.models.moe import moe_apply, moe_specs
from repro.models.params import init_params
from repro.runtime.sharding import Rules


@pytest.fixture(scope="module")
def mesh11():
    return make_test_mesh(1, 1)


def test_a2a_moe_matches_sorted(mesh11):
    cfg = get_arch("qwen3-moe-30b-a3b").smoke()
    p = init_params(moe_specs(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (2, 16, cfg.d_model),
                          jnp.float32) * 0.5
    rules = Rules({"batch": "data", "experts": "model"}, mesh11)
    y1, a1 = moe_apply(p, x, cfg, ModelContext(
        rules=rules, clause=SegmentClause(moe_dispatch="sorted")))
    y2, a2 = moe_apply(p, x, cfg, ModelContext(
        rules=rules, clause=SegmentClause(moe_dispatch="a2a")))
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=5e-3, rtol=5e-2)
    np.testing.assert_allclose(float(a1), float(a2), rtol=1e-5)


def test_a2a_moe_grads_flow(mesh11):
    cfg = get_arch("qwen3-moe-30b-a3b").smoke()
    p = init_params(moe_specs(cfg), jax.random.key(0))
    x = jax.random.normal(jax.random.key(1), (1, 8, cfg.d_model)) * 0.5
    rules = Rules({"batch": "data", "experts": "model"}, mesh11)
    ctx = ModelContext(rules=rules,
                       clause=SegmentClause(moe_dispatch="a2a"))
    g = jax.grad(lambda p: moe_apply(p, x, cfg, ctx)[0].sum())(p)
    for leaf in jax.tree.leaves(g):
        assert not np.any(np.isnan(np.asarray(leaf, np.float32)))


def test_shardmap_decode_matches_pjit(mesh11):
    cfg = get_arch("granite-8b").smoke()
    p = init_params(A.attn_specs(cfg), jax.random.key(0))
    rules = Rules({"batch": "data", "kv_seq": "model", "kv_heads": None},
                  mesh11)
    B, S = 2, 32
    # a one-layer stack of the cache's stored rows
    stack = (1, B, S, cfg.num_kv_heads * cfg.head_dim_)
    zero = {"k": jnp.zeros(stack), "v": jnp.zeros(stack)}
    x = jax.random.normal(jax.random.key(1), (B, cfg.d_model)) * 0.3
    ctx0 = ModelContext(rules=rules, clause=SegmentClause())
    ctx1 = ModelContext(rules=rules,
                        clause=SegmentClause(decode_shardmap=True))
    c0, c1 = dict(zero), dict(zero)
    for pos in range(6):
        y0, c0 = A.attn_decode(p, x, c0, jnp.int32(pos), cfg, ctx0, 0)
        y1, c1 = A.attn_decode(p, x, c1, jnp.int32(pos), cfg, ctx1, 0)
        np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                                   atol=2e-4, rtol=1e-3)
        np.testing.assert_allclose(np.asarray(c0["k"]),
                                   np.asarray(c1["k"]), atol=1e-5)


@pytest.mark.parametrize("pos", [0, 17, 63])
def test_bf16_cache_read_matches_upcast(pos):
    q = jax.random.normal(jax.random.key(2), (2, 4, 16), jnp.bfloat16)
    kc = jax.random.normal(jax.random.key(3), (2, 64, 2, 16), jnp.bfloat16)
    vc = jax.random.normal(jax.random.key(4), (2, 64, 2, 16), jnp.bfloat16)
    o1 = A.decode_attention(q, kc, vc, pos, upcast=True)
    o2 = A.decode_attention(q, kc, vc, pos, upcast=False)
    np.testing.assert_allclose(np.asarray(o1, np.float32),
                               np.asarray(o2, np.float32),
                               atol=2e-2, rtol=2e-2)


@pytest.mark.parametrize("upcast", [True, False])
@pytest.mark.parametrize("pos", [0, 40, 63, "rows"])
def test_decode_attention_whole_rows_match_per_head(pos, upcast):
    """Contracting the stored (B,S,KV*D) rows block-diagonally gives the
    per-KV-head contraction's output (GQA, G=2), for a scalar position
    and per-row ones."""
    B, S, KV, D = 3, 64, 2, 16
    q = jax.random.normal(jax.random.key(2), (B, 2 * KV, D), jnp.bfloat16)
    kc = jax.random.normal(jax.random.key(3), (B, S, KV, D), jnp.bfloat16)
    vc = jax.random.normal(jax.random.key(4), (B, S, KV, D), jnp.bfloat16)
    p = jnp.array([0, 17, 63]) if pos == "rows" else pos
    heads = A.decode_attention(q, kc, vc, p, upcast=upcast)
    rows = A.decode_attention(q, kc.reshape(B, S, KV * D),
                              vc.reshape(B, S, KV * D), p, upcast=upcast)
    np.testing.assert_allclose(np.asarray(rows, np.float32),
                               np.asarray(heads, np.float32),
                               atol=1e-2, rtol=1e-2)


def test_windowed_chunked_attention_no_full_copies():
    """window >= Sk must take the no-slice path and stay exact."""
    B, S, H, KV, D = 1, 128, 2, 1, 16
    q = jax.random.normal(jax.random.key(1), (B, S, H, D))
    k = jax.random.normal(jax.random.key(2), (B, S, KV, D))
    v = jax.random.normal(jax.random.key(3), (B, S, KV, D))
    pos = jnp.arange(S)
    a = A.naive_attention(q, k, v, pos_q=pos, pos_k=pos, window=S + 64)
    b = A.chunked_attention(q, k, v, pos_q=pos, pos_k=pos, window=S + 64,
                            q_chunk=32)
    np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
