"""Per-architecture smoke tests (reduced config, CPU): one forward +
one train step + one decode step, asserting shapes and no NaNs — plus
model-level equivalence properties (chunked==naive attention, decode
consistency with teacher forcing)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_arch, get_shape
from repro.core.plan import uniform_plan
from repro.models import (ModelContext, SegmentClause, forward, init_cache,
                          init_params, model_specs, decode_step)
from repro.models.attention import chunked_attention, naive_attention
from repro.train.step import init_train_state, jit_train_step

ALL_ARCHS = sorted(ARCHS)


def make_batch(cfg, B=2, S=32, seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    batch = {"targets": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size)}
    if cfg.frontend != "none":
        batch["embeds"] = (jax.random.normal(ks[1], (B, S, cfg.d_model))
                           * 0.02).astype(cfg.dtype)
    else:
        batch["tokens"] = jax.random.randint(ks[2], (B, S), 0,
                                             cfg.vocab_size)
    return batch


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_smoke_forward(arch):
    cfg = get_arch(arch).smoke()
    params = init_params(model_specs(cfg), jax.random.key(0))
    batch = make_batch(cfg)
    logits, aux = forward(params, batch, cfg, ModelContext())
    assert logits.shape == (2, 32, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits, np.float32)))
    if cfg.is_moe:
        assert float(aux) > 0.0    # load-balance loss active


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_smoke_train_step(arch):
    cfg = get_arch(arch).smoke()
    plan = uniform_plan(cfg, "fsdp", clause=SegmentClause(remat="dots"))
    step, _ = jit_train_step(cfg, None, plan)
    params, opt = init_train_state(cfg, plan, jax.random.key(0))
    batch = make_batch(cfg, B=2, S=16)
    params, opt, metrics = step(params, opt, batch)
    assert np.isfinite(float(metrics["total_loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))


@pytest.mark.parametrize("arch", ALL_ARCHS)
def test_arch_smoke_decode_step(arch):
    cfg = get_arch(arch).smoke()
    params = init_params(model_specs(cfg), jax.random.key(0))
    B, S = 2, 32
    caches = init_cache(cfg, B, S)
    tok = jnp.zeros((B,), jnp.int32)
    logits, caches = decode_step(params, caches, tok, jnp.int32(0), cfg,
                                 ModelContext())
    assert logits.shape == (B, cfg.vocab_size)
    assert not np.any(np.isnan(np.asarray(logits, np.float32)))


# --- decode == teacher-forced forward (the cache-correctness property) ------

@pytest.mark.parametrize("arch", [
    "granite-8b",            # GQA full attention
    "starcoder2-3b",         # sliding window (ring buffer)
    "recurrentgemma-2b",     # RG-LRU + local attention hybrid
    "xlstm-125m",            # mLSTM + sLSTM recurrent
    "chatglm3-6b",           # 2d RoPE
])
def test_decode_matches_forward(arch):
    cfg = get_arch(arch).smoke()
    params = init_params(model_specs(cfg), jax.random.key(0))
    B, S = 2, 16
    tokens = jax.random.randint(jax.random.key(1), (B, S), 0,
                                cfg.vocab_size)
    ctx = ModelContext()
    full_logits, _ = forward(params, {"tokens": tokens}, cfg, ctx)
    caches = init_cache(cfg, B, S)
    step = jax.jit(lambda p, c, t, i: decode_step(p, c, t, i, cfg, ctx))
    errs = []
    for t in range(S):
        logits, caches = step(params, caches, tokens[:, t], jnp.int32(t))
        errs.append(float(jnp.max(jnp.abs(
            logits - full_logits[:, t]))))
    assert max(errs) < 2e-2, f"decode diverges from forward: {errs}"


def test_chunked_equals_naive_attention():
    B, S, H, KV, D = 2, 128, 4, 2, 16
    q = jax.random.normal(jax.random.key(1), (B, S, H, D))
    k = jax.random.normal(jax.random.key(2), (B, S, KV, D))
    v = jax.random.normal(jax.random.key(3), (B, S, KV, D))
    pos = jnp.arange(S)
    for window in (0, 32):
        a = naive_attention(q, k, v, pos_q=pos, pos_k=pos, window=window)
        b = chunked_attention(q, k, v, pos_q=pos, pos_k=pos, window=window,
                              q_chunk=32)
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_pallas_plan_matches_xla_plan():
    """Black-box equivalence of the kernel clause (what the validator
    guarantees for every swept combination)."""
    cfg = get_arch("recurrentgemma-2b").smoke()
    params = init_params(model_specs(cfg), jax.random.key(0))
    batch = make_batch(cfg)
    lx, _ = forward(params, batch, cfg,
                    ModelContext(clause=SegmentClause(kernel="xla")))
    lp, _ = forward(params, batch, cfg,
                    ModelContext(clause=SegmentClause(
                        kernel="pallas", mlstm_chunk=16, block_q=16,
                        block_k=16)))
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp),
                               atol=5e-3, rtol=5e-3)


def test_param_counts_match_nominal():
    """Param counts stay faithful to the assigned configs."""
    from repro.models.params import param_count
    expect = {
        "xlstm-125m": (0.10e9, 0.2e9),
        "stablelm-3b": (2.5e9, 3.2e9),
        "granite-8b": (7.5e9, 8.6e9),
        "chatglm3-6b": (5.8e9, 6.6e9),
        "starcoder2-3b": (2.8e9, 3.3e9),
        "qwen3-moe-30b-a3b": (28e9, 32e9),
        "kimi-k2-1t-a32b": (0.95e12, 1.1e12),
        "recurrentgemma-2b": (2.5e9, 3.1e9),
    }
    for name, (lo, hi) in expect.items():
        n = param_count(model_specs(get_arch(name)))
        assert lo <= n <= hi, f"{name}: {n/1e9:.2f}B outside [{lo},{hi}]"


def test_init_params_same_in_every_process():
    """Weights made from one seed are the same in another process (string
    hashing is salted per process, so leaf keys must not use hash())."""
    import os
    import subprocess
    import sys
    code = ("import jax\n"
            "from repro.configs import get_arch\n"
            "from repro.models import init_params, model_specs\n"
            "p = init_params(model_specs(get_arch('stablelm-3b').smoke()),"
            " jax.random.key(0))\n"
            "print(repr(sum(float(abs(x.astype('float32')).sum())"
            " for x in jax.tree.leaves(p))))\n")
    sums = [subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=300, env=dict(os.environ, JAX_PLATFORMS="cpu",
                              PYTHONHASHSEED=str(salt))).stdout
        for salt in (1, 2)]
    assert sums[0] and sums[0] == sums[1]
