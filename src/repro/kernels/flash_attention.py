"""Pallas TPU flash-attention (forward) kernel.

Layout: q (B, H, Sq, D); k/v (B, KV, Sk, D) — head-major so each grid cell
streams contiguous (S, D) tiles HBM->VMEM.  Grid: (B, H, nq, nk) with the
k-block axis innermost (sequential on TPU), carrying the online-softmax
state (acc, m, l) in VMEM scratch.  GQA is handled in the k/v index_map
(query head h reads kv head h // G).  Causal and sliding-window masks are
applied in-kernel; fully-masked blocks are neutralized multiplicatively
(no -inf/-inf pitfalls).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime.backend import interpret_mode

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            scale: float, causal: bool, window: int,
            block_q: int, block_k: int, nk: int):
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, D)
    k = k_ref[0, 0].astype(jnp.float32)                # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)

    iq = pl.program_id(2) * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    jk = ik * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    mask = jnp.ones((block_q, block_k), jnp.bool_)
    if causal:
        mask &= iq >= jk
    if window:
        mask &= iq - jk < window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                # (bq, 1)
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)       # (bq, bk)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal: bool = True, window: int = 0,
                        block_q: int = 512, block_k: int = 1024,
                        interpret: Optional[bool] = None):
    """q: (B,H,Sq,D); k/v: (B,KV,Sk,D) -> (B,H,Sq,D)."""
    B, H, Sq, D = q.shape
    KV, Sk = k.shape[1], k.shape[2]
    G = H // KV
    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    assert Sq % block_q == 0 and Sk % block_k == 0, (Sq, Sk, block_q, block_k)
    nq, nk = Sq // block_q, Sk // block_k
    grid = (B, H, nq, nk)
    kern = functools.partial(
        _kernel, scale=D ** -0.5, causal=causal, window=window,
        block_q=block_q, block_k=block_k, nk=nk)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, block_q, D), lambda b, h, iq, ik: (b, h, iq, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, iq, ik: (b, h // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, D),
                               lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, Sq, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v)
