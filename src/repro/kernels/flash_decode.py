"""Pallas TPU flash-decode kernel: one-token attention over a long KV cache.

Layout: q (B, H, D); k/v (B, KV, Smax, D) head-major.  Grid (B, H, nk)
streams the KV cache in ``block_k`` tiles, carrying online-softmax state in
VMEM scratch.  The token position ``pos`` arrives as a (1, 1) int32 array in
SMEM and masks out not-yet-written cache slots.  Outputs are laid out
(B, H, 1, D) and (B, H, 1, 1) so every block's last two dims are the full
array dims, as the TPU tiling rule requires.  Emits the attention output
and, optionally, per-(head) LSE so sequence-sharded shards can be combined
with a single ``psum`` (see ``repro.serve``).
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


def _kernel(pos_ref, q_ref, k_ref, v_ref, o_ref, lse_ref,
            acc_ref, m_ref, l_ref, *, scale, block_k, nk):
    ik = pl.program_id(2)

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    pos = pos_ref[0, 0]
    q = q_ref[0, 0].astype(jnp.float32) * scale        # (1, D) row
    k = k_ref[0, 0].astype(jnp.float32)                # (bk, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (1, bk)
    jk = ik * block_k + jax.lax.broadcasted_iota(jnp.int32, (1, block_k), 1)
    mask = jk <= pos
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = m_ref[...] + jnp.log(l)


def flash_decode_fwd(q, k, v, pos, *, block_k: int = 1024,
                     interpret: Optional[bool] = None, return_lse: bool = False):
    """q: (B,H,D); k/v: (B,KV,Smax,D); pos scalar int32 -> (B,H,D)."""
    B, H, D = q.shape
    KV, Smax = k.shape[1], k.shape[2]
    G = H // KV
    block_k = min(block_k, Smax)
    assert Smax % block_k == 0
    nk = Smax // block_k
    q4 = q[:, :, None, :]                               # (B,H,1,D)
    pos_arr = jnp.full((1, 1), pos, jnp.int32)
    kern = functools.partial(_kernel, scale=D ** -0.5,
                             block_k=block_k, nk=nk)
    out, lse = pl.pallas_call(
        kern,
        grid=(B, H, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, 1, D), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ik: (b, h // G, ik, 0)),
            pl.BlockSpec((1, 1, block_k, D), lambda b, h, ik: (b, h // G, ik, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, D), lambda b, h, ik: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1), lambda b, h, ik: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, 1, D), q.dtype),
            jax.ShapeDtypeStruct((B, H, 1, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((1, D), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(pos_arr, q4, k, v)
    out, lse = out[:, :, 0], lse[:, :, 0, 0]
    return (out, lse) if return_lse else out
