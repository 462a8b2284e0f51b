"""Pallas TPU kernel for the chunkwise-parallel mLSTM.

Same math as ``repro.models.xlstm.mlstm_chunk`` for a single (batch, head):
intra-chunk quadratic attention with log-gated decay + inter-chunk matrix
state (C, n, m) carried in VMEM scratch across the sequential chunk axis.
Grid: (B, H, nc).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime.backend import interpret_mode

LOG_EPS = -30.0


def _kernel(q_ref, k_ref, v_ref, li_ref, lf_ref, lic_ref, lfc_ref, o_ref,
            C_ref, n_ref, m_ref, *, chunk: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        C_ref[...] = jnp.zeros_like(C_ref)
        n_ref[...] = jnp.zeros_like(n_ref)
        m_ref[...] = jnp.zeros_like(m_ref)

    f32 = jnp.float32
    q = q_ref[0, 0].astype(f32)                        # (c, dh) pre-scaled
    k = k_ref[0, 0].astype(f32)
    v = v_ref[0, 0].astype(f32)
    # gates arrive twice, as (1, c) rows and (c, 1) columns, so no value
    # needs a lane<->sublane transpose inside the kernel
    li = li_ref[0, 0].astype(f32)                      # (1, c)
    lf = lf_ref[0, 0].astype(f32)
    li_c = lic_ref[0, 0].astype(f32)                   # (c, 1)
    lf_c = lfc_ref[0, 0].astype(f32)

    # inclusive prefix sums b_j = sum_{l<=j} lf_l as triangular matmuls
    # (cumsum has no TPU lowering)
    row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    mask = row >= col                                  # [j, l]: l <= j
    dot = functools.partial(jax.lax.dot_general,
                            precision=jax.lax.Precision.HIGHEST,
                            preferred_element_type=f32)
    b_c = dot(mask.astype(f32), lf_c, (((1,), (0,)), ((), ())))   # (c, 1)
    b_r = dot(lf, (row <= col).astype(f32),
              (((1,), (0,)), ((), ())))                          # (1, c)
    total = jnp.sum(lf, axis=-1, keepdims=True)        # (1, 1)
    m_prev = m_ref[...]                                # (1, 1)

    D = jnp.where(mask, li + b_c - b_r, LOG_EPS)       # (c, c)
    m_state = m_prev + b_c                             # (c, 1)
    m_j = jnp.maximum(jnp.max(D, axis=-1, keepdims=True), m_state)
    S = jnp.exp(D - m_j) * dot(q, k, (((1,), (1,)), ((), ())))
    state_w = jnp.exp(m_state - m_j)                   # (c, 1)
    num = dot(S, v, (((1,), (0,)), ((), ()))) \
        + state_w * dot(q, C_ref[...], (((1,), (0,)), ((), ())))
    den_dot = dot(q, n_ref[...], (((1,), (1,)), ((), ()))) * state_w \
        + jnp.sum(S, axis=-1, keepdims=True)          # (c, 1)
    den = jnp.maximum(jnp.abs(den_dot), jnp.exp(-m_j))
    o_ref[0, 0] = (num / den).astype(o_ref.dtype)

    # ---- state update ----
    k_w_log = li_c + (total - b_c)                     # (c, 1)
    m_new = jnp.maximum(m_prev + total,
                        jnp.max(k_w_log, axis=0, keepdims=True))
    carry_w = jnp.exp(m_prev + total - m_new)          # (1, 1)
    kw = k * jnp.exp(k_w_log - m_new)                  # (c, dh)
    C_ref[...] = carry_w * C_ref[...] + dot(kw.T, v,
                                            (((1,), (0,)), ((), ())))
    n_ref[...] = carry_w * n_ref[...] + jnp.sum(kw, axis=0, keepdims=True)
    m_ref[...] = m_new


def mlstm_chunkwise_fwd(q, k, v, li, lf, *, chunk: int = 256,
                        interpret: Optional[bool] = None):
    """q,k,v: (B,H,S,dh) f32 (q pre-scaled); li,lf: (B,H,S) -> h (B,H,S,dh)."""
    B, H, S, dh = q.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    nc = S // chunk
    rows = [g[:, :, None, :] for g in (li, lf)]       # (B,H,1,S)
    cols = [g[..., None] for g in (li, lf)]            # (B,H,S,1)
    kern = functools.partial(_kernel, chunk=chunk)
    seq = pl.BlockSpec((1, 1, chunk, dh), lambda b, h, ic: (b, h, ic, 0))
    row = pl.BlockSpec((1, 1, 1, chunk), lambda b, h, ic: (b, h, 0, ic))
    col = pl.BlockSpec((1, 1, chunk, 1), lambda b, h, ic: (b, h, ic, 0))
    return pl.pallas_call(
        kern,
        grid=(B, H, nc),
        in_specs=[seq, seq, seq, row, row, col, col],
        out_specs=seq,
        out_shape=jax.ShapeDtypeStruct((B, H, S, dh), jnp.float32),
        scratch_shapes=[
            pltpu.VMEM((dh, dh), jnp.float32),
            pltpu.VMEM((1, dh), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(q, k, v, *rows, *cols)
