"""jit-ready wrappers around the Pallas kernels (model-layout adapters).

Every op is differentiable via ``jax.custom_vjp``: forward runs the Pallas
kernel, backward runs the vjp of the pure-jnp reference (chunked where
memory matters).  ``interpret=None`` runs the kernels compiled on a TPU
backend and interpreted elsewhere (``repro.runtime.backend``).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import ref as R
from repro.kernels.flash_attention import flash_attention_fwd
from repro.kernels.flash_decode import flash_decode_fwd
from repro.kernels.mlstm import mlstm_chunkwise_fwd
from repro.kernels.rglru import rglru_fwd
from repro.kernels.rmsnorm import rmsnorm_fwd


def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int = 512, block_k: int = 1024,
                    interpret: Optional[bool] = None):
    """Model layout: q (B,S,H,D); k/v (B,S,KV,D) -> (B,S,H,D)."""

    def _run(q, k, v):
        qh = jnp.moveaxis(q, 2, 1)                     # (B,H,S,D)
        kh = jnp.moveaxis(k, 2, 1)
        vh = jnp.moveaxis(v, 2, 1)
        o = flash_attention_fwd(qh, kh, vh, causal=causal, window=window,
                                block_q=block_q, block_k=block_k,
                                interpret=interpret)
        return jnp.moveaxis(o, 1, 2)

    def _ref(q, k, v):
        from repro.models.attention import chunked_attention
        S, Sk = q.shape[1], k.shape[1]
        return chunked_attention(
            q, k, v, pos_q=jnp.arange(S), pos_k=jnp.arange(Sk),
            window=window, q_chunk=block_q)

    @jax.custom_vjp
    def fa(q, k, v):
        return _run(q, k, v)

    def fa_fwd(q, k, v):
        return _run(q, k, v), (q, k, v)

    def fa_bwd(res, g):
        q, k, v = res
        _, vjp = jax.vjp(_ref, q, k, v)
        return vjp(g)

    fa.defvjp(fa_fwd, fa_bwd)
    return fa(q, k, v)


def flash_decode(q, k_cache, v_cache, pos, *, block_k: int = 1024,
                 interpret: Optional[bool] = None, return_lse: bool = False):
    """q (B,H,D); caches (B,Smax,KV,D): the decode cache's stored
    (B,Smax,KV*D) rows split into heads."""
    kh = jnp.moveaxis(k_cache, 2, 1)                   # (B,KV,Smax,D)
    vh = jnp.moveaxis(v_cache, 2, 1)
    return flash_decode_fwd(q, kh, vh, pos, block_k=block_k,
                            interpret=interpret, return_lse=return_lse)


def rglru(log_a, b, *, chunk: int = 256, interpret: Optional[bool] = None):
    """log_a, b: (B,S,dr) -> h (B,S,dr) f32."""

    @jax.custom_vjp
    def op(log_a, b):
        return rglru_fwd(log_a, b, chunk=chunk, interpret=interpret)

    def op_fwd(log_a, b):
        return op(log_a, b), (log_a, b)

    def op_bwd(res, g):
        log_a, b = res
        _, vjp = jax.vjp(R.rglru_ref, log_a, b)
        return vjp(g)

    op.defvjp(op_fwd, op_bwd)
    return op(log_a, b)


def mlstm_chunkwise(q, k, v, li, lf, *, chunk: int = 256,
                    interpret: Optional[bool] = None):
    """q,k,v: (B,H,S,dh) f32 (q pre-scaled); li,lf: (B,H,S) -> (B,H,S,dh)."""

    def _ref(q, k, v, li, lf):
        from repro.models.xlstm import mlstm_chunk
        B, H, S, dh = q.shape
        c = min(chunk, S)
        while S % c:
            c -= 1
        nc = S // c
        rs = lambda t: jnp.moveaxis(
            t.reshape(*t.shape[:2], nc, c, *t.shape[3:]), 2, 0)
        state0 = (jnp.zeros((B, H, dh, dh), jnp.float32),
                  jnp.zeros((B, H, dh), jnp.float32),
                  jnp.zeros((B, H), jnp.float32))

        def step(state, inp):
            h, new = mlstm_chunk(*inp, state)
            return new, h
        _, hs = jax.lax.scan(step, state0,
                             (rs(q), rs(k), rs(v), rs(li), rs(lf)))
        return jnp.moveaxis(hs, 0, 2).reshape(B, H, S, dh)

    @jax.custom_vjp
    def op(q, k, v, li, lf):
        return mlstm_chunkwise_fwd(q, k, v, li, lf, chunk=chunk,
                                   interpret=interpret)

    def op_fwd(q, k, v, li, lf):
        return op(q, k, v, li, lf), (q, k, v, li, lf)

    def op_bwd(res, g):
        _, vjp = jax.vjp(_ref, *res)
        return vjp(g)

    op.defvjp(op_fwd, op_bwd)
    return op(q, k, v, li, lf)


def rmsnorm(x, scale, *, eps: float = 1e-6, interpret: Optional[bool] = None):
    """x: (..., d) -> fused rmsnorm."""
    shp = x.shape
    x2 = x.reshape(-1, shp[-1])

    @jax.custom_vjp
    def op(x2, scale):
        return rmsnorm_fwd(x2, scale, eps=eps, interpret=interpret)

    def op_fwd(x2, scale):
        return op(x2, scale), (x2, scale)

    def op_bwd(res, g):
        x2, scale = res
        _, vjp = jax.vjp(lambda x, s: R.rmsnorm_ref(x, s, eps), x2, scale)
        return vjp(g)

    op.defvjp(op_fwd, op_bwd)
    return op(x2, scale).reshape(shp)
