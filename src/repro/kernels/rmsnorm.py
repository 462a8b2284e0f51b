"""Pallas TPU fused RMSNorm kernel (rows tiled, full feature dim in VMEM)."""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.runtime.backend import interpret_mode


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)                 # (br, d)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(var + eps) * s_ref[...].astype(jnp.float32)
    o_ref[...] = y.astype(o_ref.dtype)


def rmsnorm_fwd(x, scale, *, eps: float = 1e-6, block_rows: int = 256,
                interpret: Optional[bool] = None):
    """x: (N, d); scale: (d,) -> (N, d)."""
    N, d = x.shape
    br = min(block_rows, N)
    while N % br:
        br -= 1
    kern = functools.partial(_kernel, eps=eps)
    return pl.pallas_call(
        kern,
        grid=(N // br,),
        in_specs=[
            pl.BlockSpec((br, d), lambda i: (i, 0)),
            pl.BlockSpec((1, d), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((N, d), x.dtype),
        interpret=interpret_mode(interpret),
    )(x, scale[None, :])
