"""Pallas TPU kernel for the RG-LRU linear recurrence.

Computes ``h_t = exp(log_a_t) * h_{t-1} + b_t`` along the sequence.  The
sequence is tiled into chunks (sequential grid axis) and the feature dim
into lane-wide blocks (parallel grid axes B x nd; sequential axis nc last).
Inside a chunk the recurrence steps row by row over ``rows``-row tiles (one
sublane tile at a time), so the working set is the (chunk, block_d) in/out
blocks plus one carry row: it fits fast memory at any chunk length, and no
prefix sum (which has no TPU lowering) is needed.  The carry ``h`` lives in
VMEM scratch across chunks.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.runtime.backend import interpret_mode


def _kernel(la_ref, b_ref, o_ref, h_ref, *, chunk: int, rows: int):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    def tile(i, h):                                    # h: (1, bd) carry
        r0 = pl.multiple_of(i * rows, rows)
        a = jnp.exp(la_ref[0, pl.ds(r0, rows), :].astype(jnp.float32))
        b = b_ref[0, pl.ds(r0, rows), :].astype(jnp.float32)
        for r in range(rows):
            h = a[r:r + 1] * h + b[r:r + 1]
            o_ref[0, pl.ds(r0 + r, 1), :] = h.astype(o_ref.dtype)
        return h

    h_ref[...] = jax.lax.fori_loop(0, chunk // rows, tile, h_ref[...])


def rglru_fwd(log_a, b, *, chunk: int = 256, block_d: int = 128,
              interpret: Optional[bool] = None):
    """log_a, b: (B, S, dr) -> h: (B, S, dr), f32 math."""
    B, S, dr = log_a.shape
    chunk = min(chunk, S)
    while S % chunk:
        chunk -= 1
    block_d = min(block_d, dr)
    while dr % block_d:
        block_d -= 1
    nc, nd = S // chunk, dr // block_d
    kern = functools.partial(_kernel, chunk=chunk,
                             rows=8 if chunk % 8 == 0 else 1)
    return pl.pallas_call(
        kern,
        grid=(B, nd, nc),
        in_specs=[
            pl.BlockSpec((1, chunk, block_d), lambda ib, idd, ic: (ib, ic, idd)),
            pl.BlockSpec((1, chunk, block_d), lambda ib, idd, ic: (ib, ic, idd)),
        ],
        out_specs=pl.BlockSpec((1, chunk, block_d),
                               lambda ib, idd, ic: (ib, ic, idd)),
        out_shape=jax.ShapeDtypeStruct((B, S, dr), jnp.float32),
        scratch_shapes=[pltpu.VMEM((1, block_d), jnp.float32)],
        interpret=interpret_mode(interpret),
    )(log_a, b)
