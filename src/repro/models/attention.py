"""Attention: GQA/MHA with RoPE (full/2d), causal + sliding-window masks.

Three execution paths, selectable via the segment clause (the ComParX
"directive clause" analogue):
  * ``naive``   — full score matrix; oracle + tiny shapes.
  * ``chunked`` — q-chunked streaming attention (pure-XLA flash analogue);
                  memory O(block_q x S) instead of O(S^2).
  * ``pallas``  — TPU flash-attention kernel (``repro.kernels``).
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.context import ModelContext
from repro.models.layers import apply_rope, dense
from repro.models.params import ParamSpec

NEG_INF = -1e30


def attn_specs(cfg: ArchConfig, dtype: Optional[str] = None):
    dt = dtype or cfg.dtype
    d, H, KV, D = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    s = d ** -0.5
    return {
        "wq": ParamSpec((d, H, D), ("embed", "heads", "head_dim"), "normal", s, dt),
        "wk": ParamSpec((d, KV, D), ("embed", "kv_heads", "head_dim"), "normal", s, dt),
        "wv": ParamSpec((d, KV, D), ("embed", "kv_heads", "head_dim"), "normal", s, dt),
        "wo": ParamSpec((H, D, d), ("heads", "head_dim", "embed"), "normal",
                        (H * D) ** -0.5, dt),
    }


# --- core math ---------------------------------------------------------------

def _mask(pos_q, pos_k, window: int):
    m = pos_q[:, None] >= pos_k[None, :]
    if window:
        m &= pos_q[:, None] - pos_k[None, :] < window
    return m


def naive_attention(q, k, v, *, pos_q, pos_k, window: int = 0):
    """q: (B,Sq,H,D), k/v: (B,Sk,KV,D) -> (B,Sq,H,D)."""
    B, Sq, H, D = q.shape
    KV = k.shape[2]
    G = H // KV
    qg = q.reshape(B, Sq, KV, G, D).astype(jnp.float32)
    s = jnp.einsum("bqkgd,bskd->bkgqs", qg, k.astype(jnp.float32))
    s = s * (D ** -0.5)
    m = _mask(pos_q, pos_k, window)
    s = jnp.where(m[None, None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bkgqs,bskd->bqkgd", p, v.astype(jnp.float32))
    return o.reshape(B, Sq, H, D).astype(q.dtype)


def chunked_attention(q, k, v, *, pos_q, pos_k, window: int = 0,
                      q_chunk: int = 512):
    """Streaming q-chunked attention (same math as naive, bounded memory).

    For sliding-window attention only a (window + q_chunk)-wide K slice is
    read per chunk, making long-context local attention O(S * window).
    """
    B, Sq, H, D = q.shape
    Sk = k.shape[1]
    if Sq % q_chunk or Sq <= q_chunk:
        return naive_attention(q, k, v, pos_q=pos_q, pos_k=pos_k,
                               window=window)
    nq = Sq // q_chunk
    k_span = min(Sk, window + q_chunk) if window else Sk
    k_span = max(k_span, q_chunk)
    # when the window covers the whole K range, per-chunk dynamic slices
    # would be full copies of K/V every chunk — read K/V directly instead
    # (EXPERIMENTS §Perf, starcoder2 cell: 3x memory-term reduction)
    slice_k = bool(window) and k_span < Sk

    def one(i):
        qs = jax.lax.dynamic_slice_in_dim(q, i * q_chunk, q_chunk, axis=1)
        pq = jax.lax.dynamic_slice_in_dim(pos_q, i * q_chunk, q_chunk, axis=0)
        if slice_k:
            start = jnp.clip(i * q_chunk + q_chunk - k_span, 0, Sk - k_span)
            ks = jax.lax.dynamic_slice_in_dim(k, start, k_span, axis=1)
            vs = jax.lax.dynamic_slice_in_dim(v, start, k_span, axis=1)
            pk = jax.lax.dynamic_slice_in_dim(pos_k, start, k_span, axis=0)
        else:
            ks, vs, pk = k, v, pos_k
        return naive_attention(qs, ks, vs, pos_q=pq, pos_k=pk, window=window)

    out = jax.lax.map(one, jnp.arange(nq))            # (nq, B, c, H, D)
    return jnp.moveaxis(out, 0, 1).reshape(B, Sq, H, D)


def decode_attention(q1, k_cache, v_cache, pos, *, window: int = 0,
                     upcast: bool = True):
    """One-token attention against a KV cache.

    q1: (B,H,D); pos: scalar index of the new token, or a (B,) vector of
    per-row positions (the serving engine's continuous batching — each
    slot decodes its own stream).  Reads the full cache
    (memory-roofline bound); the Pallas flash-decode kernel implements
    the same contraction blocked over Smax.

    The caches' form picks the contraction:

    * (B,Smax,KV,D): per KV head, against each head's D entries.
    * (B,Smax,KV*D), the rows as the decode cache stores them: matmuls
      against whole rows, q spread block-diagonally over a row (query
      head h's D entries in its KV head's block, exact zeros elsewhere)
      and of the values only each head's own block kept.  No head
      dimension is cut out of the cache, so it is read once, in the
      layout it is written in, at KV times the per-head contraction's
      flops: 2H per cache element, H per byte of a bf16 cache.

    ``upcast=True`` converts the cache to f32 before the contractions (the
    naive baseline: 3x HBM traffic at bf16 caches).  ``upcast=False`` reads
    bf16 directly with f32 accumulation (``preferred_element_type``) —
    identical math on the MXU, a third of the traffic (EXPERIMENTS §Perf).
    """
    B, H, D = q1.shape
    Smax = k_cache.shape[1]
    per_head = k_cache.ndim == 4
    KV = k_cache.shape[2] if per_head else k_cache.shape[2] // D
    G = H // KV
    dt = jnp.float32 if upcast else k_cache.dtype
    k_cache, v_cache = k_cache.astype(dt), v_cache.astype(dt)
    if per_head:
        qg = q1.reshape(B, KV, G, D).astype(dt)
        s = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                       preferred_element_type=jnp.float32)
    else:
        F = KV * D
        # own[k, f]: row entry f belongs to KV head k; query head k*G + g
        own = (jnp.arange(KV)[:, None] == jnp.arange(F)[None, :] // D)
        # group g's query heads side by side, one per KV head: (B,G,F)
        qf = jnp.swapaxes(q1.reshape(B, KV, G, D), 1, 2).reshape(B, 1, G, F)
        qbd = jnp.where(own[:, None], qf, 0).reshape(B, KV, G, F)
        s = jnp.einsum("bkgf,bsf->bkgs", qbd.astype(dt), k_cache,
                       preferred_element_type=jnp.float32)
    s = s * (D ** -0.5)
    ks = jnp.arange(Smax)
    if jnp.ndim(pos) == 0:
        m = ks <= pos
        if window:
            m &= ks > pos - window
        m = m[None, None, None]
    else:
        # per-row positions (continuous batching): row b masks against
        # its own pos, so its output depends on row b's inputs alone
        m = ks[None, :] <= pos[:, None]                 # (B,Smax)
        if window:
            m &= ks[None, :] > pos[:, None] - window
        m = m[:, None, None, :]
    s = jnp.where(m, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(dt)
    if per_head:
        o = jnp.einsum("bkgs,bskd->bkgd", p, v_cache,
                       preferred_element_type=jnp.float32)
    else:
        o = jnp.einsum("bkgs,bsf->bkgf", p, v_cache,
                       preferred_element_type=jnp.float32)
        # keep each head's own block: (B,G,F), then (B,KV,G,D)
        o = jnp.where(own[:, None], o, 0.0).sum(axis=1)
        o = jnp.swapaxes(o.reshape(B, G, KV, D), 1, 2)
    return o.reshape(B, H, D).astype(q1.dtype)


# --- module-level apply ------------------------------------------------------

def _project_qkv(p, x, cfg: ArchConfig, ctx: ModelContext, positions):
    q = dense(x, p["wq"])                              # (B,S,H,D)
    k = dense(x, p["wk"])                              # (B,S,KV,D)
    v = dense(x, p["wv"])
    q = apply_rope(q, positions, cfg.rope)
    k = apply_rope(k, positions, cfg.rope)
    q = ctx.constrain(q, ("batch", "seq", "heads", None))
    k = ctx.constrain(k, ("batch", "seq", "kv_heads", None))
    v = ctx.constrain(v, ("batch", "seq", "kv_heads", None))
    return q, k, v


def attn_apply(p, x, cfg: ArchConfig, ctx: ModelContext, positions):
    """Full-sequence attention (train / prefill). x: (B,S,d_model)."""
    q, k, v = _project_qkv(p, x, cfg, ctx, positions)
    cl = ctx.clause
    if cl.kernel == "pallas":
        from repro import kernels as kops
        o = kops.flash_attention(
            q, k, v, causal=True, window=cfg.window_size,
            block_q=cl.block_q, block_k=cl.block_k, interpret=ctx.interpret)
    else:
        o = chunked_attention(q, k, v, pos_q=positions, pos_k=positions,
                              window=cfg.window_size, q_chunk=cl.block_q)
    o = ctx.constrain(o, ("batch", "seq", "heads", None))
    y = jnp.einsum("bshd,hde->bse", o, p["wo"]).astype(x.dtype)
    return ctx.constrain(y, ("batch", "seq", "embed"))


def attn_cache_spec(cfg: ArchConfig, batch: int, smax: int):
    """Abstract KV cache shapes for one layer: (B, cache_len, KV*D), a
    token's row holding its KV heads' D-vectors side by side.

    Stored this way a new token's row is contiguous, so the decode step
    writes it in place (:func:`attn_decode`), and the layout a TPU gives
    the array has no padding for a head size under 128 lanes (D=80)."""
    KV, D = cfg.num_kv_heads, cfg.head_dim_
    cache_len = min(smax, cfg.window_size) if cfg.window_size else smax
    shp = (batch, cache_len, KV * D)
    return {"k": jax.ShapeDtypeStruct(shp, jnp.dtype(cfg.dtype)),
            "v": jax.ShapeDtypeStruct(shp, jnp.dtype(cfg.dtype))}


#: logical axes of one layer's (B, Smax, KV*D) cache
CACHE_AXES = ("batch", "kv_seq", "kv_heads")


def _cache_parts(ctx: ModelContext, shape):
    """The provider's mesh axes (or None) for each dim of a
    (B,Smax,KV*D) cache."""
    if ctx.rules.mesh is None:
        return (None,) * len(shape)
    parts = tuple(ctx.rules.pspec(CACHE_AXES, shape))
    return parts + (None,) * (len(shape) - len(parts))


def _devices(ctx: ModelContext, part) -> int:
    """How many devices a partition-spec entry splits a dim over."""
    axes = () if part is None else (part,) if isinstance(part, str) else part
    return math.prod(ctx.rules.axis_sizes[a] for a in axes)


#: the most query heads for which decode attention contracts whole cache
#: rows (:func:`decode_attention`): that does H flops per byte of a bf16
#: cache, at 64 about a quarter of a v5e's 240 flops per byte of HBM, so
#: the read stays memory-bound
BLOCK_DIAG_MAX_HEADS = 64


def attn_decode_shardmap(q, k, v, cache, pos, ctx: ModelContext):
    """Sequence-sharded KV decode via shard_map (EXPERIMENTS §Perf cell C).

    The pure-pjit path dus-updates a cache whose seq dim is sharded; the
    SPMD partitioner handles that with *involuntary full rematerialization*
    (replicate -> update -> reshard) every layer — catastrophic traffic.
    Here each model shard keeps its local (B_l, S_l, KV*D) cache block,
    updates it only when ``pos`` lands in its range (collective-free), and
    attention is combined across shards with a single log-sum-exp psum —
    the same combine contract as the Pallas flash-decode kernel's LSE
    output (tests/test_kernels.py::test_flash_decode_lse_combine).
    """
    from jax.sharding import PartitionSpec as P

    mesh = ctx.rules.mesh
    axis_sizes = ctx.rules.axis_sizes
    tp = axis_sizes["model"]
    batch_axes = tuple(a for a in ("pod", "data") if a in axis_sizes)
    B, Smax = cache["k"].shape[:2]
    H, D = q.shape[1:]
    KV = k.shape[1]
    G = H // KV
    S_l = Smax // tp
    dp = 1
    for a in batch_axes:
        dp *= axis_sizes[a]
    b_ax = batch_axes if batch_axes and B % dp == 0 else None

    def local(q, k, v, ck, cv, pos):
        rank = jax.lax.axis_index("model")
        lo = rank * S_l
        slot = jnp.clip(pos - lo, 0, S_l - 1)
        in_range = (pos >= lo) & (pos < lo + S_l)
        with jax.named_scope("kv_write"):
            ck_u = jax.lax.dynamic_update_slice_in_dim(ck, k[:, None], slot,
                                                       axis=1)
            cv_u = jax.lax.dynamic_update_slice_in_dim(cv, v[:, None], slot,
                                                       axis=1)
            ck = jnp.where(in_range, ck_u, ck)
            cv = jnp.where(in_range, cv_u, cv)
        # local partial attention with global-position mask
        qg = q.reshape(q.shape[0], KV, G, D)
        ck4 = ck.reshape(ck.shape[:2] + (KV, D))
        cv4 = cv.reshape(cv.shape[:2] + (KV, D))
        s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(ck.dtype), ck4,
                       preferred_element_type=jnp.float32) * (D ** -0.5)
        ks = lo + jnp.arange(S_l)
        s = jnp.where((ks <= pos)[None, None, None], s, NEG_INF)
        m_l = jnp.max(s, axis=-1, keepdims=True)
        p_l = jnp.exp(s - m_l)
        l_l = jnp.sum(p_l, axis=-1, keepdims=True)
        o_l = jnp.einsum("bkgs,bskd->bkgd", p_l.astype(cv.dtype), cv4,
                         preferred_element_type=jnp.float32)
        # distributed softmax combine (log-sum-exp over the model axis)
        # m_l / l_l keep the trailing singleton (B,KV,G,1) for broadcast
        m_g = jax.lax.pmax(m_l, "model")
        l_g = jax.lax.psum(jnp.exp(m_l - m_g) * l_l, "model")
        o = jax.lax.psum(o_l * jnp.exp(m_l - m_g), "model")
        o = o / jnp.maximum(l_g, 1e-30)
        return o.reshape(q.shape[0], H, D).astype(q.dtype), ck, cv

    cache_spec = P(b_ax, "model", None)
    o, ck, cv = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b_ax, None, None), P(b_ax, None),
                  P(b_ax, None), cache_spec, cache_spec, P()),
        out_specs=(P(b_ax, None, None), cache_spec, cache_spec),
        check_vma=False,
    )(q, k.reshape(B, -1), v.reshape(B, -1), cache["k"], cache["v"], pos)
    return o, {"k": ck, "v": cv}


def _write_rows(c, new, slot, layer):
    """Store one step's new rows ``new`` (B,KV,D) into layer ``layer`` of
    the (L,B,Smax,KV*D) stack ``c``.

    A scalar ``slot`` writes every row there with one
    ``dynamic_update_slice``; a (B,) ``slot`` writes row ``b`` at
    ``slot[b]`` with one scatter of B rows, so row ``b`` of the result
    depends on row ``b``'s inputs alone.  Nothing else of ``c`` is read or
    written: on a donated, loop-carried buffer the update is in place.
    """
    new = new.reshape(new.shape[0], -1).astype(c.dtype)     # (B,KV*D)
    if jnp.ndim(slot) == 0:
        return jax.lax.dynamic_update_slice(c, new[None, :, None],
                                            (layer, 0, slot, 0))
    rows = jnp.arange(new.shape[0])
    return c.at[layer, rows, slot].set(new, indices_are_sorted=True,
                                       unique_indices=True)


def attn_decode(p, x1, cache, pos, cfg: ArchConfig, ctx: ModelContext,
                layer):
    """One-token decode of layer ``layer``. x1: (B,d_model); cache:
    {"k","v"}, the (L,B,Smax,KV*D) stacks of the layer's group (L=1 for
    a group that is not scanned).

    ``pos`` is a scalar (the classic batched loop: every row at the same
    position) or a ``(B,)`` vector of per-row positions (continuous
    batching).  The vector path writes row ``b`` at its own slot and
    masks per row, so row ``b`` of every output is a function of row
    ``b``'s inputs alone — the serving engine's byte-identity contract.
    The pallas flash-decode and shard_map kernels take a single scalar
    position, so vector-pos calls use the XLA path.

    **In-place cache contract.**  The stacks are carried (the decode step
    threads them through its layer loop as loop state) and donated (the
    engine's step), so the B new rows are the only bytes written
    (:func:`_write_rows`); attention then reads layer ``layer``'s slice
    of the updated stacks and never writes it.  Returns ``(y, cache)``.
    The shard_map path (a cache split over the sequence) keeps its own
    local write.

    Attention contracts whole cache rows (:func:`decode_attention`)
    unless the provider splits the rows over devices (the partial scores
    would then be summed across them) or the model has more than
    :data:`BLOCK_DIAG_MAX_HEADS` query heads; then it reads the slice
    per KV head.
    """
    q = dense(x1, p["wq"])                             # (B,H,D)
    k = dense(x1, p["wk"])                             # (B,KV,D)
    v = dense(x1, p["wv"])
    q = apply_rope(q, pos, cfg.rope)
    k = apply_rope(k, pos, cfg.rope)
    vector_pos = jnp.ndim(pos) > 0

    def read(c):
        return jax.lax.dynamic_index_in_dim(c, layer, 0, keepdims=False)

    parts = _cache_parts(ctx, cache["k"].shape[1:])
    if (not vector_pos and ctx.clause.decode_shardmap
            and not cfg.window_size and parts[1] is not None):
        o, new = attn_decode_shardmap(
            q, k, v, {n: read(c) for n, c in cache.items()}, pos, ctx)
        cache = {n: jax.lax.dynamic_update_index_in_dim(cache[n], c, layer, 0)
                 for n, c in new.items()}
        y = jnp.einsum("bhd,hde->be", o, p["wo"]).astype(x1.dtype)
        return ctx.constrain(y, ("batch", "embed")), cache
    cache_len = cache["k"].shape[2]
    slot = pos % cache_len if cfg.window_size else pos  # ring buffer if windowed
    with jax.named_scope("kv_write"):
        cache = {n: ctx.constrain(_write_rows(cache[n], new, slot, layer),
                                  ("layers",) + CACHE_AXES)
                 for n, new in (("k", k), ("v", v))}
    k_cache, v_cache = read(cache["k"]), read(cache["v"])
    heads = k_cache.shape[:2] + k.shape[1:]              # (B,Smax,KV,D)
    if (_devices(ctx, parts[2]) > 1
            or q.shape[1] > BLOCK_DIAG_MAX_HEADS):
        k_cache, v_cache = k_cache.reshape(heads), v_cache.reshape(heads)
    if cfg.window_size:
        # ring buffer: all valid entries attendable except future ones
        o = decode_attention(q, k_cache, v_cache,
                             jnp.minimum(pos, cache_len - 1), window=0,
                             upcast=ctx.clause.cache_upcast)
    elif ctx.clause.kernel == "pallas" and not vector_pos:
        from repro import kernels as kops
        o = kops.flash_decode(q, k_cache.reshape(heads),
                              v_cache.reshape(heads),
                              pos, block_k=ctx.clause.block_k,
                              interpret=ctx.interpret)
    else:
        o = decode_attention(q, k_cache, v_cache, pos,
                             upcast=ctx.clause.cache_upcast)
    y = jnp.einsum("bhd,hde->be", o, p["wo"]).astype(x1.dtype)
    y = ctx.constrain(y, ("batch", "embed"))
    return y, cache
