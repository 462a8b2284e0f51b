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

    q1: (B,H,D); caches: (B,Smax,KV,D); pos: scalar index of the new
    token, or a (B,) vector of per-row positions (the serving engine's
    continuous batching — each slot decodes its own stream).
    Reads the full cache (memory-roofline bound); the Pallas flash-decode
    kernel implements the same contraction blocked over Smax.

    ``upcast=True`` converts the cache to f32 before the contractions (the
    naive baseline: 3x HBM traffic at bf16 caches).  ``upcast=False`` reads
    bf16 directly with f32 accumulation (``preferred_element_type``) —
    identical math on the MXU, a third of the traffic (EXPERIMENTS §Perf).
    """
    B, H, D = q1.shape
    Smax, KV = k_cache.shape[1], k_cache.shape[2]
    G = H // KV
    qg = q1.reshape(B, KV, G, D)
    if upcast:
        s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(jnp.float32),
                       k_cache.astype(jnp.float32))
    else:
        s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(k_cache.dtype), k_cache,
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
    p = jax.nn.softmax(s, axis=-1)
    if upcast:
        o = jnp.einsum("bkgs,bskd->bkgd", p,
                       v_cache.astype(jnp.float32))
    else:
        o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                       preferred_element_type=jnp.float32)
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
    """Abstract KV cache shapes for one layer."""
    KV, D = cfg.num_kv_heads, cfg.head_dim_
    cache_len = min(smax, cfg.window_size) if cfg.window_size else smax
    shp = (batch, cache_len, KV, D)
    return {"k": jax.ShapeDtypeStruct(shp, jnp.dtype(cfg.dtype)),
            "v": jax.ShapeDtypeStruct(shp, jnp.dtype(cfg.dtype))}


def _seq_sharded(ctx: ModelContext, cache) -> bool:
    """True when the provider shards the KV cache's seq dim."""
    if ctx.rules.mesh is None:
        return False
    ps = ctx.rules.pspec(("batch", "kv_seq", "kv_heads", None),
                         cache["k"].shape)
    parts = list(ps)
    return len(parts) > 1 and parts[1] is not None


def attn_decode_shardmap(q, k, v, cache, pos, ctx: ModelContext):
    """Sequence-sharded KV decode via shard_map (EXPERIMENTS §Perf cell C).

    The pure-pjit path dus-updates a cache whose seq dim is sharded; the
    SPMD partitioner handles that with *involuntary full rematerialization*
    (replicate -> update -> reshard) every layer — catastrophic traffic.
    Here each model shard keeps its local (B_l, S_l, KV, D) cache block,
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
    B, Smax, KV, D = cache["k"].shape
    H = q.shape[1]
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
        s = jnp.einsum("bkgd,bskd->bkgs", qg.astype(ck.dtype), ck,
                       preferred_element_type=jnp.float32) * (D ** -0.5)
        ks = lo + jnp.arange(S_l)
        s = jnp.where((ks <= pos)[None, None, None], s, NEG_INF)
        m_l = jnp.max(s, axis=-1, keepdims=True)
        p_l = jnp.exp(s - m_l)
        l_l = jnp.sum(p_l, axis=-1, keepdims=True)
        o_l = jnp.einsum("bkgs,bskd->bkgd", p_l.astype(cv.dtype), cv,
                         preferred_element_type=jnp.float32)
        # distributed softmax combine (log-sum-exp over the model axis)
        # m_l / l_l keep the trailing singleton (B,KV,G,1) for broadcast
        m_g = jax.lax.pmax(m_l, "model")
        l_g = jax.lax.psum(jnp.exp(m_l - m_g) * l_l, "model")
        o = jax.lax.psum(o_l * jnp.exp(m_l - m_g), "model")
        o = o / jnp.maximum(l_g, 1e-30)
        return o.reshape(q.shape[0], H, D).astype(q.dtype), ck, cv

    cache_spec = P(b_ax, "model", None, None)
    o, ck, cv = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(b_ax, None, None), P(b_ax, None, None),
                  P(b_ax, None, None), cache_spec, cache_spec, P()),
        out_specs=(P(b_ax, None, None), cache_spec, cache_spec),
        check_vma=False,
    )(q, k, v, cache["k"], cache["v"], pos)
    return o, {"k": ck, "v": cv}


def attn_decode(p, x1, cache, pos, cfg: ArchConfig, ctx: ModelContext):
    """One-token decode. x1: (B,d_model); cache: {"k","v"} (B,Smax,KV,D).

    ``pos`` is a scalar (the classic batched loop: every row at the same
    position) or a ``(B,)`` vector of per-row positions (continuous
    batching).  The vector path writes the cache with a per-row one-hot
    select and masks per row, so row ``b`` of every output is a function
    of row ``b``'s inputs alone — the serving engine's byte-identity
    contract.  The pallas flash-decode and shard_map kernels take a
    single scalar position, so vector-pos calls use the XLA path.
    """
    q = dense(x1, p["wq"])                             # (B,H,D)
    k = dense(x1, p["wk"])                             # (B,KV,D)
    v = dense(x1, p["wv"])
    q = apply_rope(q, pos, cfg.rope)
    k = apply_rope(k, pos, cfg.rope)
    vector_pos = jnp.ndim(pos) > 0
    if (not vector_pos and ctx.clause.decode_shardmap
            and not cfg.window_size and _seq_sharded(ctx, cache)):
        o, new_cache = attn_decode_shardmap(q, k, v, cache, pos, ctx)
        y = jnp.einsum("bhd,hde->be", o, p["wo"]).astype(x1.dtype)
        return ctx.constrain(y, ("batch", "embed")), new_cache
    cache_len = cache["k"].shape[1]
    slot = pos % cache_len if cfg.window_size else pos  # ring buffer if windowed
    with jax.named_scope("kv_write"):
        if vector_pos:
            # per-row write: a dynamic_update_slice needs one shared scalar
            # slot, so select row b's slot with a one-hot mask instead
            hit = jnp.arange(cache_len)[None, :] == slot[:, None]  # (B,Smax)
            k_cache = jnp.where(hit[:, :, None, None], k[:, None],
                                cache["k"])
            v_cache = jnp.where(hit[:, :, None, None], v[:, None],
                                cache["v"])
        else:
            k_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["k"], k[:, None], slot, axis=1)
            v_cache = jax.lax.dynamic_update_slice_in_dim(
                cache["v"], v[:, None], slot, axis=1)
        k_cache = ctx.constrain(k_cache,
                                ("batch", "kv_seq", "kv_heads", None))
        v_cache = ctx.constrain(v_cache,
                                ("batch", "kv_seq", "kv_heads", None))
    if cfg.window_size:
        # ring buffer: all valid entries attendable except future ones
        o = decode_attention(q, k_cache, v_cache,
                             jnp.minimum(pos, cache_len - 1), window=0,
                             upcast=ctx.clause.cache_upcast)
    elif ctx.clause.kernel == "pallas" and not vector_pos:
        from repro import kernels as kops
        o = kops.flash_decode(q, k_cache, v_cache, pos,
                              block_k=ctx.clause.block_k,
                              interpret=ctx.interpret)
    else:
        o = decode_attention(q, k_cache, v_cache, pos,
                             upcast=ctx.clause.cache_upcast)
    y = jnp.einsum("bhd,hde->be", o, p["wo"]).astype(x1.dtype)
    y = ctx.constrain(y, ("batch", "embed"))
    return y, {"k": k_cache, "v": v_cache}
