"""Full causal-LM assembly: embedding -> scanned block groups -> head.

The layer stack is split into homogeneous *scan groups*
(``ArchConfig.stack_plan``); each group is one ComParX **segment** with its
own :class:`ModelContext` (sharding rules + execution clause).  Groups with
``repeats > 1`` are executed with ``jax.lax.scan`` over stacked parameters
so the HLO stays compact at any depth.  Each segment runs under a
``jax.named_scope`` of its name (``embed``, ``g<i>``, ``head``), which
lands in the compiled program's ``op_name`` metadata and nowhere else.
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ScanGroup
from repro.models.blocks import (ATTN_KINDS, block_apply, block_cache_spec,
                                 block_decode, block_specs)
from repro.models.context import ModelContext
from repro.models.layers import norm_apply, norm_specs
from repro.models.params import ParamSpec, stack_specs

SEG_EMBED = "embed"
SEG_HEAD = "head"


def segment_names(cfg: ArchConfig):
    return ([SEG_EMBED]
            + [f"g{i}" for i in range(len(cfg.stack_plan()))]
            + [SEG_HEAD])


def model_specs(cfg: ArchConfig):
    d, V = cfg.d_model, cfg.vocab_size
    specs = {SEG_EMBED: {"tok": ParamSpec((V, d), ("vocab", "embed"),
                                          "normal", 1.0, cfg.dtype)}}
    for gi, group in enumerate(cfg.stack_plan()):
        gspec = {}
        for j, kind in enumerate(group.pattern):
            bs = block_specs(kind, cfg)
            gspec[f"b{j}"] = stack_specs(bs, group.repeats) \
                if group.repeats > 1 else bs
        specs[f"g{gi}"] = gspec
    head: Dict[str, object] = {"norm": norm_specs(d, cfg.norm, cfg.dtype)}
    if not cfg.tie_embeddings:
        head["out"] = ParamSpec((d, V), ("embed", "vocab"), "normal",
                                d ** -0.5, cfg.dtype)
    specs[SEG_HEAD] = head
    return specs


def _ctx_for(ctxs, seg: str) -> ModelContext:
    if isinstance(ctxs, ModelContext):
        return ctxs
    return ctxs.get(seg, ctxs.get("*", ModelContext()))


def _remat(fn, clause):
    if clause.remat == "dots":
        # no-batch-dims policy: saves weight matmuls but NOT attention
        # score matrices (saving those costs O(S^2) HBM per layer)
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims)
    if clause.remat == "full":
        return jax.checkpoint(fn)
    return fn


def _run_group(x, gparams, group: ScanGroup, cfg, ctx, positions):
    """Forward one scan group. Returns (x, aux)."""
    def superblock(x, layer_params):
        aux = jnp.zeros((), jnp.float32)
        for j, kind in enumerate(group.pattern):
            x, a = block_apply(kind, layer_params[f"b{j}"], x, cfg, ctx,
                               positions)
            aux = aux + a
        return x, aux
    fn = _remat(superblock, ctx.clause)
    if group.repeats == 1:
        return fn(x, gparams)
    def step(carry, layer_params):
        x, aux = carry
        x, a = fn(x, layer_params)
        return (x, aux + a), None
    (x, aux), _ = jax.lax.scan(step, (x, jnp.zeros((), jnp.float32)),
                               gparams, unroll=ctx.clause.scan_unroll)
    return x, aux


def embed_tokens(params, tokens, cfg: ArchConfig, ctx: ModelContext):
    x = jnp.take(params[SEG_EMBED]["tok"], tokens, axis=0)
    axes = ("batch", "seq", "embed") if x.ndim == 3 else ("batch", "embed")
    return ctx.constrain(x, axes)


def lm_head(params, x, cfg: ArchConfig, ctx: ModelContext):
    x = norm_apply(params[SEG_HEAD]["norm"], x, cfg.norm)
    w = params[SEG_EMBED]["tok"].T if cfg.tie_embeddings \
        else params[SEG_HEAD]["out"]
    logits = jnp.einsum("...d,dv->...v", x, w,
                        preferred_element_type=jnp.float32)
    axes = ("batch", "seq", "vocab") if logits.ndim == 3 \
        else ("batch", "vocab")
    return ctx.constrain(logits, axes)


def forward(params, batch, cfg: ArchConfig, ctxs):
    """Train/prefill forward. batch: {"tokens" | "embeds", ...}.

    Returns (logits (B,S,V) f32, aux_loss scalar).
    """
    ectx = _ctx_for(ctxs, SEG_EMBED)
    with jax.named_scope(SEG_EMBED):
        if "embeds" in batch:          # vlm/audio stub frontend
            x = ectx.constrain(batch["embeds"], ("batch", "seq", "embed"))
        else:
            x = embed_tokens(params, batch["tokens"], cfg, ectx)
    S = x.shape[1]
    positions = jnp.arange(S, dtype=jnp.int32)
    aux = jnp.zeros((), jnp.float32)
    for gi, group in enumerate(cfg.stack_plan()):
        seg = f"g{gi}"
        with jax.named_scope(seg):
            x, a = _run_group(x, params[seg], group, cfg,
                              _ctx_for(ctxs, seg), positions)
        aux = aux + a
    with jax.named_scope(SEG_HEAD):
        logits = lm_head(params, x, cfg, _ctx_for(ctxs, SEG_HEAD))
    return logits, aux


# --- decode ------------------------------------------------------------------

def cache_specs(cfg: ArchConfig, batch: int, smax: int):
    """Abstract decode cache for the whole stack (stacked per group)."""
    caches = {}
    for gi, group in enumerate(cfg.stack_plan()):
        gcache = {}
        for j, kind in enumerate(group.pattern):
            cs = block_cache_spec(kind, cfg, batch, smax)
            if group.repeats > 1:
                cs = jax.tree.map(
                    lambda s: jax.ShapeDtypeStruct(
                        (group.repeats,) + s.shape, s.dtype), cs)
            gcache[f"b{j}"] = cs
        caches[f"g{gi}"] = gcache
    return caches


def init_cache(cfg: ArchConfig, batch: int, smax: int):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                        cache_specs(cfg, batch, smax))


def decode_group(x, gparams, gcache, group: ScanGroup, cfg: ArchConfig,
                 ctx: ModelContext, pos):
    """One scan group of the decode step. Returns (x, new gcache).

    Each attention block's KV stack (L,B,Smax,KV*D) is loop state of
    the group's layer loop: layer ``l`` writes its B new rows into the
    carried stack and reads its own slice of it, read-only
    (:func:`repro.models.attention.attn_decode`), so no layer slice is
    cut out and re-stacked and, with the stack donated, the step updates
    the cache in place.  A group that is not scanned passes its caches
    as one-layer stacks.  Recurrent states (a few KB a slot) pass
    through the scan's inputs and outputs.
    """
    kv = {f"b{j}" for j, kind in enumerate(group.pattern)
          if kind in ATTN_KINDS}

    def superblock(x, lp, lc, layer):
        nc = {}
        for j, kind in enumerate(group.pattern):
            b = f"b{j}"
            x, nc[b] = block_decode(kind, lp[b], x, lc[b], pos, cfg, ctx,
                                    layer)
        return x, nc

    if group.repeats == 1:
        one = lambda t: jax.tree.map(lambda c: c[None], t)  # noqa: E731
        x, nc = superblock(x, gparams, {b: one(c) if b in kv else c
                                        for b, c in gcache.items()}, 0)
        return x, {b: jax.tree.map(lambda c: c[0], c) if b in kv else c
                   for b, c in nc.items()}

    def step(carry, xs):
        x, stacks = carry
        lp, states, layer = xs
        x, nc = superblock(x, lp, {**stacks, **states}, layer)
        return ((x, {b: c for b, c in nc.items() if b in kv}),
                {b: c for b, c in nc.items() if b not in kv})

    stacks = {b: c for b, c in gcache.items() if b in kv}
    states = {b: c for b, c in gcache.items() if b not in kv}
    (x, stacks), states = jax.lax.scan(
        step, (x, stacks),
        (gparams, states, jnp.arange(group.repeats, dtype=jnp.int32)),
        unroll=ctx.clause.scan_unroll)
    return x, {**stacks, **states}


def decode_step(params, caches, tokens, pos, cfg: ArchConfig, ctxs):
    """One decoding step. tokens: (B,) int32; pos: scalar int32, or (B,)
    per-row positions.

    Returns (logits (B,V) f32, new caches).  The caches are updated in
    place when the caller donates them (:func:`decode_group`): each
    attention layer writes only its B new rows.
    """
    with jax.named_scope(SEG_EMBED):
        x = embed_tokens(params, tokens, cfg, _ctx_for(ctxs, SEG_EMBED))
    new_caches = {}
    for gi, group in enumerate(cfg.stack_plan()):
        seg = f"g{gi}"
        ctx = _ctx_for(ctxs, seg).with_(decode=True)
        with jax.named_scope(seg):
            x, new_caches[seg] = decode_group(x, params[seg], caches[seg],
                                              group, cfg, ctx, pos)
    with jax.named_scope(SEG_HEAD):
        logits = lm_head(params, x, cfg, _ctx_for(ctxs, SEG_HEAD))
    return logits, new_caches
