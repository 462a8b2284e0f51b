"""Serve-step factories: prefill and decode under a ComParX plan.

Decode state sharding follows each segment's provider rules; KV caches of
low-kv-head archs (granite kv=8, chatglm/starcoder kv=2 on a 16-way model
axis) are sharded along the *sequence* dim with LSE-combining attention —
the XLA path expresses this purely with sharding constraints.
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.plan import Plan, build_contexts
from repro.models.attention import CACHE_AXES
from repro.models.blocks import ATTN_KINDS
from repro.models.model import (SEG_EMBED, SEG_HEAD, cache_specs,
                                decode_step, forward)
from repro.models.rglru import rglru_dims  # noqa: F401  (docs reference)


def cache_axes(cfg: ArchConfig):
    """Logical axes mirroring ``models.model.cache_specs`` structure."""
    def for_kind(kind: str):
        if kind in ATTN_KINDS:
            return {"k": CACHE_AXES, "v": CACHE_AXES}
        if kind == "rec":
            return {"h": ("batch", "rnn"), "conv": ("batch", None, "rnn")}
        if kind == "mlstm":
            return {"C": ("batch", "heads", None, None),
                    "n": ("batch", "heads", None),
                    "m": ("batch", "heads"),
                    "conv": ("batch", None, "rnn")}
        if kind == "slstm":
            return {"h": ("batch", "heads", None),
                    "c": ("batch", "heads", None),
                    "n": ("batch", "heads", None),
                    "m": ("batch", "heads", None),
                    "conv": ("batch", None, "embed")}
        raise ValueError(kind)

    axes = {}
    for gi, group in enumerate(cfg.stack_plan()):
        g = {}
        for j, kind in enumerate(group.pattern):
            ax = for_kind(kind)
            if group.repeats > 1:
                ax = jax.tree.map(
                    lambda a: ("layers",) + tuple(a), ax,
                    is_leaf=lambda x: isinstance(x, tuple))
            g[f"b{j}"] = ax
        axes[f"g{gi}"] = g
    return axes


def cache_shardings(cfg: ArchConfig, shape: ShapeConfig, mesh, plan: Plan):
    ctxs = build_contexts(cfg, mesh, plan)
    axes = cache_axes(cfg)
    specs = cache_specs(cfg, shape.global_batch, shape.seq_len)

    out = {}
    for seg, seg_axes in axes.items():
        rules = ctxs[seg].rules
        out[seg] = jax.tree.map(
            lambda a, s: (NamedSharding(mesh, rules.pspec(a, s.shape))
                          if mesh is not None else rules.pspec(a, s.shape)),
            seg_axes, specs[seg],
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))
    return out


def decode_input_specs(cfg: ArchConfig, shape: ShapeConfig):
    B = shape.global_batch
    return {"tokens": jax.ShapeDtypeStruct((B,), jnp.dtype("int32")),
            "pos": jax.ShapeDtypeStruct((), jnp.dtype("int32"))}


def make_decode_step(cfg: ArchConfig, mesh, plan: Plan):
    """Returns (serve_step, shardings). serve_step:
    (params, caches, tokens, pos) -> (next_tokens, logits, new_caches)."""
    ctxs = build_contexts(cfg, mesh, plan)

    def serve_step(params, caches, tokens, pos):
        logits, new_caches = decode_step(params, caches, tokens, pos,
                                         cfg, ctxs)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, logits, new_caches

    from repro.train.step import param_shardings
    shardings = {"params": param_shardings(cfg, mesh, plan)}
    return serve_step, shardings


def make_prefill(cfg: ArchConfig, mesh, plan: Plan):
    """Full-sequence forward (prefill compute shape). Returns logits."""
    ctxs = build_contexts(cfg, mesh, plan)

    def prefill(params, batch):
        logits, _ = forward(params, batch, cfg, ctxs)
        return logits

    from repro.train.step import param_shardings
    return prefill, {"params": param_shardings(cfg, mesh, plan)}


def make_prefill_cache(cfg: ArchConfig, mesh, plan: Plan):
    """The serving engine's prefill segment: consume a prompt into a
    decode cache.

    :func:`make_prefill` computes full-sequence prompt logits but
    produces no KV/recurrent state, so request admission scans the
    plan's decode step across the prompt positions instead — one
    program per prompt length whose last-position logits match the
    full-sequence forward's (cross-validated in tests/test_serve.py)
    and whose output caches are exactly the state a token-by-token
    decode loop would leave behind.

    Returns ``prefill(params, caches, prompt) -> (first_tokens (B,),
    last_logits (B,V) f32, new_caches)`` where ``prompt`` is (B, P)
    int32 and ``caches`` a fresh ``init_cache`` pytree.
    """
    ctxs = build_contexts(cfg, mesh, plan)

    def prefill(params, caches, prompt):
        P = prompt.shape[1]

        def body(caches, i):
            tok = jax.lax.dynamic_index_in_dim(prompt, i, axis=1,
                                               keepdims=False)
            logits, caches = decode_step(params, caches, tok, i, cfg, ctxs)
            return caches, logits

        caches, logits = jax.lax.scan(
            body, caches, jnp.arange(P, dtype=jnp.int32))
        last = logits[-1]
        nxt = jnp.argmax(last, axis=-1).astype(jnp.int32)
        return nxt, last, caches

    return prefill


def prefill_input_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict:
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.dtype("int32")
    if cfg.frontend != "none":
        return {"embeds": jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                               jnp.dtype(cfg.dtype))}
    return {"tokens": jax.ShapeDtypeStruct((B, S), i32)}
