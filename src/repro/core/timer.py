"""Timer: per-segment cost attribution.

ComPar's Timer wraps every enumerated loop with wall-clock probes; the
Executor then logs total + per-loop times.  ComParX builds, per segment, a
standalone jitted program (with the segment's own sharding rules applied)
and derives its cost from the compiled artifact — or from wall-clock when
a real executor runs it.  Training shapes measure forward+backward.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from typing import Optional

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.combinator import Combination, GlobalKnobs
from repro.core.plan import dp_shards
from repro.core.providers import get_provider
from repro.core.segment import Segment
from repro.models.context import ModelContext
from repro.models.loss import softmax_xent
from repro.models.model import (SEG_EMBED, cache_specs, decode_group,
                                embed_tokens, lm_head, model_specs,
                                _run_group)
from repro.models.params import abstract_params, param_pspecs
from repro.runtime.sharding import Rules


def _ctx_for(cfg, mesh, combo: Combination, seg: Segment) -> ModelContext:
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) \
        if mesh is not None else {}
    mapping = get_provider(combo.provider).mapping(
        cfg, axis_sizes, combo.flags, seg)
    return ModelContext(rules=Rules(mapping, mesh), clause=combo.clause,
                        moe_groups=dp_shards(mesh))


def segment_program(cfg: ArchConfig, shape: ShapeConfig, seg: Segment,
                    combo: Combination, mesh, *,
                    knobs: Optional[GlobalKnobs] = None
                    ) -> Tuple[Callable, Tuple, Dict]:
    """Build (fn, abstract_args, arg_shardings) for one segment.

    ``fn`` captures the segment's compute under the combination; for
    training shapes it includes the backward pass, and — when ``knobs``
    are given — the gradient-accumulation microbatch scan (the per-step
    batch is reshaped to ``(microbatches, B/microbatches, ...)`` and the
    fwd+bwd scanned over the slices, mirroring ``train.step``).  Only the
    knob fields in ``Segment.relevant_knob_fields`` reach the program;
    inference shapes ignore knobs entirely.
    """
    ctx = _ctx_for(cfg, mesh, combo, seg)
    specs = model_specs(cfg)
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.dtype("int32")
    dt = jnp.dtype(cfg.dtype)
    train = shape.kind == "train"
    decode = shape.kind == "decode"
    mb = knobs.microbatches if (train and knobs is not None) else 1

    def shard(ax, shp):
        if mesh is None:
            return None
        return NamedSharding(mesh, ctx.rules.pspec(ax, shp))

    x_shape = (B, cfg.d_model) if decode else (B, S, cfg.d_model)
    x_axes = ("batch", "embed") if decode else ("batch", "seq", "embed")
    x_sds = jax.ShapeDtypeStruct(x_shape, dt)
    x_sh = shard(x_axes, x_shape)

    if seg.kind == "embed":
        p_abs = abstract_params({SEG_EMBED: specs[SEG_EMBED]})
        p_sh = _pshard({SEG_EMBED: specs[SEG_EMBED]}, ctx.rules, mesh)
        tok_shape = (B,) if decode else (B, S)
        tok = jax.ShapeDtypeStruct(tok_shape, i32)

        def fn(p, tokens):
            return embed_tokens(p, tokens, cfg, ctx)
        if train:
            fn = _with_microbatches(_with_bwd(fn, argnums=(0,)), mb)
        return fn, (p_abs, tok), (p_sh, shard(("batch", "seq"), tok_shape))

    if seg.kind == "head":
        need = {"head": specs["head"]}
        if cfg.tie_embeddings:
            need[SEG_EMBED] = specs[SEG_EMBED]
        p_abs = abstract_params(need)
        p_sh = _pshard(need, ctx.rules, mesh)

        def fn(p, x):
            logits = lm_head(p, x, cfg, ctx)
            tgt = jnp.zeros(logits.shape[:-1], i32)
            loss, _ = softmax_xent(logits, tgt)
            return loss
        if train:
            fn = _with_microbatches(
                _with_bwd(fn, argnums=(0, 1), scalar=True), mb)
        return fn, (p_abs, x_sds), (p_sh, x_sh)

    # --- stack segment -------------------------------------------------
    gname = seg.name
    p_abs = abstract_params(specs[gname])
    p_sh = _pshard(specs[gname], ctx.rules, mesh)
    group = [g for i, g in enumerate(cfg.stack_plan())
             if f"g{i}" == gname][0]

    if decode:
        from repro.serve.step import cache_axes
        cspecs = cache_specs(cfg, B, shape.seq_len)[gname]
        caxes = cache_axes(cfg)[gname]
        c_sh = jax.tree.map(
            lambda a, s: shard(a, s.shape), caxes, cspecs,
            is_leaf=lambda t: isinstance(t, tuple) and all(
                isinstance(e, (str, type(None))) for e in t)) \
            if mesh is not None else None
        pos = jax.ShapeDtypeStruct((), i32)

        def fn(p, caches, x, pos):
            return decode_group(x, p, caches, group, cfg,
                                ctx.with_(decode=True), pos)
        return fn, (p_abs, cspecs, x_sds, pos), (p_sh, c_sh, x_sh, None)

    def fn(p, x):
        positions = jnp.arange(S, dtype=i32)
        y, aux = _run_group(x, p, group, cfg, ctx, positions)
        return y
    if train:
        fn = _with_microbatches(_with_bwd(fn, argnums=(0, 1)), mb)
    return fn, (p_abs, x_sds), (p_sh, x_sh)


def _pshard(spec_tree, rules: Rules, mesh):
    if mesh is None:
        return None
    ps = param_pspecs(spec_tree, rules)
    from jax.sharding import PartitionSpec
    return jax.tree.map(lambda s: NamedSharding(mesh, s), ps,
                        is_leaf=lambda x: isinstance(x, PartitionSpec))


def _with_microbatches(fn, mb: int):
    """Gradient-accumulation analogue for segment scoring: split the batch
    (arg 1; arg 0 is always the segment's params) into ``mb`` slices,
    scan the fwd+bwd ``fn`` over them and average the grads — the same
    program shape ``train.step`` builds, so a swept microbatch count is
    scored with the compute/memory profile it will actually run with.
    Summing (rather than stacking) the data-side grads is fine here: the
    wrapper exists to shape the compiled program for cost attribution,
    not to train."""
    if mb <= 1:
        return fn

    @functools.wraps(fn)
    def wrapped(p, x):
        if x.shape[0] % mb:
            raise ValueError(
                f"global_batch {x.shape[0]} not divisible by "
                f"microbatches={mb}")
        xs = x.reshape((mb, x.shape[0] // mb) + x.shape[1:])
        acc0 = jax.tree.map(lambda s: jnp.zeros(s.shape, s.dtype),
                            jax.eval_shape(fn, p, xs[0]))

        def step(acc, xi):
            return jax.tree.map(jnp.add, acc, fn(p, xi)), None

        acc, _ = jax.lax.scan(step, acc0, xs)
        return jax.tree.map(lambda g: g / mb, acc)
    return wrapped


def _with_bwd(fn, argnums=(0,), scalar: bool = False):
    """Wrap a segment fn so its cost includes the backward pass."""
    @functools.wraps(fn)
    def wrapped(*args):
        def scalar_loss(*a):
            out = fn(*a)
            if scalar:
                return out
            return jnp.sum(jnp.square(out.astype(jnp.float32)))
        return jax.grad(scalar_loss, argnums=argnums)(*args)
    return wrapped
