"""Train-step factory: applies a ComParX plan to a jitted training step.

The step is pure ``(params, opt_state, batch) -> (params, opt_state,
metrics)`` with per-segment sharding constraints, remat policies, kernel
selections, and gradient-accumulation microbatching all taken from the
plan.  ``in_shardings`` / ``out_shardings`` are derived from the same
rules, so the step is directly ``jax.jit``-able on any mesh.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.plan import Plan, build_contexts
from repro.models.loss import softmax_xent
from repro.models.model import SEG_EMBED, SEG_HEAD, forward, model_specs
from repro.models.params import abstract_params, param_pspecs
from repro.optim.adamw import (AdamWState, adamw_abstract_state, adamw_init,
                               adamw_update, cosine_lr)

AUX_LOSS_WEIGHT = 0.01


def param_shardings(cfg: ArchConfig, mesh, plan: Plan):
    """Per-segment PartitionSpec tree for params (NamedSharding if mesh)."""
    specs = model_specs(cfg)
    ctxs = build_contexts(cfg, mesh, plan)
    pspecs = {seg: param_pspecs(spec_tree, ctxs[seg].rules)
              for seg, spec_tree in specs.items()}
    if mesh is None:
        return pspecs
    return jax.tree.map(
        lambda ps: NamedSharding(mesh, ps), pspecs,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def opt_shardings(cfg: ArchConfig, mesh, plan: Plan) -> AdamWState:
    ps = param_shardings(cfg, mesh, plan)
    scalar = NamedSharding(mesh, PartitionSpec()) if mesh is not None \
        else PartitionSpec()
    return AdamWState(step=scalar, m=ps, v=ps)


def batch_specs(cfg: ArchConfig, shape: ShapeConfig) -> Dict[str, object]:
    """Abstract training batch (ShapeDtypeStruct stand-ins)."""
    B, S = shape.global_batch, shape.seq_len
    i32 = jnp.dtype("int32")
    out: Dict[str, object] = {"targets": jax.ShapeDtypeStruct((B, S), i32)}
    if cfg.frontend != "none":
        out["embeds"] = jax.ShapeDtypeStruct((B, S, cfg.d_model),
                                             jnp.dtype(cfg.dtype))
    else:
        out["tokens"] = jax.ShapeDtypeStruct((B, S), i32)
    return out


def batch_shardings(cfg: ArchConfig, shape: ShapeConfig, mesh, plan: Plan):
    ctxs = build_contexts(cfg, mesh, plan)
    rules = ctxs[SEG_EMBED].rules
    specs = batch_specs(cfg, shape)
    axes = {"tokens": ("batch", "seq"), "targets": ("batch", "seq"),
            "embeds": ("batch", "seq", "embed")}
    out = {}
    for k, sds in specs.items():
        ps = rules.pspec(axes[k], sds.shape)
        out[k] = NamedSharding(mesh, ps) if mesh is not None else ps
    return out


def make_train_step(cfg: ArchConfig, mesh, plan: Plan, *,
                    peak_lr: float = 3e-4, warmup: int = 100,
                    total_steps: int = 10000,
                    weight_decay: float = 0.1, clip_norm: float = 1.0):
    """Returns (train_step_fn, shardings dict)."""
    ctxs = build_contexts(cfg, mesh, plan)
    mb = plan.knobs.microbatches

    def loss_fn(params, batch):
        logits, aux = forward(params, batch, cfg, ctxs)
        loss, metrics = softmax_xent(logits, batch["targets"])
        total = loss + AUX_LOSS_WEIGHT * aux
        metrics = dict(metrics, loss=loss, aux_loss=aux)
        return total, metrics

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch)
        return loss, metrics, grads

    def train_step(params, opt_state: AdamWState, batch):
        if mb > 1:
            split = jax.tree.map(
                lambda x: x.reshape((mb, x.shape[0] // mb) + x.shape[1:]),
                batch)

            def acc_step(carry, mbatch):
                gacc, lacc = carry
                loss, metrics, grads = grads_of(params, mbatch)
                gacc = jax.tree.map(jnp.add, gacc, grads)
                return (gacc, lacc + loss), metrics

            gz = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              params)
            (gsum, lsum), metrics = jax.lax.scan(
                acc_step, (gz, jnp.zeros(())), split)
            grads = jax.tree.map(lambda g: (g / mb).astype(jnp.float32),
                                 gsum)
            metrics = jax.tree.map(lambda x: x[-1], metrics)
            loss = lsum / mb
        else:
            loss, metrics, grads = grads_of(params, batch)
        lr = cosine_lr(opt_state.step, peak_lr=peak_lr, warmup=warmup,
                       total=total_steps)
        new_params, new_state, opt_metrics = adamw_update(
            grads, opt_state, params, lr=lr,
            weight_decay=weight_decay, clip_norm=clip_norm)
        metrics = dict(metrics, **opt_metrics, total_loss=loss)
        return new_params, new_state, metrics

    shardings = {
        "params": param_shardings(cfg, mesh, plan),
        "opt": opt_shardings(cfg, mesh, plan),
    }
    return train_step, shardings


def abstract_train_state(cfg: ArchConfig, plan: Plan):
    specs = model_specs(cfg)
    params = abstract_params(specs)
    opt = adamw_abstract_state(params, plan.knobs.opt_state_dtype)
    return params, opt


def init_train_state(cfg: ArchConfig, plan: Plan, key, shardings=None):
    """Random params + fresh optimizer state.  With ``shardings`` (the
    ``{"params", "opt"}`` dict of :func:`make_train_step`) every leaf is
    built directly on its devices, never whole on one."""
    from repro.models.params import init_params

    def init(key):
        params = init_params(model_specs(cfg), key)
        return params, adamw_init(params, plan.knobs.opt_state_dtype)
    if shardings is None:
        return init(key)
    return jax.jit(init, out_shardings=(shardings["params"],
                                        shardings["opt"]))(key)


def jit_train_step(cfg: ArchConfig, mesh, plan: Plan, **kw):
    """jit the step with in/out shardings + donation per the plan knobs."""
    step, sh = make_train_step(cfg, mesh, plan, **kw)
    if mesh is None:
        return jax.jit(step, donate_argnums=(0, 1)
                       if plan.knobs.donate else ()), sh
    bs = None  # batch shardings are data-dependent; constrain inside
    jitted = jax.jit(
        step,
        in_shardings=(sh["params"], sh["opt"], bs),
        out_shardings=(sh["params"], sh["opt"], None),
        donate_argnums=(0, 1) if plan.knobs.donate else (),
    )
    return jitted, sh
