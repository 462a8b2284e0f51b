"""Block-kind dispatcher: specs / apply / decode / cache-spec per kind.

Kinds: ``attn`` (attention + dense FFN), ``attn_moe`` (attention + MoE FFN),
``rec`` (RG-LRU + FFN), ``mlstm``, ``slstm``.  Inside a block the
attention half runs under ``jax.named_scope("attn")`` and the FFN half
under ``"mlp"`` (compiled-program metadata only).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import attention as A
from repro.models import mlp as M
from repro.models import moe as MOE
from repro.models import rglru as R
from repro.models import xlstm as X
from repro.models.context import ModelContext
from repro.models.layers import norm_apply, norm_specs

BLOCK_KINDS = ("attn", "attn_moe", "rec", "mlstm", "slstm")


def block_specs(kind: str, cfg: ArchConfig):
    dt = cfg.dtype
    d = cfg.d_model
    if kind in ("attn", "attn_moe"):
        s = {"ln1": norm_specs(d, cfg.norm, dt),
             "attn": A.attn_specs(cfg),
             "ln2": norm_specs(d, cfg.norm, dt)}
        s["ffn"] = MOE.moe_specs(cfg) if kind == "attn_moe" \
            else M.mlp_specs(cfg)
        return s
    if kind == "rec":
        return {"rec": R.rec_specs(cfg),
                "ln2": norm_specs(d, cfg.norm, dt),
                "ffn": M.mlp_specs(cfg)}
    if kind == "mlstm":
        return X.mlstm_specs(cfg)
    if kind == "slstm":
        return X.slstm_specs(cfg)
    raise ValueError(kind)


def block_apply(kind: str, p, x, cfg: ArchConfig, ctx: ModelContext,
                positions):
    """Full-sequence forward. Returns (x, aux_loss)."""
    aux = jnp.zeros((), jnp.float32)
    if kind in ("attn", "attn_moe"):
        with jax.named_scope("attn"):
            h = norm_apply(p["ln1"], x, cfg.norm)
            x = x + A.attn_apply(p["attn"], h, cfg, ctx, positions)
        with jax.named_scope("mlp"):
            h = norm_apply(p["ln2"], x, cfg.norm)
            if kind == "attn_moe":
                y, aux = MOE.moe_apply(p["ffn"], h, cfg, ctx)
            else:
                y = M.mlp_apply(p["ffn"], h, cfg, ctx)
            x = x + y
        return x, aux
    if kind == "rec":
        x = R.rec_apply(p["rec"], x, cfg, ctx)
        with jax.named_scope("mlp"):
            h = norm_apply(p["ln2"], x, cfg.norm)
            x = x + M.mlp_apply(p["ffn"], h, cfg, ctx)
        return x, aux
    if kind == "mlstm":
        return X.mlstm_apply(p, x, cfg, ctx), aux
    if kind == "slstm":
        return X.slstm_apply(p, x, cfg, ctx), aux
    raise ValueError(kind)


def block_cache_spec(kind: str, cfg: ArchConfig, batch: int, smax: int):
    """Abstract per-layer decode cache/state."""
    if kind in ("attn", "attn_moe"):
        return A.attn_cache_spec(cfg, batch, smax)
    if kind == "rec":
        return R.rec_state_spec(cfg, batch)
    if kind == "mlstm":
        return X.mlstm_state_spec(cfg, batch)
    if kind == "slstm":
        return X.slstm_state_spec(cfg, batch)
    raise ValueError(kind)


def block_decode(kind: str, p, x1, cache, pos, cfg: ArchConfig,
                 ctx: ModelContext):
    """One-token decode. x1: (B,d). Returns (x1, new_cache)."""
    if kind in ("attn", "attn_moe"):
        with jax.named_scope("attn"):
            h = norm_apply(p["ln1"], x1[:, None], cfg.norm)[:, 0]
            y, new_cache = A.attn_decode(p["attn"], h, cache, pos, cfg, ctx)
            x1 = x1 + y
        with jax.named_scope("mlp"):
            h = norm_apply(p["ln2"], x1[:, None], cfg.norm)
            if kind == "attn_moe":
                y, _ = MOE.moe_apply(p["ffn"], h, cfg, ctx)
            else:
                y = M.mlp_apply(p["ffn"], h, cfg, ctx)
            return x1 + y[:, 0], new_cache
    if kind == "rec":
        x1, new_cache = R.rec_decode(p["rec"], x1, cache, cfg, ctx)
        with jax.named_scope("mlp"):
            h = norm_apply(p["ln2"], x1[:, None], cfg.norm)
            y = M.mlp_apply(p["ffn"], h, cfg, ctx)
            return x1 + y[:, 0], new_cache
    if kind == "mlstm":
        return X.mlstm_decode(p, x1, cache, cfg, ctx)
    if kind == "slstm":
        return X.slstm_decode(p, x1, cache, cfg, ctx)
    raise ValueError(kind)
