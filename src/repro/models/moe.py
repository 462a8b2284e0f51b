"""Mixture-of-Experts layer: top-k router + grouped, sort-based dispatch.

Dispatch strategy (GShard-style groups, sort-based within a group):

1. tokens are partitioned into ``G`` groups (G = number of data shards, so
   each group's dispatch is shard-local work);
2. within a group, (token, expert) assignments are sorted by expert id and
   written into a per-expert capacity buffer ``(G, E, C, d)`` — no
   ``(T, E, C)`` one-hot tensor is ever materialized;
3. expert FFNs run as one batched einsum over the buffer (E shardable on
   the ``model`` axis = expert parallelism);
4. results are gathered back and combined with router weights.

Tokens beyond capacity ``C = cf * S_group * k / E`` are dropped (standard
capacity-factor semantics); the residual connection keeps them intact.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.context import ModelContext
from repro.models.layers import act_fn, dense
from repro.models.params import ParamSpec


def moe_specs(cfg: ArchConfig, dtype=None):
    dt = dtype or cfg.dtype
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    s = {
        "router": ParamSpec((d, E), ("embed", "experts"), "normal",
                            d ** -0.5, "float32"),
        "wi": ParamSpec((E, d, f), ("experts", "embed", "expert_ffn"),
                        "normal", d ** -0.5, dt),
        "wo": ParamSpec((E, f, d), ("experts", "expert_ffn", "embed"),
                        "normal", f ** -0.5, dt),
    }
    if cfg.glu:
        s["wg"] = ParamSpec((E, d, f), ("experts", "embed", "expert_ffn"),
                            "normal", d ** -0.5, dt)
    return s


def capacity(cfg: ArchConfig, tokens_per_group: int) -> int:
    c = int(cfg.moe_capacity_factor * tokens_per_group
            * cfg.experts_per_token / cfg.num_experts)
    return max(8, min(c, tokens_per_group))


def _dispatch_group(xg, gates, idx, E: int, C: int):
    """One group's dispatch. xg: (S,d), gates/idx: (S,k).

    Returns (buffer (E, C+1, d), combine info). Slot C is the overflow bin.
    """
    S, d = xg.shape
    k = idx.shape[-1]
    flat_e = idx.reshape(-1)                          # (S*k,)
    order = jnp.argsort(flat_e, stable=True)          # sort by expert
    e_sorted = flat_e[order]
    tok_sorted = order // k
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    offsets = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                               jnp.cumsum(counts)[:-1]])
    pos = jnp.arange(S * k, dtype=jnp.int32) - offsets[e_sorted]
    slot = jnp.where(pos < C, pos, C)                 # overflow -> bin C
    buf = jnp.zeros((E, C + 1, d), xg.dtype)
    buf = buf.at[e_sorted, slot].set(xg[tok_sorted], mode="drop")
    return buf, (e_sorted, slot, tok_sorted, order)


def _combine_group(out_buf, info, gates, S: int):
    """out_buf: (E, C+1, d) -> (S, d) weighted combine."""
    e_sorted, slot, tok_sorted, order = info
    k = gates.shape[-1]
    y = out_buf[e_sorted, slot]                       # (S*k, d)
    w_sorted = gates.reshape(-1)[order]
    keep = (slot < out_buf.shape[1] - 1).astype(y.dtype)
    y = y * (w_sorted * keep)[:, None]
    return jnp.zeros((S, out_buf.shape[-1]), y.dtype).at[tok_sorted].add(y)


def moe_apply(p, x, cfg: ArchConfig, ctx: ModelContext):
    """x: (B, S, d) -> (B, S, d). Dispatch strategy from the clause."""
    if ctx.clause.moe_dispatch == "a2a" and ctx.rules.mesh is not None \
            and "model" in ctx.rules.axis_sizes \
            and cfg.num_experts % ctx.rules.axis_sizes["model"] == 0:
        return moe_apply_a2a(p, x, cfg, ctx)
    return moe_apply_sorted(p, x, cfg, ctx)


def moe_apply_sorted(p, x, cfg: ArchConfig, ctx: ModelContext):
    """x: (B, S, d) -> (B, S, d)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    T = B * S
    G = min(ctx.moe_groups, T)
    while T % G:
        G -= 1
    Sg = T // G
    C = capacity(cfg, Sg)

    xf = x.reshape(G, Sg, d)
    xf = ctx.constrain(xf, ("batch", None, "embed"))
    logits = dense(xf, p["router"]).astype(jnp.float32)     # (G,Sg,E)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, k)                    # (G,Sg,k)
    gates = gates / jnp.clip(jnp.sum(gates, -1, keepdims=True), 1e-9)

    # aux load-balancing loss (Switch-style), returned via ctx side channel
    me = jnp.mean(probs, axis=(0, 1))                       # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32), axis=2),
        axis=(0, 1)) / k
    aux = E * jnp.sum(me * ce)

    buf, info = jax.vmap(lambda xg, g, i: _dispatch_group(xg, g, i, E, C))(
        xf, gates, idx)
    buf = ctx.constrain(buf, ("batch", "experts", None, "embed"))

    act = act_fn(cfg.act)
    h = jnp.einsum("gecd,edf->gecf", buf, p["wi"],
                   preferred_element_type=jnp.float32).astype(x.dtype)
    if cfg.glu:
        g = jnp.einsum("gecd,edf->gecf", buf, p["wg"],
                       preferred_element_type=jnp.float32).astype(x.dtype)
        h = act(g) * h
    else:
        h = act(h)
    h = ctx.constrain(h, ("batch", "experts", None, "expert_ffn"))
    out_buf = jnp.einsum("gecf,efd->gecd", h, p["wo"],
                         preferred_element_type=jnp.float32).astype(x.dtype)
    out_buf = ctx.constrain(out_buf, ("batch", "experts", None, "embed"))

    y = jax.vmap(lambda ob, inf, g: _combine_group(ob, inf, g, Sg))(
        out_buf, info, gates)
    y = y.reshape(B, S, d).astype(x.dtype)
    y = ctx.constrain(y, ("batch", "seq", "embed"))
    return y, aux


# ---------------------------------------------------------------------------
# Beyond-paper dispatch (EXPERIMENTS §Perf): shard_map expert parallelism.
#
# The sorted/einsum dispatch above leaves the token->expert routing to the
# SPMD partitioner, which materializes cross-shard gathers (collective-
# bound at 128-384 experts).  Here the routing is explicit: tokens are
# data-sharded and replicated over the model axis; each model shard owns
# E_local = E / tp experts, locally dispatches only the tokens routed to
# *its* experts (zero communication — tokens are already present), and the
# partial outputs are combined with a single psum over the model axis per
# layer.  Collective cost drops from O(buffer gathers) to one (T_local, d)
# all-reduce.
# ---------------------------------------------------------------------------

def moe_apply_a2a(p, x, cfg: ArchConfig, ctx: ModelContext):
    from functools import partial
    from jax.sharding import PartitionSpec as P

    mesh = ctx.rules.mesh
    axis_sizes = ctx.rules.axis_sizes
    tp = axis_sizes["model"]
    E, k = cfg.num_experts, cfg.experts_per_token
    E_local = E // tp
    B, S, d = x.shape
    batch_axes = tuple(a for a in ("pod", "data") if a in axis_sizes)
    dp = 1
    for a in batch_axes:
        dp *= axis_sizes[a]
    # local token count per (pod,data) shard; replicated over model
    T_local = (B * S) // dp if B % dp == 0 or (B * S) % dp == 0 else B * S
    C = capacity(cfg, T_local)

    x_spec = P(batch_axes if B % dp == 0 else None, None, None)
    w_spec_i = P("model", None, None)      # (E, d, f) sharded on experts
    r_spec = P(None, None)                 # router replicated
    out_spec = x_spec

    def local_moe(xl, router, wi, wg, wo):
        # xl: (B_l, S, d); wi/wg/wo: (E_local, ...)
        Bl, Sl, dl = xl.shape
        T = Bl * Sl
        xf = xl.reshape(T, dl)
        logits = (xf.astype(jnp.float32) @ router).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        gates, idx = jax.lax.top_k(probs, k)               # (T, k) global ids
        gates = gates / jnp.clip(jnp.sum(gates, -1, keepdims=True), 1e-9)
        rank = jax.lax.axis_index("model")
        lo = rank * E_local
        mine = (idx >= lo) & (idx < lo + E_local)          # (T, k)
        local_idx = jnp.where(mine, idx - lo, E_local)     # E_local = trash
        Cl = capacity(cfg, T)
        buf, info = _dispatch_group(xf, gates * mine, local_idx,
                                    E_local + 1, Cl)
        buf = buf[:E_local]                                # drop trash row
        act = act_fn(cfg.act)
        h = jnp.einsum("ecd,edf->ecf", buf, wi,
                       preferred_element_type=jnp.float32).astype(xl.dtype)
        if wg is not None:
            g = jnp.einsum("ecd,edf->ecf", buf, wg,
                           preferred_element_type=jnp.float32
                           ).astype(xl.dtype)
            h = act(g) * h
        else:
            h = act(h)
        ob = jnp.einsum("ecf,efd->ecd", h, wo,
                        preferred_element_type=jnp.float32).astype(xl.dtype)
        # pad the trash expert row back for combine indexing
        ob = jnp.concatenate(
            [ob, jnp.zeros((1,) + ob.shape[1:], ob.dtype)], axis=0)
        y = _combine_group(ob, info, gates * mine, T)
        # combine in the activation dtype: psum'ing bf16 partials halves
        # the per-layer collective bytes (EXPERIMENTS §Perf cell B)
        y = jax.lax.psum(y.astype(xl.dtype), "model")
        return y.reshape(Bl, Sl, dl)

    wg = p.get("wg")
    router = p["router"].astype(jnp.float32)
    fn = jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(x_spec, r_spec, w_spec_i, w_spec_i if wg is not None
                  else P(), w_spec_i),
        out_specs=out_spec,
        check_vma=False)
    if wg is None:
        fn_out = jax.shard_map(
            lambda xl, r, wi, wo: local_moe(xl, r, wi, None, wo),
            mesh=mesh, in_specs=(x_spec, r_spec, w_spec_i, w_spec_i),
            out_specs=out_spec, check_vma=False)
        y = fn_out(x, router, p["wi"], p["wo"])
    else:
        y = fn(x, router, p["wi"], wg, p["wo"])
    # aux loss: recompute cheaply outside (replicated router math)
    xf = x.reshape(-1, d)
    probs = jax.nn.softmax(
        (xf.astype(jnp.float32) @ router), axis=-1)
    gates, idx = jax.lax.top_k(probs, k)
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(idx, E, dtype=jnp.float32),
                          axis=1), axis=0) / k
    aux = E * jnp.sum(me * ce)
    return y, aux
