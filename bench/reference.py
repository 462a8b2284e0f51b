"""Plain float32 reference of the language models the benchmark runs.

Written from the layer equations alone: it imports nothing of the program
under test and takes nothing it made.  Weights are drawn from the seed by
the same published rule the configuration states (one normal draw per
parameter leaf, keyed by the leaf's path), so the reference and the
program start from the same numbers without sharing any array.

Every matrix product runs at ``precision="highest"`` (six bf16 passes on a
TPU), in float32, with weights upcast from the dtype they are stored in.
``quant="fp8"`` is the control: every matmul operand is rounded to
float8_e4m3fn with a per-tensor scale, the next precision below the
bfloat16 the configurations state.

This module holds what every layer kind shares: the arithmetic, norms,
rope, activations, the embedding and the head, the forward over the
layer list, the loss, training and the gap.  Each kind's leaves and
equations are its own file, ``bench/blocks/<kind>.py`` (``bench/config.py``).
"""
from __future__ import annotations

import math
import zlib
from functools import partial
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from bench import config as C

F32 = jnp.float32
FP8_MAX = 448.0                    # largest finite float8_e4m3fn
NORM_EPS = 1e-6
Z_LOSS = 1e-4
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.95, 1e-8


# --- parameter leaves: (shape, init, scale, dtype) ---------------------------

def norm_leaves(m, d):
    out = {"scale": ((d,), "ones", 1.0, m["dtype"])}
    if m["norm"] == "layernorm":
        out["bias"] = ((d,), "zeros", 1.0, m["dtype"])
    return out


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 4 and isinstance(x[1], str)


def param_leaves(m: Dict):
    """Nested dict of leaf descriptions, layers stacked per repeated group."""
    d, V, dt = m["d_model"], m["vocab_size"], m["dtype"]
    tree = {"embed": {"tok": ((V, d), "normal", 1.0, dt)}}
    for gi, (pat, reps) in enumerate(C.plan(m)):
        g = {}
        for j, kind in enumerate(pat):
            leaves = C.block(kind).leaves(m)
            if reps > 1:
                leaves = jax.tree.map(
                    lambda l: ((reps,) + l[0],) + l[1:], leaves,
                    is_leaf=_is_leaf)
            g[f"b{j}"] = leaves
        tree[f"g{gi}"] = g
    head = {"norm": norm_leaves(m, d)}
    if not m["tie_embeddings"]:
        head["out"] = ((d, V), "normal", d ** -0.5, dt)
    tree["head"] = head
    return tree


def init_params(m: Dict, key):
    """Weights from the seed's key: leaf at path ``a/b/c`` draws
    ``normal(fold_in(key, crc32("a/b/c") >> 1), shape) * scale`` in
    float32 and stores it in its dtype."""
    def one(path, leaf):
        shape, init, scale, dtype = leaf
        if init == "zeros":
            return jnp.zeros(shape, dtype)
        if init == "ones":
            return jnp.ones(shape, dtype)
        name = "/".join(str(p.key) for p in path)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()) >> 1)
        return (jax.random.normal(k, shape, F32) * scale).astype(dtype)

    return jax.tree_util.tree_map_with_path(one, param_leaves(m),
                                            is_leaf=_is_leaf)


# --- arithmetic ----------------------------------------------------------------

def _q8(x):
    """Round to float8_e4m3fn with a per-tensor scale; back in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / FP8_MAX
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


class Arith:
    """Matmuls in float32 at highest precision, or (control) on fp8
    operands."""

    def __init__(self, quant: Optional[str] = None):
        if quant not in (None, "fp8"):
            raise ValueError(quant)
        self.quant = quant

    def mm(self, eq: str, a, b):
        a, b = a.astype(F32), b.astype(F32)
        if self.quant == "fp8":
            a, b = _q8(a), _q8(b)
        return jnp.einsum(eq, a, b, precision="highest")


def norm(p, x, kind: str):
    x = x.astype(F32)
    if kind == "layernorm":
        x = x - jnp.mean(x, axis=-1, keepdims=True)
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + NORM_EPS)
    y = y * p["scale"].astype(F32)
    if kind == "layernorm":
        y = y + p["bias"].astype(F32)
    return y


def rope(x, pos, style: str):
    """x: (B,S,H,D); rotates interleaved pairs (0,1), (2,3), ... of the
    whole head by pos * 10000^(-2i/D)."""
    if style == "none":
        return x
    if style != "full":
        raise ValueError(f"no reference for rope {style!r}")
    D = x.shape[-1]
    inv = 1.0 / (10000.0 ** (jnp.arange(0, D, 2, dtype=F32) / D))
    th = pos.astype(F32)[:, None] * inv                 # (S, D/2)
    cos, sin = jnp.cos(th)[:, None, :], jnp.sin(th)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     axis=-1).reshape(x.shape)


def act(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu}[name]


def forward(params, tokens, m: Dict, ar: Arith, *, remat: bool = False):
    """tokens (B,S) int32 -> logits (B,S,V) float32."""
    x = params["embed"]["tok"][tokens].astype(F32)
    for gi, (pat, reps) in enumerate(C.plan(m)):
        def superblock(x, lp, pat=pat):
            for j, kind in enumerate(pat):
                def fn(p, x, kind=kind):
                    return C.block(kind).forward(p, x, m, ar)
                if remat:
                    fn = jax.checkpoint(fn)
                x = fn(lp[f"b{j}"], x)
            return x
        gp = params[f"g{gi}"]
        if reps == 1:
            x = superblock(x, gp)
        else:
            x, _ = jax.lax.scan(lambda x, lp: (superblock(x, lp), None),
                                x, gp)
    x = norm(params["head"]["norm"], x, m["norm"])
    w = params["embed"]["tok"].T if m["tie_embeddings"] \
        else params["head"]["out"]
    return ar.mm("bsd,dv->bsv", x, w)


def xent(logits, targets):
    """Mean over tokens of (lse - logit[target]) + 1e-4 lse^2."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - ll + Z_LOSS * lse * lse)


# --- training ------------------------------------------------------------------

def lr_at(step: int, h: Dict) -> float:
    """Linear warm-up to ``peak_lr`` then cosine to 10% over ``total``;
    ``step`` counts updates already made."""
    peak, warm, total = h["peak_lr"], h["warmup"], h["total_steps"]
    if step < warm:
        return peak * min(1.0, step / max(warm, 1))
    t = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return peak * (0.1 + 0.9 * 0.5 * (1 + math.cos(math.pi * t)))


def make_grad_fn(m: Dict, ar: Arith, rows: int):
    """(params, tokens (B,S), targets (B,S)) -> (loss, grads in float32),
    accumulated over blocks of ``rows`` rows so the float32 activations
    fit beside the weights.  The float32 copy of the weights lives only
    inside each block's program, and the sum is kept in place."""
    def block_loss(p32, tok, tgt):
        return xent(forward(p32, tok, m, ar, remat=True), tgt)

    @partial(jax.jit, donate_argnums=(1,))
    def add_block(params, acc, tok, tgt, w):
        p32 = jax.tree.map(lambda x: x.astype(F32), params)
        lo, g = jax.value_and_grad(block_loss)(p32, tok, tgt)
        return lo, jax.tree.map(lambda a, b: a + w * b, acc, g)

    def grad_fn(params, tokens, targets):
        B = tokens.shape[0]
        if B % rows:
            raise ValueError(f"batch {B} is not a multiple of {rows} rows")
        acc = jax.tree.map(lambda x: jnp.zeros(x.shape, F32), params)
        loss = 0.0
        for r in range(0, B, rows):
            lo, acc = add_block(params, acc, tokens[r:r + rows],
                                targets[r:r + rows], rows / B)
            loss += float(lo) * rows / B
        return loss, acc
    return grad_fn


@partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def _adam(params, grads, mom, vel, step, lr, wd, clip):
    """One AdamW step; also the clipped gradient's squared norm per leaf
    and the global norm before clipping."""
    gn = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / jnp.maximum(gn, 1e-9))
    grads = jax.tree.map(lambda g: g * scale, grads)
    bc1, bc2 = 1 - ADAM_B1 ** step, 1 - ADAM_B2 ** step
    mom = jax.tree.map(lambda a, g: ADAM_B1 * a + (1 - ADAM_B1) * g,
                       mom, grads)
    vel = jax.tree.map(lambda a, g: ADAM_B2 * a + (1 - ADAM_B2) * g * g,
                       vel, grads)

    def upd(p, a, b):
        pf = p.astype(F32)
        delta = (a / bc1) / (jnp.sqrt(b / bc2) + ADAM_EPS) + wd * pf
        return (pf - lr * delta).astype(p.dtype)
    sq = jax.tree.map(lambda g: jnp.sum(g * g), grads)
    return jax.tree.map(upd, params, mom, vel), mom, vel, sq, gn


@jax.jit
def _sq_change(a, b):
    return jax.tree.map(
        lambda x, y: jnp.sum(jnp.square(x.astype(F32) - y.astype(F32))), a, b)


def train_steps(m: Dict, key, batches, hyper: Dict, *,
                quant: Optional[str] = None, rows: int = 1):
    """AdamW steps, one per batch, from the weights of the seed's key.

    ``batches``: list of (tokens, targets).  Returns the losses, the
    per-leaf norms of the clipped first gradient and of the parameters'
    change after the last step, and the global norm of the first
    gradient before clipping.  Parameters are kept in the dtype the
    configuration states between steps, as the program keeps them.
    """
    grad_fn = make_grad_fn(m, Arith(quant), rows)
    params = jax.jit(lambda k: init_params(m, k))(key)
    p0 = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(params)
    mom = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    vel = jax.tree.map(lambda p: jnp.zeros(p.shape, F32), params)
    losses, first, norm0 = [], None, None
    for i, (tok, tgt) in enumerate(batches):
        loss, g = grad_fn(params, tok, tgt)
        losses.append(loss)
        params, mom, vel, sq, gn = _adam(
            params, g, mom, vel, float(i + 1), lr_at(i, hyper),
            hyper["weight_decay"], hyper["clip_norm"])
        if first is None:
            first, norm0 = _sqrt_leaves(sq), float(gn)
    del mom, vel
    change = _sqrt_leaves(_sq_change(params, p0))
    return losses, first, change, norm0


def _sqrt_leaves(tree) -> Dict[str, float]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(p.key) for p in path): math.sqrt(float(x))
            for path, x in flat}


# --- serving -------------------------------------------------------------------

def make_gap_fn(m: Dict, quant: Optional[str] = None):
    """(params, tokens (1,L), next (1,L)) -> (gap, top) per position: how
    far the float32 reference's logit of ``next`` lies below its best,
    and, with ``quant``, the gap of the token that the lower precision
    ranks first instead of ``next``."""
    ref, low = Arith(None), Arith(quant) if quant else None

    @jax.jit
    def gaps(params, tokens, nxt):
        lg = forward(params, tokens, m, ref)
        best = jnp.max(lg, axis=-1)
        if low is not None:
            nxt = jnp.argmax(forward(params, tokens, m, low), axis=-1)
        got = jnp.take_along_axis(lg, nxt[..., None], axis=-1)[..., 0]
        return best - got
    return gaps
