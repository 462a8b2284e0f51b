"""Block kind ``attn``: pre-norm causal attention, then a dense FFN, each
added to the residual.

Attention has H query heads over KV key/value heads of size D (grouped
when KV < H), rope on queries and keys.  At query position t (t keys) it
costs ``4 H D t`` operations: the scores and the weighted sum.  Every
token multiplies every weight matrix.
"""
import jax
import jax.numpy as jnp

from bench.reference import act, norm, norm_leaves, rope


def head_dim(m) -> int:
    return m["head_dim"] or m["d_model"] // m["num_heads"]


# --- the reference --------------------------------------------------------------

def attention_leaves(m):
    dt, d = m["dtype"], m["d_model"]
    H, KV, D = m["num_heads"], m["num_kv_heads"], head_dim(m)
    return {"wq": ((d, H, D), "normal", d ** -0.5, dt),
            "wk": ((d, KV, D), "normal", d ** -0.5, dt),
            "wv": ((d, KV, D), "normal", d ** -0.5, dt),
            "wo": ((H, D, d), "normal", (H * D) ** -0.5, dt)}


def ffn_leaves(m):
    dt, d, f = m["dtype"], m["d_model"], m["d_ff"]
    out = {"wi": ((d, f), "normal", d ** -0.5, dt),
           "wo": ((f, d), "normal", f ** -0.5, dt)}
    if m["glu"]:
        out["wg"] = ((d, f), "normal", d ** -0.5, dt)
    return out


def leaves(m):
    d = m["d_model"]
    return {"ln1": norm_leaves(m, d), "attn": attention_leaves(m),
            "ln2": norm_leaves(m, d), "ffn": ffn_leaves(m)}


def attention(a, h, pos, m, ar):
    """Causal attention of the normed input ``h`` (B,S,d) at positions
    ``pos`` (S,) -> (B,S,d)."""
    S = h.shape[1]
    q = rope(ar.mm("bsd,dhk->bshk", h, a["wq"]), pos, m["rope"])
    k = rope(ar.mm("bsd,dhk->bshk", h, a["wk"]), pos, m["rope"])
    v = ar.mm("bsd,dhk->bshk", h, a["wv"])
    B, _, H, D = q.shape
    KV = k.shape[2]
    q = q.reshape(B, S, KV, H // KV, D)
    s = ar.mm("bqkgd,bskd->bkgqs", q, k) * D ** -0.5
    s = jnp.where(pos[:, None] >= pos[None, :], s, -jnp.inf)
    o = ar.mm("bkgqs,bskd->bqkgd", jax.nn.softmax(s, axis=-1), v)
    return ar.mm("bshk,hke->bse", o.reshape(B, S, H, D), a["wo"])


def ffn(f, h, m, ar):
    """The dense FFN of the normed input ``h`` (B,S,d) -> (B,S,d)."""
    u = ar.mm("bsd,df->bsf", h, f["wi"])
    u = act(m["act"])(ar.mm("bsd,df->bsf", h, f["wg"])) * u if m["glu"] \
        else act(m["act"])(u)
    return ar.mm("bsf,fd->bsd", u, f["wo"])


def forward(p, x, m, ar):
    pos = jnp.arange(x.shape[1])
    x = x + attention(p["attn"], norm(p["ln1"], x, m["norm"]), pos, m, ar)
    return x + ffn(p["ffn"], norm(p["ln2"], x, m["norm"]), m, ar)


# --- the arithmetic --------------------------------------------------------------

def attention_params(m) -> int:
    d, H, KV, D = m["d_model"], m["num_heads"], m["num_kv_heads"], head_dim(m)
    return d * H * D + 2 * d * KV * D + H * D * d


def ffn_params(m) -> int:
    return (3 if m["glu"] else 2) * m["d_model"] * m["d_ff"]


def held_params(m) -> int:
    return attention_params(m) + ffn_params(m)


active_params = held_params


def norm_params(m) -> int:
    return 2 * m["d_model"] * (2 if m["norm"] == "layernorm" else 1)


def mix_flops(m, keys: float) -> float:
    return 4 * m["num_heads"] * head_dim(m) * keys
