"""Operations and bytes the algorithms need, from a configuration's shapes.

The benchmark's own arithmetic; it reads only the configuration file's
``model`` dict.  Conventions:

* A weight matrix of ``n`` parameters costs ``2 n`` operations per token
  forward (one multiply-add); training costs three times the forward
  (backward twice), and nothing recomputed is counted.
* Causal attention at query position t (t keys) costs ``4 H D t``
  (scores and the weighted sum).  A training sequence of S tokens
  averages ``(S + 1) / 2`` keys per query.
* The head is counted once per token that needs logits: every training
  token, every decoded token, and the last prompt token of a prefill.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

BENCH = Path(__file__).resolve().parent


def _kinds(m: Dict):
    pat = list(m["block_pattern"])
    reps, rem = divmod(m["num_layers"], len(pat))
    return pat * reps + pat[:rem]


def _hd(m: Dict) -> int:
    return m["head_dim"] or m["d_model"] // m["num_heads"]


def block_weights(kind: str, m: Dict) -> Dict[str, int]:
    """{leaf: parameters} of one block's weight matrices, norms and
    biases excluded."""
    d, H = m["d_model"], m["num_heads"]
    if kind == "attn":
        KV, D, f = m["num_kv_heads"], _hd(m), m["d_ff"]
        w = {"wq": d * H * D, "wk": d * KV * D, "wv": d * KV * D,
             "wo": H * D * d, "wi": d * f, "wo_ffn": f * d}
        if m["glu"]:
            w["wg"] = d * f
        return w
    raise ValueError(f"no arithmetic for block kind {kind!r}")


def block_other(kind: str, m: Dict) -> int:
    """Parameters of a block's norms and biases."""
    nrm = m["d_model"] * (2 if m["norm"] == "layernorm" else 1)
    if kind == "attn":
        return 2 * nrm
    raise ValueError(kind)


def param_count(m: Dict) -> int:
    """Every parameter of the model, as the configuration defines it."""
    d, V = m["d_model"], m["vocab_size"]
    n = V * d + d * (2 if m["norm"] == "layernorm" else 1)
    if not m["tie_embeddings"]:
        n += d * V
    for kind in _kinds(m):
        n += sum(block_weights(kind, m).values())
        n += block_other(kind, m)
    return n


def weight_params(m: Dict) -> int:
    """Parameters that take part in a matmul for every token (the head
    included, the embedding gather not)."""
    n = m["d_model"] * m["vocab_size"]
    for kind in _kinds(m):
        n += sum(block_weights(kind, m).values())
    return n


def _mix_per_token(m: Dict, keys: float) -> float:
    """Forward sequence-mixing operations of one token attending to
    ``keys`` positions, summed over layers."""
    H = m["num_heads"]
    out = 0.0
    for kind in _kinds(m):
        if kind == "attn":
            out += 4 * H * _hd(m) * keys
    return out


def train_flops_per_token(m: Dict, seq: int) -> float:
    """Forward and backward operations per trained token."""
    return 3 * (2 * weight_params(m) + _mix_per_token(m, (seq + 1) / 2))


def prefill_flops(m: Dict, prompt: int) -> float:
    """Operations to consume a prompt and give the first token's logits."""
    body = 2 * (weight_params(m) - m["d_model"] * m["vocab_size"])
    mix = sum(_mix_per_token(m, t) for t in range(1, prompt + 1))
    return body * prompt + mix + 2 * m["d_model"] * m["vocab_size"]


def decode_flops(m: Dict, contexts: Iterable[int]) -> float:
    """Operations of one decode step over active rows whose new token
    attends to ``c`` positions each (c = filled positions + 1)."""
    return sum(2 * weight_params(m) + _mix_per_token(m, c)
               for c in contexts)


def peaks(device_kind: str) -> Dict:
    """The published peaks of one chip of ``device_kind``; an unknown kind
    is an error, never a default."""
    with open(BENCH / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]
