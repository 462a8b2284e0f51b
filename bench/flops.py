"""Operations and bytes the algorithms need, from a configuration's shapes.

The benchmark's own arithmetic; it reads only the model dict
(``bench/config.py`` ``model``) and each layer kind's counts in its block
file (``bench/blocks/<kind>.py``).  Conventions:

* A weight matrix of ``n`` parameters costs ``2 n`` operations per token
  forward (one multiply-add); training costs three times the forward
  (backward twice), and nothing recomputed is counted.
* A layer's weights are those it holds; a token multiplies its active
  ones (all of them but in a layer of sparse experts).
* Sequence mixing is each kind's own count at ``keys`` positions; a
  training sequence of S tokens averages ``(S + 1) / 2`` keys per query.
* The head is counted once per token that needs logits: every training
  token, every decoded token, and the last prompt token of a prefill.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable

from bench import config as C

BENCH = Path(__file__).resolve().parent


def param_count(m: Dict) -> int:
    """Every parameter of the model, as the configuration defines it."""
    d, V = m["d_model"], m["vocab_size"]
    n = V * d + d * (2 if m["norm"] == "layernorm" else 1)
    if not m["tie_embeddings"]:
        n += d * V
    for kind in C.kinds(m):
        b = C.block(kind)
        n += b.held_params(m) + b.norm_params(m)
    return n


def weight_params(m: Dict) -> int:
    """Parameters each token multiplies (the head included, the embedding
    gather not)."""
    n = m["d_model"] * m["vocab_size"]
    for kind in C.kinds(m):
        n += C.block(kind).active_params(m)
    return n


def _mix_per_token(m: Dict, keys: float) -> float:
    """Forward sequence-mixing operations of one token attending to
    ``keys`` positions, summed over layers."""
    out = 0.0
    for kind in C.kinds(m):
        out += C.block(kind).mix_flops(m, keys)
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
