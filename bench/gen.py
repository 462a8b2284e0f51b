"""The one traffic generator: reads a mix's parameters, makes its inputs.

A mix is a data file ``bench/traffic/<name>.json`` with ``"kind"``:

``train``  token batches of ``batch`` rows of ``seq`` tokens (plus the
           shifted targets) from the program's own synthetic feed, with
           one theme row per batch, made on the device in one jitted
           call.
``serve``  offline waves of requests: prompt and answer lengths are the
           quantiles of clipped lognormals of the published means,
           prompts snapped to a geometric grid; every wave of every seed
           sends the same sizes (so the seed does not change the work),
           and the seed draws their order, their pairing and the token
           ids.

Every input is a pure function of (mix, configuration, seed), and seeds may
be any whole number (above 32 bits too).
"""
from __future__ import annotations

import json
import math
from pathlib import Path
from statistics import NormalDist
from typing import Dict, List, Tuple

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load_mix(name: str, root: Path = TRAFFIC_DIR) -> Dict:
    with open(root / f"{name}.json") as f:
        return json.load(f)


def _seed_words(seed: int, *more: int) -> List[int]:
    """A seed of any size as 32-bit words, for numpy's SeedSequence."""
    s = int(seed)
    words = [s & 0xFFFFFFFF, (s >> 32) & 0xFFFFFFFF, int(s < 0)]
    return words + [int(m) for m in more]


def jax_key(seed: int, stream: int = 0):
    """A JAX key from a seed of any size (folds in both 32-bit halves)."""
    import jax
    lo, hi, neg = _seed_words(seed)
    k = jax.random.key(lo)
    return jax.random.fold_in(jax.random.fold_in(
        jax.random.fold_in(k, hi), neg), stream)


# --- training --------------------------------------------------------------

def data_seed(seed: int) -> int:
    """A 31-bit seed for the program's data pipeline from a seed of any
    size (both 32-bit halves and the sign mixed in)."""
    lo, hi, neg = _seed_words(seed)
    return (lo ^ (hi * 0x9E3779B1) ^ (neg * 0x85EBCA6B)) & 0x7FFFFFFF


def train_pool(mix: Dict, cfg, seed: int):
    """``mix["pool"]`` batches on the device, (tokens, targets), each
    (pool, batch, seq) int32, made in one jitted call.

    Batch ``i`` is step ``i`` of the program's own ``SyntheticLM`` feed
    (motifs and ramps) with row ``mix["theme_row"]`` replaced by a theme:
    one token drawn from the seed, repeated, after a first token that
    differs from batch to batch.  Every position of a theme row sees the
    same context, so its gradient adds up coherently and a step that
    trains on it lowers its loss at the next step by a wide margin; the
    check steps' losses therefore move by much more than rounding when a
    step leaves rows out."""
    import jax
    import jax.numpy as jnp

    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import SyntheticLM

    n, B, S, V = mix["pool"], mix["batch"], mix["seq"], cfg.vocab_size
    data = SyntheticLM(cfg, ShapeConfig("bench", S, B, "train"),
                       seed=data_seed(seed))

    @jax.jit
    def make(key):
        bs = [data.batch_at(i) for i in range(n)]
        toks = jnp.stack([b["tokens"] for b in bs])
        tgts = jnp.stack([b["targets"] for b in bs])
        t = jax.random.randint(key, (), 0, V)
        row = jnp.full((n, S + 1), t, jnp.int32)
        row = row.at[:, 0].set((t + 1 + jnp.arange(n)) % V)
        r = mix["theme_row"]
        return (toks.at[:, r].set(row[:, :-1]),
                tgts.at[:, r].set(row[:, 1:]))
    return make(jax_key(seed, 1))


# --- serving ---------------------------------------------------------------

def _lognormal_quantiles(spec: Dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a lognormal of the given mean and log
    standard deviation, rounded and clipped."""
    median = spec["mean"] * math.exp(-spec["sigma"] ** 2 / 2)
    z = [NormalDist().inv_cdf((i + 0.5) / n) for i in range(n)]
    x = median * np.exp(spec["sigma"] * np.asarray(z))
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def grid(spec: Dict) -> np.ndarray:
    """``spec["grid"]`` lengths spaced geometrically from min to max."""
    g = np.geomspace(spec["min"], spec["max"], spec["grid"])
    return np.unique(np.rint(g).astype(np.int64))


def _snap(x: np.ndarray, g: np.ndarray) -> np.ndarray:
    d = np.abs(np.log(x[:, None]) - np.log(g[None, :]))
    return g[np.argmin(d, axis=1)]


def wave_sizes(mix: Dict) -> Tuple[np.ndarray, np.ndarray]:
    """(prompt lengths, answer lengths) every wave sends, sorted: the
    quantiles of the mix's distributions, prompts snapped to the grid."""
    n = mix["wave"]
    return (_snap(_lognormal_quantiles(mix["prompt"], n),
                  grid(mix["prompt"])),
            _lognormal_quantiles(mix["answer"], n))


def prompt_lengths(mix: Dict) -> List[int]:
    """The distinct prompt lengths the mix sends: the programs to warm."""
    return sorted(set(int(p) for p in wave_sizes(mix)[0]))


def wave(mix: Dict, vocab: int, seed: int, w: int):
    """Requests of wave ``w``: [(rid, prompt tuple, max_new_tokens)].

    Every wave of every seed sends the same sizes; the seed and the wave
    draw their order and which answer goes with which prompt, and the
    prompts' tokens."""
    prompts, answers = wave_sizes(mix)
    rng = np.random.default_rng(_seed_words(seed, w))
    prompts, answers = rng.permutation(prompts), rng.permutation(answers)
    return [(f"w{w}r{i}",
             tuple(int(t) for t in rng.integers(0, vocab, int(p))), int(a))
            for i, (p, a) in enumerate(zip(prompts, answers))]
