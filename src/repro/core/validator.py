"""Black-box validation (paper §4.1).

ComPar optionally runs a user testing script on every combination's output
and rejects combinations that fail.  ComParX's analogue: run the candidate
plan's step on a reduced config with real numerics (CPU) and compare
logits/loss against the reference plan (single-device, XLA kernels, no
remat).  Sharding choices must be numerics-preserving; kernel/remat
clauses must stay within tolerance.
"""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ArchConfig
from repro.core.combinator import Combination
from repro.core.plan import Plan, build_contexts, uniform_plan
from repro.models.context import SegmentClause
from repro.models.model import forward, model_specs
from repro.models.params import init_params


def _tiny_batch(cfg: ArchConfig, batch: int = 2, seq: int = 16, seed: int = 0):
    ks = jax.random.split(jax.random.key(seed), 3)
    out = {"targets": jax.random.randint(ks[0], (batch, seq), 0,
                                         cfg.vocab_size)}
    if cfg.frontend != "none":
        out["embeds"] = (jax.random.normal(
            ks[1], (batch, seq, cfg.d_model), jnp.float32) * 0.02
        ).astype(cfg.dtype)
    else:
        out["tokens"] = jax.random.randint(ks[2], (batch, seq), 0,
                                           cfg.vocab_size)
    return out


def validate_plan(cfg: ArchConfig, plan: Plan, *,
                  reference: Optional[Plan] = None,
                  atol: float = 5e-2, rtol: float = 5e-2,
                  seed: int = 0) -> Tuple[bool, str]:
    """Black-box test: candidate-vs-reference forward on a reduced config.

    Returns (passed, message).  Runs on the reduced (smoke) config so it is
    executable on this CPU container regardless of the target scale.
    """
    small = cfg if cfg.name.endswith("-smoke") else cfg.smoke()
    reference = reference or uniform_plan(
        small, "fsdp", clause=SegmentClause(remat="none", kernel="xla"))
    params = init_params(model_specs(small), jax.random.key(seed))
    batch = _tiny_batch(small, seed=seed)

    def run(p):
        # a numerics check at reduced widths: kernels interpreted on any
        # backend, so it never depends on the chip's block-tiling rules
        ctxs = {k: c.with_(interpret=True) for k, c in
                build_contexts(small, None, p).items()}
        logits, aux = forward(params, batch, small, ctxs)
        return np.asarray(logits, np.float32)

    try:
        cand = run(plan)
    except Exception as e:
        return False, f"candidate failed to execute: {type(e).__name__}: {e}"
    ref = run(reference)
    if np.any(np.isnan(cand)):
        return False, "candidate produced NaNs"
    err = float(np.max(np.abs(cand - ref)))
    scale = float(np.max(np.abs(ref)) + 1e-9)
    if err > atol + rtol * scale:
        return False, f"output mismatch: max_abs_err={err:.4g} scale={scale:.4g}"
    return True, f"ok (max_abs_err={err:.4g})"


def validate_combination(cfg: ArchConfig, combo: Combination,
                         **kw) -> Tuple[bool, str]:
    """Validate one combination applied uniformly (cheapest black-box)."""
    small = cfg if cfg.name.endswith("-smoke") else cfg.smoke()
    plan = uniform_plan(small, combo.provider, combo.flags, combo.clause)
    return validate_plan(small, plan, **kw)
