"""Backend facts decided in one place: Pallas interpret mode and the
persistent compile cache."""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

#: fixed in-checkout cache directory (the path is part of the cache key,
#: so it must not move between runs); listed in .gitignore
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """Whether a Pallas kernel runs in interpret mode.

    ``None`` decides from the backend: compiled on a TPU, interpreted
    wherever no TPU backend is present (CPU test runs).  An
    explicit bool wins, which is how compile-only tests build the real
    kernel for a described chip from a CPU process.
    """
    if interpret is None:
        return jax.default_backend() != "tpu"
    return bool(interpret)


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    A set ``JAX_COMPILATION_CACHE_DIR`` stands as JAX read it; otherwise
    the cache lives at the fixed ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
