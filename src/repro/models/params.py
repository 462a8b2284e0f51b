"""Parameter-spec system.

A model is declared as a pytree of :class:`ParamSpec` leaves (single source
of truth for shape, dtype, init and *logical* sharding axes).  From the spec
tree we derive:

* concrete initialized parameters      (``init_params``)
* abstract ``ShapeDtypeStruct`` params (``abstract_params`` — dry-run)
* ``PartitionSpec`` trees              (``param_pspecs`` — given provider rules)

Logical axis names used across the codebase:
``vocab, embed, heads, kv_heads, head_dim, ffn, experts, expert_ffn, rnn,
conv, layers`` (``layers`` is the scan-stack dim and is never sharded).
"""
from __future__ import annotations

import dataclasses
import zlib
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec


@dataclass(frozen=True)
class ParamSpec:
    shape: Tuple[int, ...]
    logical_axes: Tuple[Optional[str], ...]
    init: str = "normal"          # normal | zeros | ones
    scale: float = 1.0            # stddev for normal init
    dtype: str = "bfloat16"

    def __post_init__(self):
        assert len(self.shape) == len(self.logical_axes), (
            self.shape, self.logical_axes)

    @property
    def jdtype(self):
        return jnp.dtype(self.dtype)

    def stacked(self, n: int) -> "ParamSpec":
        """Add a leading scan ("layers") dim of size n."""
        return dataclasses.replace(
            self, shape=(n,) + tuple(self.shape),
            logical_axes=("layers",) + tuple(self.logical_axes))


def is_spec(x) -> bool:
    return isinstance(x, ParamSpec)


def _leaf_key(root_key, path) -> jax.Array:
    # per-leaf key from the flattened path string; crc32, not hash(), so
    # the same seed gives the same weights in every process
    name = "/".join(str(getattr(p, "key", getattr(p, "idx", p))) for p in path)
    return jax.random.fold_in(root_key, zlib.crc32(name.encode()) >> 1)


def init_params(specs, key):
    """Materialize a spec tree into concrete parameters."""
    def init_one(path, spec: ParamSpec):
        if spec.init == "zeros":
            return jnp.zeros(spec.shape, spec.jdtype)
        if spec.init == "ones":
            return jnp.ones(spec.shape, spec.jdtype)
        k = _leaf_key(key, path)
        return (jax.random.normal(k, spec.shape, jnp.float32) * spec.scale
                ).astype(spec.jdtype)
    return jax.tree_util.tree_map_with_path(init_one, specs,
                                            is_leaf=is_spec)


def abstract_params(specs):
    """Spec tree -> ShapeDtypeStruct tree (no allocation; for dry-run)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.jdtype), specs,
        is_leaf=is_spec)


def param_pspecs(specs, rules) -> object:
    """Spec tree -> PartitionSpec tree under ``rules``.

    ``rules`` is a :class:`repro.runtime.sharding.Rules` (maps logical axis
    name -> mesh axes with divisibility fallback).
    """
    return jax.tree.map(lambda s: rules.pspec(s.logical_axes, s.shape),
                        specs, is_leaf=is_spec)


def param_count(specs) -> int:
    import math
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)
    return sum(math.prod(s.shape) for s in leaves)


def param_bytes(specs) -> int:
    import math
    leaves = jax.tree.leaves(specs, is_leaf=is_spec)
    return sum(math.prod(s.shape) * s.jdtype.itemsize for s in leaves)


def stack_specs(specs, n: int):
    """Stack a block's spec tree along a new leading scan dim."""
    return jax.tree.map(lambda s: s.stacked(n), specs, is_leaf=is_spec)


def stack_params(param_list):
    """Stack a list of concrete per-layer param pytrees along dim 0."""
    return jax.tree.map(lambda *xs: jnp.stack(xs, axis=0), *param_list)


# Convenience constructors -------------------------------------------------

def dense_spec(d_in: int, d_out: Tuple[int, ...], axes_in, axes_out,
               dtype: str, scale: Optional[float] = None) -> ParamSpec:
    """Weight (d_in, *d_out) with fan-in scaled normal init."""
    if scale is None:
        scale = d_in ** -0.5
    d_out = (d_out,) if isinstance(d_out, int) else tuple(d_out)
    axes_out = (axes_out,) if isinstance(axes_out, (str, type(None))) \
        else tuple(axes_out)
    return ParamSpec((d_in,) + d_out, (axes_in,) + axes_out,
                     "normal", scale, dtype)
