"""Logical-axis sharding rules.

A :class:`Rules` object maps *logical* axis names (``embed``, ``heads``,
``batch`` ...) to physical mesh axes, with divisibility-aware fallbacks.
Strategy providers (``repro.core.providers``) are essentially factories of
``Rules`` — the "compiler output" of ComParX is a set of rules per segment.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple, Union

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

Candidate = Union[None, str, Tuple[str, ...]]


def _as_candidates(v) -> List[Candidate]:
    """Normalize a mapping value into an ordered candidate list."""
    if isinstance(v, list):
        return v + [None] if v and v[-1] is not None else (v or [None])
    return [v, None] if v is not None else [None]


class Rules:
    """logical axis -> mesh axes resolution with divisibility fallback."""

    def __init__(self, mapping: Dict[str, object],
                 mesh: Optional[Mesh] = None):
        self.mapping = {k: _as_candidates(v) for k, v in (mapping or {}).items()}
        self.mesh = mesh
        self.axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) \
            if mesh is not None else {}

    # ------------------------------------------------------------------
    def _resolve_one(self, name: Optional[str], dim: int,
                     used: set) -> Optional[Tuple[str, ...]]:
        if name is None:
            return None
        for cand in self.mapping.get(name, [None]):
            if cand is None:
                return None
            axes = (cand,) if isinstance(cand, str) else tuple(cand)
            # keep only axes that exist in this mesh and are unused
            axes = tuple(a for a in axes
                         if a in self.axis_sizes and a not in used)
            if not axes:
                continue
            size = 1
            for a in axes:
                size *= self.axis_sizes[a]
            if dim % size == 0:
                used.update(axes)
                return axes
        return None

    def pspec(self, logical_axes: Sequence[Optional[str]],
              shape: Sequence[int]) -> PartitionSpec:
        used: set = set()
        parts = []
        for name, dim in zip(logical_axes, shape):
            axes = self._resolve_one(name, dim, used)
            if axes is None:
                parts.append(None)
            elif len(axes) == 1:
                parts.append(axes[0])
            else:
                parts.append(axes)
        # trim trailing Nones for tidiness
        while parts and parts[-1] is None:
            parts.pop()
        return PartitionSpec(*parts)

    def sharding(self, logical_axes, shape) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return NamedSharding(self.mesh, self.pspec(logical_axes, shape))

    def constrain(self, x, logical_axes):
        """with_sharding_constraint by logical axes (no-op without a mesh)."""
        if self.mesh is None:
            return x
        s = self.sharding(logical_axes, x.shape)
        return jax.lax.with_sharding_constraint(x, s)

    # ------------------------------------------------------------------
    @classmethod
    def null(cls) -> "Rules":
        return cls({}, None)

    def merged(self, extra: Dict[str, object]) -> "Rules":
        m = dict(self.mapping)
        m.update({k: _as_candidates(v) for k, v in extra.items()})
        r = Rules.__new__(Rules)
        r.mapping, r.mesh, r.axis_sizes = m, self.mesh, self.axis_sizes
        return r

    def __repr__(self):
        return f"Rules({ {k: v for k, v in self.mapping.items()} })"


def batch_axes(mesh: Optional[Mesh]) -> Tuple[str, ...]:
    """The data-parallel axes present in a mesh (pod first for DCN)."""
    if mesh is None:
        return ()
    return tuple(a for a in ("pod", "data") if a in mesh.axis_names)
