"""MeshSpec: the declarative, wire-serializable mesh/topology point.

A ``jax.Mesh`` holds live device handles, so it can never cross a
process boundary — which is why meshed sweeps used to be locked out of
the process and remote scoring backends entirely.  :class:`MeshSpec` is
the content of a mesh *without* the devices: ordered ``(axis name,
size)`` pairs plus the device platform it must materialize on.  It is
pure JSON on the wire (``to_json``/``from_json``), and whichever process
ends up scoring a job calls :meth:`to_mesh` to rebuild the mesh against
*its own* local devices — a process worker, the HTTP scoring server, or
the parent all materialize the same spec independently and build
byte-identical programs.

MeshSpec is also the sweep's second outer axis
(``ComParTuner.sweep(mesh_space=[...])``): each spec is one swept
topology point, content-identified by :attr:`mid` — the versioned hash
that keys DB rows, incumbent scopes and the ``score_cache.mesh`` column,
so scores from different topologies can never alias.

The **local point** (no mesh at all) is ``MeshSpec(())`` — empty axes,
``to_mesh()`` returns ``None``, ``mid == "local"`` (matching the
historical cache key for meshless sweeps).  ``None`` entries in a
``mesh_space`` are coerced to it by :func:`as_mesh_point`.
"""
from __future__ import annotations

import hashlib
import json
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

#: version of the mesh content key.  v1 was the pre-spec era: an
#: *unversioned* sha1 of a live mesh's axes/shape/platform blob.  v2 is
#: the MeshSpec content hash.  Bumping the version changes every hash,
#: so score_cache rows written under the old key format can never be
#: served to (or clobbered by) spec-keyed sweeps.
MESH_KEY_VERSION = 2


class MeshUnsatisfiable(ValueError):
    """This host cannot materialize the spec (not enough matching
    devices).  A *protocol* error on the scoring server — the client's
    request can never succeed here, so it must fail loudly (HTTP 400),
    not be retried as a transient outage."""


@dataclass(frozen=True)
class MeshSpec:
    """Axis names + sizes + device kind; ``()`` axes = the local point."""

    axes: Tuple[Tuple[str, int], ...] = ()
    device_kind: str = ""               # "" = any local platform

    def __post_init__(self):
        # tolerate list/dict inputs (JSON decoding, hand-written specs)
        axes = self.axes.items() if isinstance(self.axes, dict) else self.axes
        object.__setattr__(
            self, "axes", tuple((str(n), int(s)) for n, s in axes))
        for name, size in self.axes:
            if size < 1:
                raise ValueError(f"mesh axis {name!r} has size {size}")

    # --- convenience constructors -------------------------------------
    @classmethod
    def of(cls, device_kind: str = "", **axes: int) -> "MeshSpec":
        """``MeshSpec.of(data=2, model=2)`` (kwarg order = axis order)."""
        return cls(tuple(axes.items()), device_kind)

    @classmethod
    def from_mesh(cls, mesh) -> "MeshSpec":
        """Derive the spec of a live ``jax.Mesh``.

        ``device_kind`` is deliberately left unconstrained: it is an
        *explicit* materialization constraint (part of the content key
        when set), and baking the parent's platform in here would give a
        fixed live mesh and the equivalent hand-written spec different
        content keys — splitting the score cache for no reason.  (The
        meshless ``"local"`` key never carried a platform either; the
        executor ``cache_tag`` half of the environment column is what
        scopes scores to a scoring method.)
        """
        return cls(tuple(zip(mesh.axis_names,
                             (int(d) for d in mesh.devices.shape))))

    # --- content ------------------------------------------------------
    @property
    def is_local(self) -> bool:
        return not self.axes

    @property
    def axis_names(self) -> Tuple[str, ...]:
        return tuple(n for n, _ in self.axes)

    @property
    def shape(self) -> Tuple[int, ...]:
        return tuple(s for _, s in self.axes)

    @property
    def n_devices(self) -> int:
        n = 1
        for _, s in self.axes:
            n *= s
        return n

    def axis_sizes(self) -> Dict[str, int]:
        return dict(self.axes)

    def key(self) -> str:
        """Human-readable point label (the mesh analogue of
        ``GlobalKnobs.key``)."""
        if self.is_local:
            return "local"
        body = "x".join(f"{n}{s}" for n, s in self.axes)
        return f"{body}[{self.device_kind or 'any'}]"

    @property
    def mid(self) -> str:
        """Versioned content id: keys DB rows (``row_cid``), incumbent
        scopes and the ``score_cache.mesh`` column.  ``"local"`` for the
        local point — the historical meshless cache key."""
        if self.is_local:
            return "local"
        blob = json.dumps({"v": MESH_KEY_VERSION,
                           "axes": [list(a) for a in self.axes],
                           "kind": self.device_kind}, sort_keys=True)
        return hashlib.sha1(blob.encode()).hexdigest()[:12]

    # --- wire format --------------------------------------------------
    def to_json(self) -> Dict:
        return {"axes": [list(a) for a in self.axes],
                "device_kind": self.device_kind}

    @classmethod
    def from_json(cls, d: Dict) -> "MeshSpec":
        return cls(tuple((n, int(s)) for n, s in d.get("axes") or ()),
                   str(d.get("device_kind", "")))

    # --- materialization ----------------------------------------------
    def _local_devices(self):
        import jax
        return [d for d in jax.devices()
                if not self.device_kind
                or d.device_kind == self.device_kind]

    def check_local(self):
        """Raise :class:`MeshUnsatisfiable` unless this host can
        materialize the spec.  Cheap enough for submit-time validation
        (the scoring server rejects unsatisfiable specs with HTTP 400
        instead of burning workers on a request that can never score)."""
        if self.is_local:
            return
        have = len(self._local_devices())
        if have < self.n_devices:
            kind = self.device_kind or "any"
            raise MeshUnsatisfiable(
                f"mesh {self.key()} needs {self.n_devices} {kind!r} "
                f"device(s); this host has {have}")

    def to_mesh(self):
        """Materialize against *this process's* devices (``None`` for
        the local point).  Raises :class:`MeshUnsatisfiable` when the
        host can't satisfy the spec."""
        if self.is_local:
            return None
        import numpy as np
        from jax.sharding import Mesh
        self.check_local()
        devs = self._local_devices()[: self.n_devices]
        return Mesh(np.array(devs).reshape(self.shape), self.axis_names)


#: the local (meshless) sweep point
LOCAL = MeshSpec(())


def as_mesh_point(m) -> MeshSpec:
    """Coerce one ``mesh_space`` entry: ``None`` -> the local point,
    dicts -> spec (``{"data": 2}`` shorthand or the full
    ``{"axes": ..., "device_kind": ...}`` wire form), live meshes ->
    :meth:`MeshSpec.from_mesh`."""
    if m is None:
        return LOCAL
    if isinstance(m, MeshSpec):
        return m
    if isinstance(m, dict):
        if "axes" in m:
            return MeshSpec.from_json(m)
        d = dict(m)                      # {"data": 2, ...} shorthand;
        kind = d.pop("device_kind", "")  # "device_kind" is reserved
        return MeshSpec(tuple(d.items()), str(kind or ""))
    if hasattr(m, "axis_names") and hasattr(m, "devices"):
        return MeshSpec.from_mesh(m)
    raise TypeError(f"not a mesh point: {m!r}")


#: spec.mid -> materialized Mesh, per process.  A process's device set
#: is fixed for its lifetime, so materializing each spec once is safe —
#: and worth it: thread-backend jobs and warm process workers score many
#: jobs under the same point.
_MESH_CACHE: Dict[str, object] = {}


def cached_mesh(spec: Optional[MeshSpec]):
    """``spec.to_mesh()`` memoized per process (None passes through)."""
    if spec is None or spec.is_local:
        return None
    mesh = _MESH_CACHE.get(spec.mid)
    if mesh is None:
        mesh = spec.to_mesh()
        _MESH_CACHE[spec.mid] = mesh
    return mesh


def default_mesh_space(device_count: Optional[int] = None,
                       device_kind: str = "") -> List[MeshSpec]:
    """Topology presets derived from the detected devices: the local
    point, the flat data ring, and every 2-D ``data x model``
    factorization of ``device_count`` — the points
    ``sweep(mesh_space="auto")`` races.

    ``device_count=None`` detects via ``jax.device_count()`` (lazy: a
    module importing this one never pulls jax in).  Single-device hosts
    get just the local point.  Factor pairs are ordered data-major
    (``data >= model`` first), matching the usual batch-parallel bias;
    every spec is buildable on this host by construction.
    """
    if device_count is None:
        import jax
        device_count = jax.device_count()
    n = int(device_count)
    out = [LOCAL]
    if n <= 1:
        return out
    out.append(MeshSpec((("data", n),), device_kind))
    pairs = []
    for a in range(2, n + 1):
        if n % a == 0 and n // a >= 2:
            pairs.append((a, n // a))
    # data-major order: (4,2) before (2,4) on 8 devices
    for a, b in sorted(pairs, key=lambda p: (-p[0], p[1])):
        out.append(MeshSpec((("data", a), ("model", b)), device_kind))
    return out


def __getattr__(name: str):
    # PEP 562: DEFAULT_MESH_SPACE queries local devices, so it must not
    # run at import time (importing meshspec would initialize jax)
    if name == "DEFAULT_MESH_SPACE":
        return default_mesh_space()
    raise AttributeError(name)
