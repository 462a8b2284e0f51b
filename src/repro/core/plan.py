"""Parallelization plans: per-segment Combination + global knobs.

A :class:`Plan` is ComParX's "output program": where ComPar emits a fused
C file, ComParX emits a serializable plan that the step builders apply to
the jitted program (sharding rules + remat + kernels + microbatching).
"""
from __future__ import annotations

import json
import logging
from dataclasses import dataclass, field
from typing import Dict, Optional

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.combinator import Combination, GlobalKnobs
from repro.core.meshspec import MeshSpec
from repro.core.providers import get_provider
from repro.core.segment import Segment, fragment
from repro.models.context import ModelContext, SegmentClause
from repro.runtime.sharding import Rules

log = logging.getLogger("repro.plan")


@dataclass
class Plan:
    segments: Dict[str, Combination]
    knobs: GlobalKnobs = field(default_factory=GlobalKnobs)
    meta: Dict[str, object] = field(default_factory=dict)
    #: the mesh/topology point the plan was fused for.  ``None`` =
    #: unswept (pre-mesh plans load unchanged); set by ``fuse_joint``
    #: when a ``mesh_space`` was swept — the CHOSEN topology, the mesh
    #: analogue of ``knobs``.
    mesh: Optional[MeshSpec] = None

    def to_json(self) -> Dict:
        return {"segments": {k: c.to_json() for k, c in self.segments.items()},
                "knobs": vars(self.knobs), "meta": self.meta,
                "mesh": self.mesh.to_json() if self.mesh is not None
                else None}

    @classmethod
    def from_json(cls, d: Dict) -> "Plan":
        return cls({k: Combination.from_json(v)
                    for k, v in d["segments"].items()},
                   GlobalKnobs(**d["knobs"]), d.get("meta", {}),
                   MeshSpec.from_json(d["mesh"]) if d.get("mesh") else None)

    def save(self, path: str):
        with open(path, "w") as f:
            json.dump(self.to_json(), f, indent=2, sort_keys=True)

    @classmethod
    def load(cls, path: str) -> "Plan":
        with open(path) as f:
            return cls.from_json(json.load(f))

    def lint(self, cfg: ArchConfig, shape, *, trace: bool = True):
        """Certify this plan against ``(cfg, shape)`` without compiling.

        Thin wrapper over :func:`repro.analysis.analyze_plan` — returns
        the list of :class:`repro.analysis.Diagnostic`; empty means the
        plan passes every static rule."""
        from repro.analysis import analyze_plan
        return analyze_plan(cfg, shape, self, trace=trace)

    def describe(self) -> str:
        lines = [f"knobs: {self.knobs.key()}"]
        if self.mesh is not None:
            lines.insert(0, f"mesh: {self.mesh.key()}")
        for seg, c in sorted(self.segments.items()):
            lines.append(f"  {seg:8s} -> {c.label()}")
        return "\n".join(lines)


def uniform_plan(cfg: ArchConfig, provider: str,
                 flags=frozenset(), clause: Optional[SegmentClause] = None,
                 knobs: Optional[GlobalKnobs] = None) -> Plan:
    """Single-provider plan — the "one compiler for the whole program"
    baseline that ComPar's fusion is compared against."""
    clause = clause or SegmentClause()
    combo = Combination(provider, frozenset(flags), clause)
    return Plan({s.name: combo for s in fragment(cfg)},
                knobs or GlobalKnobs())


def default_plan(cfg: ArchConfig, shape: ShapeConfig) -> Plan:
    """The a-priori 'single best compiler' baseline plan per cell
    (what a practitioner would pick without ComParX's sweep)."""
    if shape.kind == "train":
        clause = SegmentClause(remat="dots", kernel="xla")
        knobs = GlobalKnobs(microbatches=1, donate=True,
                            opt_state_dtype="bfloat16" if cfg.is_moe
                            else "float32")
        if cfg.is_moe:
            return uniform_plan(
                cfg, "expert_par",
                frozenset({"tp_attention", "fsdp_dense", "2d_experts"}),
                clause, knobs)
        return uniform_plan(cfg, "hybrid2d", frozenset({"shard_vocab"}),
                            clause, knobs)
    clause = SegmentClause(remat="none", kernel="xla")
    if cfg.is_moe:
        return uniform_plan(
            cfg, "expert_par",
            frozenset({"tp_attention", "fsdp_dense", "2d_experts"}),
            clause)
    return uniform_plan(cfg, "tensor_par", frozenset({"shard_vocab"}),
                        clause)


def dp_shards(mesh) -> int:
    """Number of data-parallel shards (pod x data axes)."""
    if mesh is None:
        return 1
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    return sizes.get("pod", 1) * sizes.get("data", 1)


def build_contexts(cfg: ArchConfig, mesh,
                   plan: Plan) -> Dict[str, ModelContext]:
    """Apply a plan: per-segment ModelContext with provider rules.

    A plan missing a segment (e.g. fused for a smaller config) gets that
    segment's context from the plan's first combination — loudly: the
    substitution is logged and recorded in ``plan.meta`` so partial plans
    stay visible instead of silently borrowing an arbitrary combination.
    """
    axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape)) \
        if mesh is not None else {}
    ctxs: Dict[str, ModelContext] = {}
    groups = dp_shards(mesh)
    substituted: Dict[str, Dict[str, str]] = {}
    for seg in fragment(cfg):
        combo = plan.segments.get(seg.name)
        if combo is None:
            donor, combo = next(iter(plan.segments.items()))
            log.warning(
                "plan has no combination for segment %r; substituting %s "
                "from segment %r", seg.name, combo.label(), donor)
            substituted[seg.name] = {"from": donor, "combo": combo.label()}
        provider = get_provider(combo.provider)
        mapping = provider.mapping(cfg, axis_sizes, combo.flags, seg)
        ctxs[seg.name] = ModelContext(
            rules=Rules(mapping, mesh), clause=combo.clause,
            moe_groups=groups)
    if substituted:
        plan.meta.setdefault("substituted_segments", {}).update(substituted)
    return ctxs


def segment_rules(cfg: ArchConfig, mesh, plan: Plan) -> Dict[str, Rules]:
    return {k: c.rules for k, c in
            build_contexts(cfg, mesh, plan).items()}
