"""ComParX tuner: the paper's end-to-end workflow (Fig. 1).

Fragmentor -> Combinator (-> DB register) -> Parallelizer+Executor per
(combination, knob point) (-> DB record, Continue-mode resumable) ->
black-box validation -> Optimal Plan Generator -> fused Plan whose
``knobs`` are the joint argmin over the swept GlobalKnobs grid
(``sweep(global_space=...)`` — the paper's RTL-routine axis).

The sweep execution core is the three-stage pipeline of
``repro.core.backends`` (see docs/sweep_engine.md):

* **Scheduler** — groups (segment, combination) rows that resolve to the
  *same program* (structural score sharing), resolves whole groups from
  the persistent cross-project ``score_cache``, and orders the remaining
  unique programs cheapest-lower-bound-first.
* **ScoringBackend** — scores unique programs: ``thread`` (PR-1
  semantics; soft off-main-thread deadline), ``sequential`` (one worker,
  no pool), ``process`` (spawned workers; true parallel tracing past
  the GIL and a *hard* kill-based timeout with requeue-once-then-fail),
  or ``remote`` (ship jobs to a sweep scoring server —
  ``sweep(remote_url=...)`` — which resolves them against ITS shared
  score cache first: cross-host amortization).
* **Recorder** — fans outcomes back out to member rows, keeps the
  report accounting, applies the cache policy (transient outcomes are
  never cached), and writes batched transactions.

Exact lower-bound pruning (never changes the argmin) runs inside the
backend against shared incumbents.
"""
from __future__ import annotations

import logging
import time
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.backends import Recorder, Scheduler, make_backend
from repro.core.combinator import (Combination, GlobalKnobs, SweepSpec,
                                   enumerate_combinations, global_grid,
                                   paper_combination_count, row_cid,
                                   swept_knob_fields)
from repro.core.cost_model import CostTerms
from repro.core.db import SweepDB
from repro.core.executor import (DryRunExecutor, ParallelSweepRunner,  # noqa: F401  (ParallelSweepRunner re-exported for spies/back-compat)
                                 SweepJob, WallClockExecutor)
from repro.core.fusion import best_uniform, fuse, fuse_joint  # noqa: F401  (fuse re-exported)
from repro.core.meshspec import MeshSpec, as_mesh_point, cached_mesh
from repro.core.plan import Plan
from repro.core.providers import all_providers, get_provider
from repro.core.segment import Segment, fragment

log = logging.getLogger("repro.tuner")


@dataclass
class SweepReport:
    project: str
    n_combinations: int     # realized registered rows (incl. the knob axis)
    n_done: int = 0
    n_failed: int = 0
    n_invalid: int = 0
    n_pruned: int = 0       # rows skipped by the exact lower-bound prune
    n_scored: int = 0       # programs that actually compiled+analyzed
    n_cached: int = 0       # rows served from the persistent score cache
    n_shared: int = 0       # rows that shared an in-run compiled score
    n_transient: int = 0    # rows failed by deadline/crash (retryable)
    n_static: int = 0       # rows rejected by the static analyzer before
                            # dispatch (static_checks="strict")
    n_inapplicable: int = 0  # (segment, combination) pairs dropped because
                             # the provider is inapplicable to the segment
                             # (counted once, before the knob/mesh axes)
    n_knob_points: int = 1  # GlobalKnobs points swept (the RTL axis)
    n_mesh_points: int = 1  # mesh/topology points swept (the mesh axis)
    paper_count: int = 0    # the paper's formula, an upper bound
    elapsed_s: float = 0.0
    #: degraded-mode accounting — a sweep that limped home must say so
    n_fallback_local: int = 0       # rows re-scored locally after the
                                    # remote retry budget ran out
    n_transient_retried: int = 0    # extra dispatches spent on transient
                                    # recovery (requeues + retry rounds)
    #: failure-kind histogram over FAILED rows ("deadline", "crash",
    #: "mesh", "unreachable", "server", "deterministic", "transient")
    failure_kinds: Dict[str, int] = field(default_factory=dict)
    #: per-rule histogram over statically diagnosed rows (strict AND
    #: warn modes; one count per row per distinct rule) — see
    #: repro.analysis for the rule ids
    static_rules: Dict[str, int] = field(default_factory=dict)
    #: the winning (mesh, knob) point's per-segment valid rows
    per_segment: Dict[str, List[Tuple[Combination, CostTerms]]] = \
        field(default_factory=dict)
    #: knobs.key() -> fused predicted total, every fusable knob point
    #: (of the winning mesh point, when the mesh is swept)
    per_knob_total_s: Dict[str, float] = field(default_factory=dict)
    #: mesh.key() -> fused predicted total, every fusable mesh point
    per_mesh_total_s: Dict[str, float] = field(default_factory=dict)
    #: segment kind -> {"n", "mean", "max"} of bound/measured over done
    #: rows — the drift observability for the calibrated machine model
    #: (a ratio > 1 means the certificate broke: see audit_soundness)
    bound_tightness: Dict[str, Dict[str, float]] = field(default_factory=dict)
    #: the inner kernel sweep's observability (``sweep(kernel_space=...)``):
    #: variants enumerated/timed/cache-hit/failed, top_k, per-op best
    #: schedule, per-segment kept counts.  None = no kernel axis.
    kernel_tuning: Optional[Dict] = None

    def summary(self) -> str:
        s = (f"project={self.project} knob_points={self.n_knob_points} "
             f"mesh_points={self.n_mesh_points} "
             f"done={self.n_done} failed={self.n_failed} "
             f"invalid={self.n_invalid} pruned={self.n_pruned} "
             f"scored={self.n_scored} cached={self.n_cached} "
             f"shared={self.n_shared} transient={self.n_transient} "
             f"realized={self.n_combinations} "
             f"paper_formula_upper_bound={self.paper_count} "
             f"elapsed={self.elapsed_s:.1f}s")
        if self.n_static or self.static_rules:
            s += f" static={self.n_static}"
            if self.static_rules:
                rules = ",".join(f"{k}:{v}" for k, v in
                                 sorted(self.static_rules.items()))
                s += f"[{rules}]"
        if self.n_inapplicable:
            s += f" inapplicable={self.n_inapplicable}"
        if self.n_transient_retried:
            s += f" transient_retried={self.n_transient_retried}"
        if self.n_fallback_local:
            s += f" fallback_local={self.n_fallback_local}"
        if self.failure_kinds:
            kinds = ",".join(f"{k}:{v}" for k, v in
                             sorted(self.failure_kinds.items()))
            s += f" failure_kinds={kinds}"
        if self.bound_tightness:
            tight = ",".join(
                f"{k}:mean={v['mean']:.2f}/max={v['max']:.2f}(n={v['n']})"
                for k, v in sorted(self.bound_tightness.items()))
            s += f" bound_tightness={tight}"
        if self.kernel_tuning:
            kt = self.kernel_tuning
            s += (f" kernel_tuning=variants:{kt['n_variants']},"
                  f"timed:{kt['n_timed']},cached:{kt['n_cached']},"
                  f"failed:{kt['n_failed']},top_k:{kt['top_k']}")
        return s


@dataclass(frozen=True)
class BackendOptions:
    """``sweep()``'s scoring-backend kwargs as one typed value.

    ``sweep(backend=BackendOptions(...))`` — the bare kwargs
    (``workers=``, ``remote_url=``, ...) still work and mean exactly the
    same thing; passing a bundle AND a non-default bare kwarg of the
    same group is a ValueError, never a silent override."""
    backend: str = "thread"
    workers: int = 1
    remote_url: Optional[str] = None
    remote_token: Optional[str] = None
    fallback: Optional[str] = None
    retry: Optional[object] = None          # backends.RetryPolicy
    transient_retries: Optional[int] = None


@dataclass(frozen=True)
class SearchOptions:
    """``sweep()``'s search-strategy kwargs as one typed value
    (``sweep(search=SearchOptions(...))``); same conflict contract as
    :class:`BackendOptions`."""
    prune: bool = False
    prune_margin: float = 0.1
    static_checks: str = "warn"
    kernel_space: Optional[object] = None   # "auto" | {field: values}
    kernel_top_k: int = 2
    use_cache: bool = True
    share_scores: bool = True
    record_batch: int = 64


def _unbundle(bundle, bare: Dict[str, Tuple], kind: str) -> List:
    """Explode a kwarg bundle, refusing non-default bare twins."""
    clash = [k for k, (v, d) in bare.items() if v is not d and v != d]
    if clash:
        raise ValueError(
            f"{kind} conflicts with bare kwarg(s) {sorted(clash)}: pass "
            f"the value inside the bundle or drop the bundle")
    return [getattr(bundle, f) for f in bundle.__dataclass_fields__]


class ComParTuner:
    def __init__(self, cfg: ArchConfig, shape: ShapeConfig, mesh=None, *,
                 db: Optional[SweepDB] = None, project: Optional[str] = None,
                 mode: str = "new", executor: str = "dryrun",
                 machine=None, registry=None,
                 validate: bool = False, timeout_s: Optional[int] = 300):
        self.cfg = cfg
        self.shape = shape
        # a declarative MeshSpec is accepted wherever a live mesh is:
        # materialized here once against local devices
        self.mesh = cached_mesh(mesh) if isinstance(mesh, MeshSpec) else mesh
        self.db = db or SweepDB(":memory:")
        name = project or f"{cfg.name}-{shape.name}"
        self.project = self.db.open_project(
            name, mode, {"arch": cfg.name, "shape": shape.name})
        # ``machine``: the dryrun scorer's hardware model — None (the
        # built-in v5e constants), "auto" (calibrate this host or load
        # its cached profile from the DB's machine_cache), a
        # MachineProfile, or a Hardware.  The calibrated view's name
        # lands in the executor cache_tag, so calibrated and constant
        # scores never share cache rows; bounds divide by the same view
        # (Scheduler reads executor.hw), so pruning stays exact.
        if executor == "dryrun":
            hw = None
            if machine is not None:
                from repro.core.machine import resolve_machine
                hw = resolve_machine(machine, self.db)
            self.executor = DryRunExecutor(
                self.mesh, timeout_s=timeout_s,
                **({"hw": hw} if hw is not None else {}))
        elif executor == "wallclock":
            if machine is not None:
                log.warning("machine= ignored: wallclock scores are "
                            "measured, not modeled")
            self.executor = WallClockExecutor(self.mesh, timeout_s=timeout_s)
        else:
            raise ValueError(executor)
        # ``registry``: where the fused plan of every ``sweep()`` is
        # persisted for the serving side (repro.serve) — None (off),
        # True (a PlanRegistry in THIS tuner's DB: plans beside the
        # scores that produced them), a PlanRegistry, or a DB path.
        self.registry = None
        if registry is not None and registry is not False:
            from repro.serve.registry import PlanRegistry
            if registry is True:
                self.registry = PlanRegistry(self.db)
            elif hasattr(registry, "register") and hasattr(registry,
                                                           "lookup"):
                # duck-typed, not isinstance: `python -m` runs modules
                # under __main__, which forks the class object
                self.registry = registry
            else:
                self.registry = PlanRegistry(registry)
        self.validate = validate
        #: cached ScoringBackends (warm process pools) — see _engine()
        self._engines: Dict[Tuple, object] = {}
        #: the latest sweep's kernel-autotuner verdict (None = no kernel
        #: axis) — _bound_tightness/audit_soundness recompute bounds with
        #: the same per-schedule floors the Scheduler stamped on jobs
        self._kernel_tuning = None

    # ------------------------------------------------------------------
    def sweep(self, providers: Optional[Sequence[str]] = None,
              clause_space=None, *,
              spec: Optional[SweepSpec] = None,
              budget: Optional[int] = None,
              knobs: GlobalKnobs = GlobalKnobs(),
              global_space: Optional[Dict[str, Tuple]] = None,
              mesh_space: Optional[Sequence] = None,
              boundary_costs: bool = False,
              max_flags: Optional[int] = None,
              backend="thread",
              search: Optional[SearchOptions] = None,
              workers: int = 1,
              remote_url: Optional[str] = None,
              remote_token: Optional[str] = None,
              fallback: Optional[str] = None,
              retry=None,
              transient_retries: Optional[int] = None,
              kernel_space=None, kernel_top_k: int = 2,
              static_checks: str = "warn",
              prune: bool = False, prune_margin: float = 0.1,
              use_cache: bool = True, share_scores: bool = True,
              record_batch: int = 64) -> Tuple[Plan, SweepReport]:
        """Run the sweep.  Engine knobs (see docs/sweep_engine.md):

        ``spec``          a :class:`~repro.core.combinator.SweepSpec`
                          carrying the whole search space (providers +
                          clause/global/mesh/kernel axes) as one typed
                          value — what :func:`load_sweep_json` returns.
                          Conflicts with the bare axis kwargs it covers
                          (``providers``/``clause_space``/
                          ``global_space``/``mesh_space``/
                          ``kernel_space``): passing both is a
                          ValueError.
        ``search``        a :class:`SearchOptions` bundling the
                          search-strategy kwargs (prune/static_checks/
                          kernel axis/cache policy); ``backend`` also
                          accepts a :class:`BackendOptions` bundling the
                          scoring-backend kwargs.  Bare kwargs still
                          work and are normalized to the same values —
                          a bundle plus a non-default bare twin raises.
        ``global_space``  GlobalKnobs grid to sweep as the outer axis
                          (the paper's RTL-routine dimension), e.g.
                          ``{"microbatches": (1, 2)}`` — unlisted fields
                          stay at their defaults.  The returned plan's
                          ``knobs`` are the joint argmin across the
                          grid.  Default ``None`` = today's single fixed
                          point (the ``knobs`` argument, which is
                          otherwise ignored).  The grid is not
                          ``budget``-sampled.
        ``mesh_space``    mesh/topology points swept as a second outer
                          axis: a list of ``MeshSpec`` | ``None`` (the
                          local point) | ``{"axis": size}`` dicts | live
                          meshes.  The returned ``plan.mesh`` is CHOSEN
                          by the joint argmin over
                          (segment, combination, knobs, mesh).  Default
                          ``None`` = the mesh is not swept (the
                          constructor's fixed mesh applies); when given,
                          the constructor mesh is *not* implicitly a
                          point — list it if you want it raced.
        ``backend``       scoring backend: ``thread`` (default) |
                          ``sequential`` | ``process`` | ``remote``
        ``workers``       workers scoring unique programs (threads or
                          spawned processes, per ``backend``; the remote
                          backend's workers live server-side)
        ``remote_url``    sweep scoring server URL (``backends/server.py``);
                          implies ``backend="remote"``.  Jobs are shipped
                          as JSON and resolved against the *server's*
                          score cache first — cross-host score sharing.
        ``remote_token``  shared-secret bearer token for a ``--token``
                          server (401 without it is a protocol error,
                          never retried)
        ``fallback``      local backend name (``thread`` | ``sequential``
                          | ``process``) that re-scores, in the same
                          run, jobs the remote backend failed
                          transiently (outage past the retry budget) —
                          the degraded-mode path; counted loudly in
                          ``SweepReport.n_fallback_local``
        ``retry``         a :class:`~repro.core.backends.RetryPolicy`
                          overriding the pipeline's retry contract
                          (request budget/backoff, per-job dispatch
                          attempts, scheduler retry rounds)
        ``transient_retries``  bounded Scheduler-level rounds re-running
                          transient failures in-sweep before they are
                          recorded (default: the retry policy's
                          ``sweep_retries``, 1)
        ``kernel_space``  the hierarchical kernel axis: ``"auto"`` (the
                          built-in tile/variant grid) or a
                          ``{field: values}`` grid over the kernel
                          schedule fields (``kernel``/``block_q``/
                          ``block_k``/``mlstm_chunk``).  The kernel
                          autotuner times every (op, schedule) variant
                          in isolation first (``kernel_cache``-resolved:
                          repeat sweeps re-benchmark nothing), then the
                          outer cross-product carries only the
                          ``kernel_top_k`` cheapest schedules per
                          segment — a T-schedule grid adds at most k
                          combos per affected segment instead of xT
                          compiles.  Kernel-space fields override the
                          same fields of ``clause_space`` in the
                          enumerated grid.  Default ``None`` = no inner
                          sweep (today's flat behavior).
        ``kernel_top_k``  surviving schedules per segment
                          (``>= len(grid)`` keeps everything: the sweep
                          is then byte-identical to an exhaustive clause
                          sweep over the merged space)
        ``static_checks`` the static validity analyzer
                          (``repro.analysis``): ``"warn"`` (default —
                          lint every point, report the per-rule
                          histogram in ``SweepReport.static_rules``,
                          dispatch everything), ``"strict"`` (also
                          settle ``error``-diagnosed rows as
                          ``"static"`` before they become JobSpecs —
                          sound: every dropped point provably fails
                          when compiled, so the fused plan is
                          byte-identical to an unlinted sweep), or
                          ``"off"`` (no lint at all).  Static rows are
                          never written to ``score_cache``.
        ``prune``         exact lower-bound pruning on/off
        ``prune_margin``  relative headroom the bound must clear
        ``use_cache``     persistent structural score cache on/off
        ``share_scores``  group structurally identical rows into one
                          compile (off = one compile per row, the
                          pre-engine behavior — benchmark baseline)
        ``record_batch``  DB rows per write transaction
        """
        t0 = time.time()
        # normalize the typed kwarg bundles first (backend, then search,
        # then spec), so a spec/bundle field colliding with a bare kwarg
        # is caught no matter which side carried it
        if isinstance(backend, BackendOptions):
            (backend, workers, remote_url, remote_token, fallback, retry,
             transient_retries) = _unbundle(
                backend,
                {"workers": (workers, 1), "remote_url": (remote_url, None),
                 "remote_token": (remote_token, None),
                 "fallback": (fallback, None), "retry": (retry, None),
                 "transient_retries": (transient_retries, None)},
                "BackendOptions")
        if search is not None:
            if not isinstance(search, SearchOptions):
                raise ValueError(f"search= takes a SearchOptions, got "
                                 f"{type(search).__name__}")
            (prune, prune_margin, static_checks, kernel_space,
             kernel_top_k, use_cache, share_scores, record_batch) = \
                _unbundle(
                    search,
                    {"prune": (prune, False),
                     "prune_margin": (prune_margin, 0.1),
                     "static_checks": (static_checks, "warn"),
                     "kernel_space": (kernel_space, None),
                     "kernel_top_k": (kernel_top_k, 2),
                     "use_cache": (use_cache, True),
                     "share_scores": (share_scores, True),
                     "record_batch": (record_batch, 64)},
                    "SearchOptions")
        if spec is not None:
            if not isinstance(spec, SweepSpec):
                raise ValueError(f"spec= takes a SweepSpec, got "
                                 f"{type(spec).__name__}")
            clash = [k for k, v in
                     {"providers": providers, "clause_space": clause_space,
                      "global_space": global_space,
                      "mesh_space": mesh_space,
                      "kernel_space": kernel_space}.items()
                     if v is not None]
            if clash:
                raise ValueError(
                    f"spec= conflicts with bare kwarg(s) {sorted(clash)}: "
                    f"the SweepSpec already carries those axes")
            providers = list(spec.providers) or None
            clause_space = spec.clauses
            global_space = spec.globals
            mesh_space = list(spec.meshes) if spec.meshes is not None \
                else None
            kernel_space = spec.kernel_space
        points = global_grid(global_space) if global_space is not None \
            else [knobs]
        if isinstance(mesh_space, str):
            if mesh_space != "auto":
                raise ValueError(f"mesh_space={mesh_space!r}: the only "
                                 f"string value is 'auto'")
            from repro.core.meshspec import default_mesh_space
            mesh_space = default_mesh_space()
        mesh_swept = mesh_space is not None
        mpoints: Optional[List[MeshSpec]] = None
        if mesh_swept:
            # normalize + dedupe by content: the same topology listed
            # twice would register colliding rows and double-count points
            mpoints, seen = [], set()
            for m in mesh_space:
                mp = as_mesh_point(m)
                if mp.mid not in seen:
                    seen.add(mp.mid)
                    mpoints.append(mp)
            if not mpoints:
                raise ValueError("mesh_space is empty")
            if self.mesh is not None:
                log.info("mesh_space sweeps its own points; the fixed "
                         "constructor mesh is not implicitly included")
        # prune + boundary_costs compose exactly now: the Scheduler
        # stamps every job with the Viterbi pruning allowance
        # (JobSpec.slack_s = (n_segs-1) * max single boundary cost), so
        # a pruned combination provably cannot win any chain either —
        # see IncumbentTracker.pruned and fusion.max_boundary_cost_s.
        if remote_url is not None:
            backend = "remote"
        if backend == "remote" and not remote_url:
            raise ValueError("backend='remote' needs remote_url "
                             "(the sweep scoring server URL)")
        if fallback is not None and backend != "remote":
            raise ValueError("fallback= is the remote backend's degraded "
                             "mode; it needs remote_url/backend='remote'")
        if backend in ("process", "remote") and isinstance(
                self.executor, WallClockExecutor):
            # a chip belongs to one process: a worker or a scoring server
            # would time on devices this process holds, or on another host
            raise ValueError(
                f"backend={backend!r} scores outside this process; a "
                "wallclock sweep times on this process's devices, so use "
                "backend='thread' or 'sequential'")
        if workers > 1 and not getattr(self.executor, "parallel_safe", True):
            log.warning("workers=%d -> 1: %s timings would contend on the "
                        "device", workers, type(self.executor).__name__)
            workers = 1
        if prune and not hasattr(self.executor, "hw"):
            # the bound divides by the analytic hw model's peak; against an
            # executor measuring real wall seconds on unknown hardware the
            # certificate (bound <= score) no longer holds
            log.warning("prune disabled: %s has no hardware model",
                        type(self.executor).__name__)
            prune = False
        providers = list(providers or all_providers())
        segs = fragment(self.cfg)

        # Hierarchical kernel axis: run the inner (op, schedule) sweep
        # first, then enumerate the OUTER space over the merged grid and
        # filter each segment down to its top-k surviving schedules.
        # Filtering (instead of nested expansion) preserves enumeration
        # order, so kernel_top_k >= len(grid) registers rows in exactly
        # the order an exhaustive clause sweep would — argmin tie-breaks,
        # and therefore fused plans, stay byte-identical.
        tuning = None
        space = clause_space
        if kernel_space is not None:
            from repro.kernels.autotune import (DEFAULT_KERNEL_SPACE,
                                                tune_segments)
            if isinstance(kernel_space, str):
                if kernel_space != "auto":
                    raise ValueError(f"kernel_space={kernel_space!r}: the "
                                     f"only string value is 'auto'")
                kernel_space = DEFAULT_KERNEL_SPACE
            kspace = {k: tuple(v) for k, v in kernel_space.items()}
            from repro.core.combinator import DEFAULT_CLAUSE_SPACE
            space = dict(clause_space or DEFAULT_CLAUSE_SPACE)
            space.update(kspace)
            tuning = tune_segments(self.db, self.cfg, self.shape, segs,
                                   space, self.executor,
                                   top_k=kernel_top_k, use_cache=use_cache)
            rep_kernel = tuning.report
        self._kernel_tuning = tuning

        combos = enumerate_combinations(providers, space,
                                        budget=budget, max_flags=max_flags)
        rep = SweepReport(
            self.project, n_combinations=0, n_knob_points=len(points),
            n_mesh_points=len(mpoints) if mesh_swept else 1,
            paper_count=paper_combination_count(
                [len(get_provider(p).flags) for p in providers],
                # charge the formula's rtl term for what is actually
                # swept, not the field count of a fixed knobs instance
                n_rtl=len(swept_knob_fields(global_space)),
                n_d=len(space or {}) or 6))
        if tuning is not None:
            rep.kernel_tuning = rep_kernel

        # Combinator: register every (segment, combination, knob point,
        # mesh point), one transaction.  Unswept mesh = None (bare row
        # ids: pre-mesh projects resume unchanged).  Inapplicable
        # (provider, segment) pairs are counted, not silently dropped —
        # sweep accounting must be exact against paper_combination_count.
        per_seg_combos: Dict[str, List[Combination]] = {}
        for seg in segs:
            kept: List[Combination] = []
            for c in combos:
                if not get_provider(c.provider).applicable(self.cfg, seg):
                    rep.n_inapplicable += 1
                    continue
                if tuning is not None and not tuning.keeps(seg.name,
                                                           c.clause):
                    continue
                kept.append(c)
            per_seg_combos[seg.name] = kept
        reg: List[Tuple] = []
        for mp in (mpoints if mesh_swept else [None]):
            for kn in points:
                for seg in segs:
                    reg.extend((seg.name, c, kn, mp)
                               for c in per_seg_combos[seg.name])
        rep.n_combinations = len(reg)
        self.db.register_many(self.project, reg)

        self._execute(segs, per_seg_combos, points, rep,
                      mesh_points=mpoints, kernel_tuning=tuning,
                      static_checks=static_checks,
                      backend=backend, workers=workers,
                      remote_url=remote_url, remote_token=remote_token,
                      fallback=fallback, retry=retry,
                      transient_retries=transient_retries, prune=prune,
                      prune_margin=prune_margin, use_cache=use_cache,
                      share_scores=share_scores, record_batch=record_batch,
                      boundary_slack=prune and boundary_costs)

        # collect valid results per (mesh point, knob point, segment)
        by_rid = {(r["segment"], r["cid"]): r
                  for r in self.db.results(self.project)}

        def knob_table(mp):
            per_knob: Dict[str, Dict[str, List[Tuple[Combination,
                                                     CostTerms]]]] = {}
            for kn in points:
                table = per_knob.setdefault(kn.kid, {})
                for seg in segs:
                    good = table.setdefault(seg.name, [])
                    for c in per_seg_combos[seg.name]:
                        r = by_rid.get((seg.name, row_cid(c, kn, mp)))
                        if r is not None and r["status"] == "done" \
                                and r["cost"]:
                            good.append((c, CostTerms.from_dict(r["cost"])))
            return per_knob

        counts = self.db.done_count(self.project)
        rep.n_done = counts.get("done", 0)
        rep.n_failed = counts.get("failed", 0)
        rep.n_invalid = counts.get("invalid", 0)
        rep.n_pruned = counts.get("pruned", 0)
        rep.n_static = counts.get("static", 0)
        rep.bound_tightness, violations = self._bound_tightness()
        if violations:
            # should be impossible (the bound is certified); seeing this
            # in a summary means a floor overshoots — fix it before
            # trusting prune=True
            log.warning("bound soundness violated on %d done row(s): %s",
                        len(violations), violations[:3])

        if mesh_swept:
            per_mesh = {mp.mid: knob_table(mp) for mp in mpoints}
            plan = fuse_joint(self.cfg, self.shape, None, per_mesh, points,
                              boundary_costs=boundary_costs,
                              mesh_points=mpoints)
            rep.per_segment = per_mesh[plan.mesh.mid][plan.knobs.kid]
            rep.per_mesh_total_s = dict(plan.meta["per_mesh_total_s"])
        else:
            per_knob = knob_table(None)
            plan = fuse_joint(self.cfg, self.shape, self.mesh, per_knob,
                              points, boundary_costs=boundary_costs)
            rep.per_segment = per_knob[plan.knobs.kid]
        plan.meta["project"] = self.project
        rep.per_knob_total_s = dict(plan.meta["per_knob_total_s"])
        rep.elapsed_s = time.time() - t0
        if self.registry is not None:
            # plans are keyed by what they were tuned FOR: the plan's
            # chosen mesh when the mesh was swept, the fixed one else
            self.registry.register(
                self.cfg, self.shape, plan, report=rep,
                mesh=plan.mesh if plan.mesh is not None else self.mesh,
                cache_tag=self.executor.cache_tag)
        log.info(rep.summary())
        return plan, rep

    # ------------------------------------------------------------------
    def _bound_tightness(self):
        """Recompute ``combo_lower_bound`` for every ``done`` row of
        this project and compare against the recorded score.

        Returns ``(table, violations)``: a per-segment-kind
        ``{"n", "mean", "max"}`` table of bound/measured ratios (the
        SweepReport's drift observability) and the rows where the bound
        exceeded the measurement — which the certificate says must be
        empty.  Cheap: no compiles, one DB scan.
        """
        from repro.core.cost_model import V5E, combo_lower_bound
        hw = getattr(self.executor, "hw", V5E)
        fixed_chips = getattr(self.executor, "n_chips", 1)
        fixed_axes = dict(zip(self.mesh.axis_names,
                              self.mesh.devices.shape)) \
            if self.mesh is not None else None
        segs = {s.name: s for s in fragment(self.cfg)}
        stats: Dict[str, Dict[str, float]] = {}
        violations = []
        for r in self.db.results(self.project):
            if r["status"] != "done" or not r["cost"]:
                continue
            seg = segs.get(r["segment"])
            if seg is None:
                continue
            mesh = r["mesh"]
            # rows recorded by a pre-kernel-axis sweep of the same
            # project project to unmeasured schedules -> floor 0.0
            kflops = self._kernel_tuning.floor_flops(
                r["segment"], r["combo"].clause) \
                if self._kernel_tuning is not None else 0.0
            bound = combo_lower_bound(
                self.cfg, self.shape, seg, r["combo"],
                mesh.n_devices if mesh is not None else fixed_chips, hw,
                knobs=r["knobs"],
                mesh_axes=mesh.axis_sizes() if mesh is not None
                else fixed_axes, kernel_flops=kflops)
            total = CostTerms.from_dict(r["cost"]).total_s
            if total <= 0.0:
                continue
            ratio = bound / total
            st = stats.setdefault(seg.kind, {"n": 0, "sum": 0.0, "max": 0.0})
            st["n"] += 1
            st["sum"] += ratio
            st["max"] = max(st["max"], ratio)
            if bound > total * (1.0 + 1e-9):
                violations.append((r["segment"], r["cid"], bound, total))
        table = {k: {"n": int(v["n"]), "mean": v["sum"] / v["n"],
                     "max": v["max"]} for k, v in stats.items() if v["n"]}
        return table, violations

    def audit_soundness(self) -> Dict[str, Dict[str, float]]:
        """Assert ``combo_lower_bound <= measured total_s`` for every
        ``done`` row in this project; returns the per-kind tightness
        table on success.

        With the dryrun executor this checks the actual pruning
        certificate (bound and score share ``executor.hw``).  With a
        wallclock executor the bound models different units than the
        measurement, so the check is skipped for assertion purposes
        (pruning is force-disabled there anyway) and only the table is
        returned.
        """
        table, violations = self._bound_tightness()
        if violations and hasattr(self.executor, "hw"):
            lines = "; ".join(
                f"{seg}/{cid}: bound={b:.3e} > measured={t:.3e}"
                for seg, cid, b, t in violations[:10])
            raise AssertionError(
                f"combo_lower_bound overshoots {len(violations)} done "
                f"row(s) — pruning certificate broken: {lines}")
        return table

    # ------------------------------------------------------------------
    def _execute(self, segs: Sequence[Segment],
                 per_seg_combos: Dict[str, List[Combination]],
                 knob_points: Sequence[GlobalKnobs],
                 rep: SweepReport, *,
                 mesh_points: Optional[Sequence[MeshSpec]],
                 kernel_tuning=None,
                 static_checks: str = "off",
                 backend: str, workers: int,
                 remote_url: Optional[str],
                 remote_token: Optional[str], fallback: Optional[str],
                 retry, transient_retries: Optional[int], prune: bool,
                 prune_margin: float, use_cache: bool,
                 share_scores: bool, record_batch: int,
                 boundary_slack: bool = False):
        """Score everything not already settled (Continue mode):
        Scheduler -> ScoringBackend -> Recorder, with bounded
        Scheduler-level transient retry rounds (``scheduler.drive``)."""
        from repro.core.backends import (RetryPolicy, drive, env_key,
                                         shape_key)
        # ONE key pair for the whole pipeline: the Recorder writes cache
        # entries and the workers read them under the same sk/mk.  A
        # swept mesh point overrides mk per job (JobSpec.mesh_key).
        sk, mk = shape_key(self.shape), env_key(self.mesh, self.executor)
        scheduler = Scheduler(
            self.db, self.project, self.cfg, self.shape, self.mesh,
            self.executor, validate=self.validate,
            share_scores=share_scores, use_cache=use_cache,
            shape_key=sk, mesh_key=mk, boundary_slack=boundary_slack,
            kernel_tuning=kernel_tuning, static_checks=static_checks,
            # the mesh-devices rule asks THIS host: valid for every
            # backend that scores locally, never for a remote server
            static_devices=(backend != "remote"))
        recorder = Recorder(
            self.db, self.project, rep, shape_key=sk, mesh_key=mk,
            use_cache=use_cache, batch=record_batch)
        work = scheduler.build(segs, per_seg_combos, recorder,
                               knob_points=knob_points,
                               mesh_points=mesh_points)

        engine, transient_engine = self._engine(
            backend, workers=workers, remote_url=remote_url,
            remote_token=remote_token, fallback=fallback, retry=retry,
            prune=prune, prune_margin=prune_margin, use_cache=use_cache,
            shape_key=sk, mesh_key=mk)
        policy = retry if retry is not None else RetryPolicy()
        rounds = policy.sweep_retries if transient_retries is None \
            else transient_retries
        try:
            drive(engine, work, recorder, transient_retries=rounds)
        finally:
            # flush BEFORE closing: results already scored must land in
            # the DB even if the engine's teardown throws — and a failing
            # close must never eat the recorder flush (or vice versa)
            try:
                recorder.flush()
            finally:
                if transient_engine:
                    engine.close()

    # ------------------------------------------------------------------
    def _engine(self, backend: str, *, workers: int,
                remote_url: Optional[str], remote_token: Optional[str],
                fallback: Optional[str], retry, prune: bool,
                prune_margin: float, use_cache: bool,
                shape_key: str, mesh_key: str):
        """Build a ScoringBackend; cache process backends for warm-worker
        reuse.

        A process pool pays ~seconds of jax import per spawned worker, so
        it is kept alive across ``sweep()`` calls on one tuner (same
        engine parameters) and only torn down by :meth:`close`.  Thread/
        sequential/remote backends hold no local resources (the remote
        backend's warm pool lives server-side) and are built per sweep.
        Returns ``(engine, transient)``; transient engines are closed by
        the caller after the run.  A cached engine that survived an
        aborted sweep culls its dead workers on reuse (see
        ``ProcessBackend.run``)."""
        kw = dict(
            workers=workers, prune=prune, prune_margin=prune_margin,
            timeout_s=getattr(self.executor, "timeout_s", None),
            # workers get a read-only cache view only when the cache is
            # on — use_cache=False must force real recompiles everywhere
            db_path=self.db.path if use_cache else None,
            shape_key=shape_key, mesh_key=mesh_key, remote_url=remote_url,
            token=remote_token, retry=retry, fallback=fallback)
        if backend != "process":
            return make_backend(backend, self.executor, self.cfg,
                                self.shape, **kw), True
        key = (backend,) + tuple(sorted(kw.items()))
        engine = self._engines.get(key)
        if engine is None:
            engine = make_backend(backend, self.executor, self.cfg,
                                  self.shape, **kw)
            self._engines[key] = engine
        return engine, False

    def close(self):
        """Release cached scoring backends (warm process-worker pools).
        Idempotent and exception-safe: one backend's failing teardown
        never leaks the others' worker pools.  Also runs on GC and via
        the context-manager exit."""
        engines, self._engines = self._engines, {}
        first_err = None
        for engine in engines.values():
            try:
                engine.close()
            except Exception as e:           # keep releasing the rest
                log.warning("engine close failed: %s", e)
                if first_err is None:
                    first_err = e
        if first_err is not None:
            raise first_err

    def __enter__(self) -> "ComParTuner":
        return self

    def __exit__(self, *exc):
        self.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass

    # ------------------------------------------------------------------
    def baselines(self, knobs: GlobalKnobs = GlobalKnobs(), *,
                  global_space: Optional[Dict[str, Tuple]] = None):
        """Per-provider best uniform plans + the fused plan comparison
        (the numbers behind the Fig. 2/4 analogues).

        With ``global_space`` the baseline is per provider the best
        uniform plan over *any* swept knob point — the fair comparison
        against a joint-argmin fused plan.  Rows recorded by the pre-knob
        engine (no knob spec) count as the default point.  Rows from a
        swept ``mesh_space`` are grouped per mesh point (a uniform plan
        must live on ONE topology — mixing points across segments is not
        a realizable plan), and the baseline is the best over any
        point."""
        points = global_grid(global_space) if global_space is not None \
            else [knobs]
        kids = {kn.kid: kn for kn in points}
        segs = fragment(self.cfg)
        #: (mesh mid or "", knob kid) -> segment -> rows
        by_gid: Dict[Tuple[str, str],
                     Dict[str, List[Tuple[Combination, CostTerms]]]] = {}
        for r in self.db.results(self.project):
            if r["status"] != "done" or not r["cost"]:
                continue
            gid = (r["mesh"].mid if r["mesh"] is not None else "",
                   (r["knobs"] or GlobalKnobs()).kid)
            by_gid.setdefault(gid, {}).setdefault(r["segment"], []).append(
                (r["combo"], CostTerms.from_dict(r["cost"])))
        out = {}
        for pname in all_providers():
            best = None
            for (_, kid), rows in by_gid.items():
                kn = kids.get(kid)
                if kn is None:
                    continue
                per_seg = {s.name: [(c, t) for c, t in rows.get(s.name, [])
                                    if c.provider == pname] for s in segs}
                if not all(per_seg.values()):
                    continue
                try:
                    _, total = best_uniform(self.cfg, per_seg, kn)
                except ValueError:
                    continue
                if best is None or total < best:
                    best = total
            if best is not None:
                out[pname] = best
        return out
