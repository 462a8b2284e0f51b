"""Executors: score one combination on one segment (or a whole program).

* :class:`DryRunExecutor` — the production path on this CPU container:
  ``jit(...).lower(...).compile()`` + roofline terms from the compiled
  artifact (cost_analysis + HLO collective parsing).  Per-combination
  deadlines make a straggling compile a recorded failure instead of a
  sweep-blocker (ComPar rejects failed combinations the same way).
* :class:`WallClockExecutor` — ComPar's literal empirical loop: run the
  program and take the median wall-clock.  Used on CPU for small configs
  (tests, examples, benchmark suites).
"""
from __future__ import annotations

import signal
import threading
import time
from concurrent.futures import FIRST_COMPLETED, ThreadPoolExecutor, wait
from contextlib import contextmanager
from dataclasses import dataclass
from typing import Dict, Iterator, Optional, Sequence

import jax
import numpy as np

from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.combinator import Combination, GlobalKnobs
from repro.core.cost_model import CostTerms, Hardware, V5E, combo_lower_bound
from repro.core.segment import Segment
from repro.core.timer import segment_program
from repro.runtime.hlo import analyze_hlo


class CombinationFailed(Exception):
    """A combination could not be scored.

    ``transient`` distinguishes outcomes that depend on machine load or
    the time budget (deadline overruns, worker crashes) from deterministic
    failures (lowering / sharding errors).  Transient failures are
    retryable and must never enter the persistent score cache; the flag
    travels on the raising executor, so cacheability is decided where the
    failure happened instead of by substring-matching error text.
    """

    def __init__(self, msg: str = "", *, transient: bool = False):
        super().__init__(msg)
        self.transient = transient


@contextmanager
def deadline(seconds: Optional[int]):
    """Straggler guard.

    On the main thread: SIGALRM, which interrupts a hung compile.  Off the
    main thread (the worker-pool path) ``signal`` is unavailable
    (``ValueError: signal only works in main thread``), so we fall back to
    a soft deadline: the block runs to completion and is *then* failed if
    it overran — a straggler still becomes a recorded failure instead of a
    silent sweep-blocker.
    """
    if not seconds:
        yield
        return

    if threading.current_thread() is not threading.main_thread():
        # CPU time, not wall: with N workers sharing cores (and the GIL
        # during tracing), wall-clock would fail jobs at workers=N that
        # pass at workers=1.  Thread CPU time stays ~constant under
        # contention, keeping parallel and sequential sweeps in
        # agreement; it is lenient for XLA's internal threads, which is
        # the safe direction for a straggler guard.
        t0 = time.thread_time()
        yield
        if time.thread_time() - t0 > seconds:
            raise CombinationFailed(f"deadline {seconds}s exceeded (soft)",
                                    transient=True)
        return

    def handler(signum, frame):
        raise CombinationFailed(f"deadline {seconds}s exceeded",
                                transient=True)

    old = signal.signal(signal.SIGALRM, handler)
    signal.setitimer(signal.ITIMER_REAL, float(seconds))
    try:
        yield
    finally:
        signal.setitimer(signal.ITIMER_REAL, 0.0)
        signal.signal(signal.SIGALRM, old)


def lower_and_compile(fn, args, shardings, mesh, donate_argnums=()):
    kw = {}
    if mesh is not None and shardings is not None:
        kw["in_shardings"] = shardings
    if donate_argnums:
        kw["donate_argnums"] = tuple(donate_argnums)
    jitted = jax.jit(fn, **kw)
    if mesh is not None:
        with jax.set_mesh(mesh):
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
    else:
        lowered = jitted.lower(*args)
        compiled = lowered.compile()
    return lowered, compiled


def analyze_compiled(lowered, compiled, n_chips: int,
                     hw: Hardware = V5E) -> CostTerms:
    """Roofline terms from the compiled (post-SPMD, per-device) module.

    XLA:CPU's cost_analysis counts while bodies once, so we use the
    call-graph HLO walk (``runtime.hlo.analyze_hlo``) — trip-count-exact
    flops, an HBM-traffic byte estimator, and ring-factor collective
    bytes.  All per-device.
    """
    try:
        hlo = compiled.as_text()
    except Exception:
        hlo = lowered.as_text()
    res = analyze_hlo(hlo)
    f_pd, b_pd, c_pd = res["flops"], res["bytes"], res["collective"]
    ca = compiled.cost_analysis() or {}
    mem = {}
    try:
        ma = compiled.memory_analysis()
        mem = {"argument_bytes": getattr(ma, "argument_size_in_bytes", 0),
               "output_bytes": getattr(ma, "output_size_in_bytes", 0),
               "temp_bytes": getattr(ma, "temp_size_in_bytes", 0),
               "peak_bytes": (getattr(ma, "argument_size_in_bytes", 0)
                              + getattr(ma, "temp_size_in_bytes", 0))}
    except Exception:
        pass
    terms = CostTerms(
        compute_s=f_pd / hw.peak_flops,
        memory_s=b_pd / hw.hbm_bw,
        collective_s=c_pd / hw.link_bw,
        flops=f_pd * n_chips,
        bytes_accessed=b_pd * n_chips,
        collective_bytes=c_pd,
        bytes_per_device=mem.get("peak_bytes", 0))
    terms.detail.update({k: v for k, v in res.items()
                         if k.startswith("coll_")})
    terms.detail["xla_cost_analysis_flops"] = float(ca.get("flops", 0.0))
    terms.detail.update(mem)
    return terms


#: sentinel for "no per-job mesh override: use the executor's own mesh".
#: Distinct from ``None`` — a swept *local* point passes ``mesh=None``
#: explicitly to score meshless even on a fixed-mesh executor.
_OWN_MESH = object()


class DryRunExecutor:
    #: analytic scoring: concurrent workers don't perturb each other
    parallel_safe = True

    def __init__(self, mesh, hw: Hardware = V5E,
                 timeout_s: Optional[int] = 300):
        self.mesh = mesh
        self.hw = hw
        self.timeout_s = timeout_s
        self.n_chips = int(mesh.devices.size) if mesh is not None else 1

    @property
    def cache_tag(self) -> str:
        """Score-cache identity: scores from different executors (or
        hardware models) must never be served to each other."""
        return f"dryrun:{self.hw.name}"

    def score_segment(self, cfg: ArchConfig, shape: ShapeConfig,
                      seg: Segment, combo: Combination,
                      knobs: Optional[GlobalKnobs] = None,
                      mesh=_OWN_MESH) -> CostTerms:
        # ``mesh`` is the swept topology point's materialized mesh (the
        # mesh axis: one executor scores every point of a mesh_space);
        # left unset, the executor's fixed mesh applies
        mesh = self.mesh if mesh is _OWN_MESH else mesh
        n_chips = int(mesh.devices.size) if mesh is not None else 1
        # donation is part of the lowered program (buffer aliasing), so a
        # swept `donate` knob genuinely changes what is scored; safe here
        # because the dry-run path never executes the compiled artifact
        donate = (0,) if (shape.kind == "train" and knobs is not None
                          and knobs.donate) else ()
        with deadline(self.timeout_s):
            try:
                fn, args, shardings = segment_program(
                    cfg, shape, seg, combo, mesh, knobs=knobs)
                lowered, compiled = lower_and_compile(
                    fn, args, shardings, mesh, donate_argnums=donate)
            except CombinationFailed:
                raise
            except Exception as e:  # sharding/lowering failure = invalid combo
                raise CombinationFailed(f"{type(e).__name__}: {e}") from e
        return analyze_compiled(lowered, compiled, n_chips, self.hw)


class WallClockExecutor:
    """Empirical timing on the local device(s) — ComPar's measurement loop."""

    #: concurrent timed runs contend on the device and corrupt medians
    parallel_safe = False

    def __init__(self, mesh=None, repeats: int = 5,
                 timeout_s: Optional[int] = 120):
        self.mesh = mesh
        self.repeats = repeats
        self.timeout_s = timeout_s
        self.n_chips = int(mesh.devices.size) if mesh is not None else 1

    @property
    def cache_tag(self) -> str:
        # empirical timings are hardware identity, so the tag embeds the
        # local platform: two hosts sharing a score DB must never serve
        # each other wall-clock medians measured on different silicon.
        # (The analytic DryRunExecutor embeds its hw MODEL name instead —
        # its scores are platform-independent by construction.)
        return f"wallclock:r{self.repeats}:{jax.devices()[0].platform}"

    def score_segment(self, cfg: ArchConfig, shape: ShapeConfig,
                      seg: Segment, combo: Combination,
                      knobs: Optional[GlobalKnobs] = None,
                      mesh=_OWN_MESH) -> CostTerms:
        mesh = self.mesh if mesh is _OWN_MESH else mesh
        # NOTE: no buffer donation here — the timing loop re-calls the
        # compiled program with the same concrete buffers, and donated
        # arrays are deleted after the first call.  A swept `donate`
        # point therefore scores identically under wallclock (relevance
        # is over-inclusive, which costs a duplicate compile, never
        # correctness).
        with deadline(self.timeout_s):
            try:
                fn, args, shardings = segment_program(
                    cfg, shape, seg, combo, mesh, knobs=knobs)
                concrete = _materialize(args, shardings)
                lowered, compiled = lower_and_compile(
                    fn, concrete, shardings, mesh)
                out = compiled(*concrete)
                jax.block_until_ready(out)
                times = []
                for _ in range(self.repeats):
                    t0 = time.perf_counter()
                    out = compiled(*concrete)
                    jax.block_until_ready(out)
                    times.append(time.perf_counter() - t0)
            except CombinationFailed:
                raise
            except Exception as e:
                raise CombinationFailed(f"{type(e).__name__}: {e}") from e
        wall = float(np.median(times))
        t = CostTerms(compute_s=wall)
        t.detail["wall_s"] = wall
        return t


class SleepExecutor:
    """Deterministic straggler: sleeps ``sleep_s`` per job *without* arming
    the deadline — the stand-in for a hung native compile that SIGALRM
    cannot interrupt.  Exists to exercise the process backend's hard
    (kill-based) timeout in tests and CI; never used in real sweeps."""

    parallel_safe = True

    def __init__(self, sleep_s: float = 3600.0,
                 timeout_s: Optional[float] = None):
        self.sleep_s = sleep_s
        self.timeout_s = timeout_s
        self.n_chips = 1

    @property
    def cache_tag(self) -> str:
        return f"sleep:{self.sleep_s}"

    def score_segment(self, cfg: ArchConfig, shape: ShapeConfig,
                      seg: Segment, combo: Combination,
                      knobs: Optional[GlobalKnobs] = None,
                      mesh=None) -> CostTerms:
        time.sleep(self.sleep_s)
        return CostTerms(compute_s=self.sleep_s)


class CrashExecutor:
    """Kills its own process on every job — the stand-in for a segfaulting
    worker, used to exercise the process backend's crash detection and
    requeue-once-then-fail policy.  Never used in real sweeps."""

    parallel_safe = True

    def __init__(self, timeout_s: Optional[float] = None):
        self.timeout_s = timeout_s
        self.n_chips = 1

    @property
    def cache_tag(self) -> str:
        return "crash"

    def score_segment(self, cfg: ArchConfig, shape: ShapeConfig,
                      seg: Segment, combo: Combination,
                      knobs: Optional[GlobalKnobs] = None,
                      mesh=None) -> CostTerms:
        import os
        os._exit(13)


# --- parallel, pruning sweep runner -----------------------------------------

# One *unique* program to score; ``segments`` lists every segment name
# whose (segment, combination) rows share it.  The canonical dataclass
# lives in backends.base (it is also the process/remote wire format) —
# one type, so Scheduler-built jobs and hand-built jobs can never drift.
from repro.core.backends.base import JobSpec as SweepJob  # noqa: E402


@dataclass
class JobResult:
    job: SweepJob
    status: str                       # done | failed | pruned
    cost: Optional[CostTerms] = None
    error: str = ""
    transient: bool = False           # deadline/crash — retryable, uncacheable


class ParallelSweepRunner:
    """Fan unique (segment, combination) programs across a thread pool.

    * ``workers=1`` degrades to a plain in-thread loop (no pool overhead).
    * With ``prune=True``, each job first compares its analytic roofline
      lower bound (:func:`~repro.core.cost_model.combo_lower_bound`)
      against the incumbent best score of every member segment; a job
      whose bound already exceeds all incumbents is skipped as
      ``pruned`` — exact, since bound <= true score (see cost_model).
      Jobs are dispatched cheapest-bound-first so incumbents tighten
      early.  ``prune_margin`` demands the bound exceed the incumbent by
      a relative margin before pruning (safety headroom).
    * Per-worker timeouts come from the wrapped executor's ``deadline``;
      off the main thread that is a soft deadline (see :func:`deadline`).
    """

    def __init__(self, executor, cfg: ArchConfig, shape: ShapeConfig, *,
                 workers: int = 1, prune: bool = False,
                 prune_margin: float = 0.1):
        # the exactness-critical prune predicate lives in ONE place
        # (backends.base.IncumbentTracker), shared with the process
        # backend so all backends prune — and therefore fuse — identically
        from repro.core.backends.base import IncumbentTracker
        self.executor = executor
        self.cfg = cfg
        self.shape = shape
        self.workers = max(1, int(workers))
        self.prune = prune
        self.prune_margin = prune_margin
        self.tracker = IncumbentTracker(prune, prune_margin)

    # ------------------------------------------------------------------
    def _pruned(self, job: SweepJob) -> bool:
        return self.tracker.pruned(job)

    def _observe(self, segments: Sequence[str], total_s: float):
        self.tracker.observe(segments, total_s)

    def _run_job(self, job: SweepJob) -> JobResult:
        if self._pruned(job):
            return JobResult(job, "pruned",
                             error=f"lower bound {job.bound_s:.3e}s > "
                                   f"incumbent best")
        kw = {}
        if job.mesh is not None:
            # a swept mesh point: materialize it (memoized per process —
            # many jobs share a point) and build under it instead of the
            # executor's own mesh.  Only passed when present, so
            # hand-built executors without the parameter stay usable.
            from repro.core.meshspec import MeshUnsatisfiable, cached_mesh
            try:
                kw["mesh"] = cached_mesh(job.mesh)
            except MeshUnsatisfiable as e:
                # environment-dependent, not a verdict on the combination:
                # another host (or a bigger device count) may satisfy it
                return JobResult(job, "failed", error=str(e), transient=True)
        try:
            cost = self.executor.score_segment(
                self.cfg, self.shape, job.seg, job.combo, knobs=job.knobs,
                **kw)
        except CombinationFailed as e:
            return JobResult(job, "failed", error=str(e),
                             transient=getattr(e, "transient", False))
        except Exception as e:
            # an analysis bug must fail the row, not abort the sweep (an
            # escaping exception would drop the tuner's buffered batches)
            return JobResult(job, "failed", error=f"{type(e).__name__}: {e}")
        self._observe(job.segments, cost.total_s)
        return JobResult(job, "done", cost=cost)

    # ------------------------------------------------------------------
    def run(self, jobs: Sequence[SweepJob],
            incumbents: Optional[Dict[str, float]] = None
            ) -> Iterator[JobResult]:
        """Yield a :class:`JobResult` per job as each completes.

        ``incumbents``: segment name -> best known total_s, used to seed
        pruning (cache hits, Continue-mode rows)."""
        self.tracker.seed(incumbents)
        n_chips = getattr(self.executor, "n_chips", 1)
        hw = getattr(self.executor, "hw", V5E)
        mesh = getattr(self.executor, "mesh", None)
        fixed_axes = dict(zip(mesh.axis_names, mesh.devices.shape)) \
            if mesh is not None else None
        for job in jobs:
            if job.bound_s <= 0.0:      # Scheduler-built jobs arrive bounded
                job.bound_s = combo_lower_bound(
                    self.cfg, self.shape, job.seg, job.combo,
                    job.mesh.n_devices if job.mesh is not None else n_chips,
                    hw, knobs=job.knobs,
                    mesh_axes=job.mesh.axis_sizes()
                    if job.mesh is not None else fixed_axes)
        ordered = sorted(jobs, key=lambda j: (j.bound_s, j.key))

        if self.workers == 1:
            for job in ordered:
                yield self._run_job(job)
            return

        with ThreadPoolExecutor(max_workers=self.workers) as pool:
            pending = {pool.submit(self._run_job, j) for j in ordered}
            while pending:
                finished, pending = wait(pending,
                                         return_when=FIRST_COMPLETED)
                for fut in finished:
                    yield fut.result()


def _stand_in(shape, dtype):
    if np.issubdtype(dtype, np.integer):
        return jax.numpy.zeros(shape, dtype)
    return (jax.random.normal(jax.random.key(42), shape, "float32") * 0.02
            ).astype(dtype)


def _materialize(args, shardings):
    """Concrete stand-ins for abstract ``args``, each leaf built directly
    under its sharding (``shardings`` is a prefix tree of ``args``; None
    = default device), so a sharded program never starts from a whole
    copy on one device."""
    def make(sds, sharding):
        return jax.jit(_stand_in, static_argnums=(0, 1),
                       out_shardings=sharding)(tuple(sds.shape),
                                               np.dtype(sds.dtype))

    return jax.tree.map(
        lambda sh, sub: jax.tree.map(lambda s: make(s, sh), sub),
        shardings, args,
        is_leaf=lambda x: x is None or isinstance(x, jax.sharding.Sharding))
