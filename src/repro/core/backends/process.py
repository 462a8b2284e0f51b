"""Process backend: score unique programs in spawned worker processes.

Why a second backend exists at all: thread workers are GIL-bound during
jax tracing (compilation releases the GIL, tracing does not — tiny smoke
programs are tracing-dominated), and the off-main-thread deadline is
*soft*: a hung XLA compile still occupies its thread forever.  Spawned
workers fix both — true parallel tracing, and two layers of deadline:

* **in-worker hard deadline** — jobs run on the worker process's main
  thread, so the executor's SIGALRM deadline actually interrupts a hung
  Python-level compile (graceful: the worker reports a transient failure
  and stays warm);
* **parent-side kill** — the backstop for hangs SIGALRM cannot reach
  (native code that never returns to the interpreter): a worker busy past
  ``timeout_s`` wall-clock is terminated, the job is requeued once onto
  another worker, and on a second loss recorded as a **transient**
  failure.  The sweep can never hang on one combination.

Worker lifecycle: workers are warm (one jax import + executor per
process, reused across jobs AND across successive ``run()`` calls — the
pool is only torn down by ``close()``), crash-detected (an exiting worker
fails its job through the same requeue-once-then-fail policy), and
replaced lazily while work remains.  Each worker holds a read-only view of the score
cache (``ScoreCacheReader`` on the WAL DB), so groups another sweep
process scored mid-run are served without compiling.

Everything crosses the process boundary as the JSON wire format of
``backends.base`` (JobSpec / JobOutcome + arch/shape registry specs) —
exactly what a remote/HTTP backend will speak next.
"""
from __future__ import annotations

import logging
import multiprocessing as mp
import multiprocessing.connection
import os
import time
from collections import deque
from typing import Dict, Iterator, List, Optional, Sequence, Set, Tuple

from repro.core.backends.base import (FAILED, PRUNED, DONE, IncumbentTracker,
                                      JobOutcome, JobSpec, RetryPolicy,
                                      ScoringBackend, executor_from_spec,
                                      executor_to_spec)

log = logging.getLogger("repro.backends.process")

_POLL_S = 0.05          # parent event-loop tick
_SPAWN_TIMEOUT_S = 120  # budget for a worker to import jax and report ready

#: set once the forkserver has been asked to preload jax (the request is
#: sticky for the life of the forkserver process, so asking again is
#: pointless — and ignored by the stdlib once the server is running)
_FORKSERVER_PRELOADED = False


def _resolve_ctx(start_method: str):
    """Resolve a start-method name to a multiprocessing context.

    ``"auto"`` prefers **forkserver** with jax preloaded into the server
    process: the stdlib forkserver imports the preload list once, and
    every worker then *forks* from that warm interpreter — spawning a
    worker costs a fork plus executor construction instead of a cold
    multi-second jax import.  (Preloading only imports jax; backends
    initialize lazily in each worker, so the fork never clones live
    device state.)  Platforms without forkserver fall back to plain
    ``"spawn"``.  Explicit method names pass through unchanged, so
    ``start_method="spawn"`` still means spawn.
    """
    global _FORKSERVER_PRELOADED
    if start_method != "auto":
        return mp.get_context(start_method)
    if "forkserver" not in mp.get_all_start_methods():
        return mp.get_context("spawn")
    ctx = mp.get_context("forkserver")
    if not _FORKSERVER_PRELOADED:
        try:
            ctx.set_forkserver_preload(["jax"])
            _FORKSERVER_PRELOADED = True
        except Exception as e:     # pragma: no cover - stdlib quirk
            log.debug("forkserver preload unavailable: %s", e)
    return ctx


# --- worker side -------------------------------------------------------------

def _score_one(executor, cfg, shape, spec: JobSpec, cache, shape_key: str,
               mesh_key: str) -> JobOutcome:
    from repro.core.executor import CombinationFailed
    # a mesh-axis job carries its own cache environment column; the init
    # message's mesh_key covers fixed-mesh/local jobs
    env = spec.mesh_key or mesh_key
    if cache is not None and spec.signature:
        hit = cache.get(spec.signature, shape_key, env, spec.eff_cid)
        if hit is not None and hit["status"] in (DONE, FAILED):
            return JobOutcome(spec.key, hit["status"], cost=hit["cost"],
                              error=hit["error"], cached=True)
    kw = {}
    if spec.mesh is not None:
        # the swept topology point: THIS worker materializes the spec
        # against its own local devices (memoized across its jobs)
        from repro.core.meshspec import MeshUnsatisfiable, cached_mesh
        try:
            kw["mesh"] = cached_mesh(spec.mesh)
        except MeshUnsatisfiable as e:
            # environment-dependent (another host may have the devices):
            # transient, so it is retryable and never cached
            return JobOutcome(spec.key, FAILED, error=str(e), transient=True,
                              kind="mesh")
    try:
        cost = executor.score_segment(cfg, shape, spec.seg, spec.combo,
                                      knobs=spec.knobs, **kw)
    except CombinationFailed as e:
        transient = getattr(e, "transient", False)
        return JobOutcome(spec.key, FAILED, error=str(e),
                          transient=transient,
                          kind="deadline" if transient else "")
    except Exception as e:
        # an analysis bug must fail the row, not kill the worker
        return JobOutcome(spec.key, FAILED,
                          error=f"{type(e).__name__}: {e}")
    return JobOutcome(spec.key, DONE, cost=cost.as_dict())


def _worker_main(conn, init: Dict):
    """Worker process entry point: build cfg/shape/executor once (warm
    reuse), then serve JobSpec JSON until a ``None`` shutdown message.

    Workers score analytically and never claim an accelerator: on a TPU
    host the chip belongs to the parent, so the worker (and anything it
    starts) is pinned to the CPU backend before any backend initializes.
    """
    import jax
    os.environ["JAX_PLATFORMS"] = "cpu"
    jax.config.update("jax_platforms", "cpu")
    from repro.configs.registry import arch_from_spec, shape_from_spec
    from repro.core.db import ScoreCacheReader
    cfg = arch_from_spec(init["arch"])
    shape = shape_from_spec(init["shape"])
    # allow_test: a local worker trusts its parent process (the
    # fault-injection executors exist for the backend's own tests)
    executor = executor_from_spec(init["executor"], allow_test=True)
    cache = ScoreCacheReader(init["db_path"]) if init.get("db_path") else None
    conn.send({"ready": True})
    try:
        while True:
            msg = conn.recv()
            if msg is None:
                return
            spec = JobSpec.from_json(msg)
            out = _score_one(executor, cfg, shape, spec, cache,
                             init.get("shape_key", ""),
                             init.get("mesh_key", ""))
            conn.send(out.to_json())
    except (EOFError, KeyboardInterrupt):
        return
    finally:
        if cache is not None:
            cache.close()


# --- parent side -------------------------------------------------------------

class _Worker:
    __slots__ = ("proc", "conn", "job", "started", "spawned", "ready", "wid")

    def __init__(self, proc, conn, wid: int):
        self.proc = proc
        self.conn = conn
        self.wid = wid
        self.job: Optional[JobSpec] = None
        self.started: float = 0.0
        self.spawned: float = time.monotonic()
        self.ready = False


class ProcessBackend(ScoringBackend):
    """Score jobs on a pool of spawned worker processes with hard
    preemptive per-job timeouts and requeue-once-then-fail recovery."""

    name = "process"
    #: dispatches per job before a loss becomes a transient failure
    max_attempts = 2
    #: parent kills at timeout_s * (1 + grace): the worker's in-process
    #: SIGALRM fires at timeout_s and reports gracefully (keeping the
    #: worker warm); the parent kill is the backstop for native hangs
    kill_grace = 0.2

    def __init__(self, executor, cfg, shape, *, workers: int = 2,
                 prune: bool = False, prune_margin: float = 0.1,
                 timeout_s: Optional[float] = None,
                 db_path: Optional[str] = None,
                 shape_key: str = "", mesh_key: str = "",
                 start_method: str = "auto",
                 retry: Optional[RetryPolicy] = None,
                 fault_plan=None):
        from repro.configs.registry import arch_to_spec, shape_to_spec
        self.workers = max(1, int(workers))
        self.timeout_s = timeout_s
        # the unified retry contract: how many dispatches a job gets
        # before a loss becomes a transient failure
        if retry is not None:
            self.max_attempts = max(1, retry.max_attempts)
        #: FaultPlan consulted at "process.kill_worker" after each
        #: dispatch (tests only; None in production = one branch per job)
        self.fault_plan = fault_plan
        self.prune = prune
        self.prune_margin = prune_margin
        self.tracker = IncumbentTracker(prune, prune_margin)
        self._ctx = _resolve_ctx(start_method)
        self._pool: List[_Worker] = []
        self._next_wid = 0
        self._deaths = 0            # workers lost (crash or kill)
        #: (job key, worker id) per successful dispatch of the last run —
        #: the observable record of the requeue-diversification policy
        self.dispatch_log: List[Tuple[str, int]] = []
        self._init = {
            "executor": executor_to_spec(executor),
            "arch": arch_to_spec(cfg),
            "shape": shape_to_spec(shape),
            "db_path": db_path if db_path and db_path != ":memory:" else None,
            "shape_key": shape_key,
            "mesh_key": mesh_key,
        }

    # ------------------------------------------------------------------
    def _spawn(self) -> _Worker:
        parent, child = self._ctx.Pipe(duplex=True)
        proc = self._ctx.Process(target=_worker_main,
                                 args=(child, self._init), daemon=True)
        proc.start()
        child.close()
        w = _Worker(proc, parent, self._next_wid)
        self._next_wid += 1
        self._pool.append(w)
        return w

    def _kill(self, w: _Worker):
        if w in self._pool:
            self._pool.remove(w)
        try:
            w.proc.terminate()
            w.proc.join(timeout=5)
            if w.proc.is_alive():
                w.proc.kill()
                w.proc.join(timeout=5)
        finally:
            try:
                w.conn.close()
            except OSError:
                pass
        self._deaths += 1

    def warmup(self, timeout_s: float = _SPAWN_TIMEOUT_S):
        """Spawn the full pool and block until every worker reports
        ready (jax imported, executor built).  Optional — ``run`` spawns
        lazily — but lets callers keep worker start-up out of timing
        windows."""
        while len(self._pool) < self.workers:
            self._spawn()
        t0 = time.monotonic()
        while any(not w.ready for w in self._pool):
            if time.monotonic() - t0 > timeout_s:
                self.close()        # don't leak the healthy workers
                raise RuntimeError("process-backend worker failed to start "
                                   f"within {timeout_s}s")
            for w in list(self._pool):
                if not w.ready and not w.proc.is_alive():
                    wid, code = w.wid, w.proc.exitcode
                    self.close()
                    raise RuntimeError(
                        f"worker {wid} died during startup (exit {code})")
            self._drain_messages(block_s=_POLL_S)

    # ------------------------------------------------------------------
    def _drain_messages(self, block_s: float = _POLL_S) -> List[JobOutcome]:
        """Receive ready-pings and outcomes from every live worker."""
        outcomes: List[JobOutcome] = []
        conns = {w.conn: w for w in self._pool}
        if not conns:
            time.sleep(block_s)
            return outcomes
        for conn in mp.connection.wait(list(conns), timeout=block_s):
            w = conns[conn]
            try:
                msg = conn.recv()
            except (EOFError, OSError):
                continue        # death handled by the liveness check
            if isinstance(msg, dict) and msg.get("ready"):
                w.ready = True
                continue
            out = JobOutcome.from_json(msg)
            if out.status == DONE and out.cost and w.job is not None:
                from repro.core.cost_model import CostTerms
                self.tracker.observe(w.job.segments,
                                     CostTerms.from_dict(out.cost).total_s)
            w.job = None
            outcomes.append(out)
        return outcomes

    def _lose(self, w: _Worker, reason: str, queue, attempts, excluded,
              kind: str = "crash") -> Optional[JobOutcome]:
        """A busy worker died or was killed: requeue its job until the
        retry policy's ``max_attempts`` is burned, then fail it as
        transient.  The lost worker's id joins the job's excluded set so
        the retry is never dispatched back to it (or to whatever
        inherits its id) — the retry must diversify, not burn itself on
        the same slot that just died."""
        job = w.job
        self._kill(w)
        excluded.setdefault(job.key, set()).add(w.wid)
        attempts[job.key] = attempts.get(job.key, 0) + 1
        if attempts[job.key] >= self.max_attempts:
            log.warning("job %s lost %d times (%s): transient failure",
                        job.key, attempts[job.key], reason)
            return JobOutcome(job.key, FAILED, error=f"{reason}; requeue "
                              "limit reached", transient=True,
                              attempts=attempts[job.key], kind=kind)
        log.warning("job %s lost (%s): requeued", job.key, reason)
        queue.appendleft(job)
        return None

    def _next_job(self, w: _Worker, queue, excluded: Dict[str, Set[int]],
                  attempts: Dict[str, int]
                  ) -> Tuple[Optional[JobSpec], List[JobOutcome]]:
        """Pop the first job dispatchable to ``w``: pruned jobs are
        settled on the spot (returned for yielding), jobs excluded on
        ``w`` — they already died in its hands once — stay queued for a
        different worker."""
        pruned: List[JobOutcome] = []
        skipped: List[JobSpec] = []
        job = None
        while queue:
            j = queue.popleft()
            if self.tracker.pruned(j):
                pruned.append(JobOutcome(
                    j.key, PRUNED,
                    error=f"lower bound {j.bound_s:.3e}s > incumbent best",
                    attempts=attempts.get(j.key, 0) + 1))
                continue
            if w.wid in excluded.get(j.key, ()):
                skipped.append(j)
                continue
            job = j
            break
        for j in reversed(skipped):
            queue.appendleft(j)
        return job, pruned

    def _dispatch(self, w: _Worker, job: JobSpec, queue) -> bool:
        """Send ``job`` to ``w``; on a dead pipe the job goes back to the
        queue attempt-free (it never started) and the worker is culled."""
        try:
            w.conn.send(job.to_json())
        except (OSError, ValueError):
            queue.appendleft(job)
            self._kill(w)
            return False
        w.job = job
        w.started = time.monotonic()
        self.dispatch_log.append((job.key, w.wid))
        if self.fault_plan is not None and \
                self.fault_plan.fires("process.kill_worker") is not None:
            # chaos: the worker dies holding the job it just accepted —
            # the liveness check sees the crash and requeues per policy
            log.warning("fault injection: killing worker %d holding %s",
                        w.wid, job.key)
            w.proc.terminate()
        return True

    # ------------------------------------------------------------------
    def run(self, jobs: Sequence[JobSpec],
            incumbents: Optional[Dict[str, float]] = None
            ) -> Iterator[JobOutcome]:
        """Score ``jobs``; the worker pool survives the call.

        Successive ``run()`` calls on one backend reuse the warm workers
        (jax already imported, executor built) — that is what keeps the
        outer knob axis, and repeated sweeps through a cached tuner
        engine, from paying the ~seconds-per-worker spawn cost per call.
        Incumbents do NOT carry over: each run gets a fresh tracker
        seeded only from its own ``incumbents``, so a previous sweep's
        bests can never prune this one's rows.
        """
        # engine-reuse hygiene: a previous run that ended in an error or
        # an abandoned generator can leave dead workers in the pool —
        # cull them before they can swallow this run's dispatches
        for w in list(self._pool):
            if not w.proc.is_alive():
                self._kill(w)
        self.tracker = IncumbentTracker(self.prune, self.prune_margin)
        self.tracker.seed(incumbents)
        self._deaths = 0
        self.dispatch_log = []
        queue = deque(jobs)
        attempts: Dict[str, int] = {}
        excluded: Dict[str, Set[int]] = {}
        death_budget = 2 * self.workers + self.max_attempts * len(queue) + 4
        try:
            while queue or any(w.job is not None for w in self._pool):
                # keep the pool at strength while work remains
                busy = sum(1 for w in self._pool if w.job is not None)
                need = min(self.workers, busy + len(queue))
                while len(self._pool) < need:
                    self._spawn()

                # dispatch to ready idle workers, oldest-spawned first
                # (pruning at dispatch time, same as the thread runner's
                # job-start check).  A requeued job skips workers in its
                # excluded set — the retry prefers a proven survivor
                # over the worker (or slot) it just died on.
                idle = [w for w in self._pool if w.job is None and w.ready]
                idle.sort(key=lambda w: (w.spawned, w.wid))
                dispatched = False
                for w in idle:
                    job, pruned_outs = self._next_job(w, queue, excluded,
                                                      attempts)
                    for out in pruned_outs:
                        yield out
                    if job is None:
                        continue
                    if self._dispatch(w, job, queue):
                        dispatched = True
                if (queue and not dispatched
                        and not any(w.job is not None for w in self._pool)
                        and any(w.job is None and w.ready and w in self._pool
                                for w in idle)):
                    # every idle worker is excluded for every queued job
                    # and nothing is in flight.  Under the kill-on-loss
                    # policy excluded ids are always dead, so this can't
                    # trigger — but exclusion must degrade to a dispatch,
                    # never to a stalled sweep.
                    w = next(w for w in idle
                             if w.job is None and w.ready and w in self._pool)
                    self._dispatch(w, queue.popleft(), queue)

                for out in self._drain_messages():
                    out.attempts = attempts.get(out.key, 0) + 1
                    yield out

                now = time.monotonic()
                kill_after = self.timeout_s * (1.0 + self.kill_grace) \
                    if self.timeout_s else None
                for w in list(self._pool):
                    if w.job is None:
                        if not w.proc.is_alive():
                            self._kill(w)       # idle death: just cull
                        elif not w.ready and \
                                now - w.spawned > _SPAWN_TIMEOUT_S:
                            # hung during init (never sent ready): the
                            # startup path is covered by the no-hang
                            # guarantee too
                            log.warning("worker %d hung during startup; "
                                        "killed", w.wid)
                            self._kill(w)
                        continue
                    if kill_after and now - w.started > kill_after:
                        out = self._lose(
                            w, f"hard deadline {self.timeout_s}s exceeded "
                               f"(worker {w.wid} killed)", queue, attempts,
                            excluded, kind="deadline")
                        if out is not None:
                            yield out
                    elif not w.proc.is_alive():
                        out = self._lose(
                            w, f"worker {w.wid} crashed "
                               f"(exit {w.proc.exitcode})", queue, attempts,
                            excluded, kind="crash")
                        if out is not None:
                            yield out
                if self._deaths > death_budget:
                    raise RuntimeError(
                        f"process backend lost {self._deaths} workers; "
                        "giving up instead of respawning forever")
        finally:
            # keep the pool warm for the next run(); but if the caller
            # abandoned the generator mid-run (break / error), workers
            # still holding jobs would poison the next call — cull them
            for w in [w for w in self._pool if w.job is not None]:
                self._kill(w)

    # ------------------------------------------------------------------
    def close(self):
        for w in list(self._pool):
            try:
                if w.ready and w.job is None and w.proc.is_alive():
                    w.conn.send(None)           # graceful shutdown
            except (OSError, BrokenPipeError):
                pass
        deadline = time.monotonic() + 2.0
        for w in list(self._pool):
            w.proc.join(timeout=max(0.0, deadline - time.monotonic()))
            if w.proc.is_alive():
                w.proc.terminate()
                w.proc.join(timeout=2)
            try:
                w.conn.close()
            except OSError:
                pass
        self._pool = []
