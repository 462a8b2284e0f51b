"""The parallel / cached / pruned sweep engine.

Invariants: parallel == sequential, cached == fresh (identical CostTerms,
zero recompiles), pruning never changes the fused plan, Continue mode
resumes without recompiling, and the DB/deadline satellite fixes hold.
Backend suite: sequential, thread and process backends fuse byte-identical
plans; a hung process worker is killed by the hard timeout.
"""
import json
import threading
import time

import pytest

from repro.configs import get_arch, get_shape
from repro.core import ComParTuner, SweepDB
from repro.core.combinator import Combination
from repro.core.cost_model import CostTerms, combo_lower_bound
from repro.core.executor import CombinationFailed, deadline
from repro.core.segment import Segment, fragment
from repro.models.context import SegmentClause


def _plan_bytes(plan):
    """Byte-identity of the fused decisions: per-segment combinations AND
    the chosen knob point (the joint-argmin output)."""
    d = plan.to_json()
    return json.dumps({"segments": d["segments"], "knobs": d["knobs"]},
                      sort_keys=True).encode()

SPACE = {"remat": ("none", "full"), "kernel": ("xla",), "block_q": (16, 32),
         "block_k": (16,), "scan_unroll": (1,), "mlstm_chunk": (16,)}


def _tuner(db, project, mode="new", **kw):
    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    return ComParTuner(cfg, shape, mesh=None, db=db, project=project,
                       mode=mode, executor="dryrun", timeout_s=120), cfg, shape


def _sweep(tuner, **kw):
    return tuner.sweep(providers=["tensor_par", "fsdp"], clause_space=SPACE,
                       max_flags=1, **kw)


@pytest.fixture(scope="module")
def sequential():
    db = SweepDB(":memory:")
    tuner, cfg, shape = _tuner(db, "seq")
    plan, rep = _sweep(tuner, workers=1, use_cache=False, prune=False)
    return plan, rep


def test_parallel_agrees_with_sequential(sequential):
    plan_seq, rep_seq = sequential
    db = SweepDB(":memory:")
    tuner, _, _ = _tuner(db, "par")
    plan_par, rep_par = _sweep(tuner, workers=4, use_cache=False, prune=False)
    assert plan_par.segments == plan_seq.segments
    assert rep_par.n_done == rep_seq.n_done
    assert rep_par.n_failed == rep_seq.n_failed == 0


def test_structural_sharing_compiles_unique_programs_once(sequential):
    _, rep = sequential
    # with no mesh all providers/flags collapse per segment-relevant clause:
    # far fewer compiles than rows, and every row still gets a result
    assert rep.n_scored < rep.n_combinations
    assert rep.n_scored + rep.n_shared == rep.n_done


def test_cache_hits_return_identical_costterms(sequential, tmp_path):
    plan1, rep1 = sequential
    db = SweepDB(str(tmp_path / "sweep.db"))
    t1, _, _ = _tuner(db, "c1")
    plan_a, rep_a = _sweep(t1, use_cache=True)
    assert rep_a.n_cached == 0
    t2, _, _ = _tuner(db, "c2")
    plan_b, rep_b = _sweep(t2, use_cache=True)
    # second sweep of the same config recompiles NOTHING
    assert rep_b.n_scored == 0
    assert rep_b.n_cached == rep_b.n_combinations
    assert plan_b.segments == plan_a.segments == plan1.segments
    # identical CostTerms row-for-row
    rows_a = {(r["segment"], r["cid"]): r["cost"]
              for r in db.results("c1") if r["status"] == "done"}
    rows_b = {(r["segment"], r["cid"]): r["cost"]
              for r in db.results("c2") if r["status"] == "done"}
    assert rows_a.keys() == rows_b.keys() and len(rows_a) > 0
    for k, cost in rows_a.items():
        assert CostTerms.from_dict(cost).as_dict() == \
            CostTerms.from_dict(rows_b[k]).as_dict()


def test_cache_survives_reopen(tmp_path):
    path = str(tmp_path / "sweep.db")
    t1, _, _ = _tuner(SweepDB(path), "p1")
    _sweep(t1, use_cache=True)
    t2, _, _ = _tuner(SweepDB(path), "p2")   # fresh connection
    _, rep = _sweep(t2, use_cache=True)
    assert rep.n_scored == 0
    assert rep.n_cached == rep.n_combinations


def test_pruning_never_changes_the_plan(sequential):
    plan_seq, rep_seq = sequential
    db = SweepDB(":memory:")
    tuner, _, _ = _tuner(db, "pr")
    plan_pr, rep_pr = _sweep(tuner, workers=2, use_cache=False, prune=True,
                             prune_margin=0.0)
    assert plan_pr.segments == plan_seq.segments
    # every registered row is settled one way or another
    assert (rep_pr.n_done + rep_pr.n_failed + rep_pr.n_pruned
            == rep_pr.n_combinations)


def test_continue_mode_resumes_without_recompiling():
    db = SweepDB(":memory:")
    t1, _, _ = _tuner(db, "r", mode="new")
    plan1, rep1 = _sweep(t1, use_cache=False)
    assert rep1.n_scored > 0
    t2, _, _ = _tuner(db, "r", mode="continue")
    plan2, rep2 = _sweep(t2, use_cache=False)
    assert rep2.n_scored == 0            # all rows settled -> nothing to do
    assert rep2.n_done == rep1.n_done
    assert plan2.segments == plan1.segments


def test_lower_bound_is_below_measured_score(sequential):
    """The pruning certificate: bound <= true score for every scored row."""
    _, rep = sequential
    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    segs = {s.name: s for s in fragment(cfg)}
    checked = 0
    for sname, rows in rep.per_segment.items():
        for combo, cost in rows:
            lb = combo_lower_bound(cfg, shape, segs[sname], combo)
            assert lb <= cost.total_s + 1e-12, (sname, combo.label())
            checked += 1
    assert checked > 0


def test_segment_signature_structural_identity():
    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    a = Segment("g0", "stack", ("attn",), 2)
    b = Segment("g7", "stack", ("attn",), 2)      # same structure, new name
    c = Segment("g1", "stack", ("attn", "rec"), 2)
    assert a.signature(cfg, shape) == b.signature(cfg, shape)
    assert a.signature(cfg, shape) != c.signature(cfg, shape)
    # arch name is excluded; arch *fields* are not
    import dataclasses
    renamed = dataclasses.replace(cfg, name="other")
    wider = dataclasses.replace(cfg, d_model=cfg.d_model * 2)
    assert a.signature(renamed, shape) == a.signature(cfg, shape)
    assert a.signature(wider, shape) != a.signature(cfg, shape)


def test_relevant_clause_fields():
    embed = Segment("embed", "embed")
    head = Segment("head", "head")
    attn = Segment("g0", "stack", ("attn",), 2)
    moe = Segment("g0", "stack", ("attn_moe",), 2)
    rec = Segment("g0", "stack", ("rec",), 2)
    assert embed.relevant_clause_fields("train") == frozenset()
    assert head.relevant_clause_fields("train") == frozenset()
    assert {"remat", "kernel", "block_q"} <= attn.relevant_clause_fields("train")
    assert "cache_upcast" in attn.relevant_clause_fields("decode")
    assert "cache_upcast" not in attn.relevant_clause_fields("train")
    assert "moe_dispatch" in moe.relevant_clause_fields("train")
    assert "mlstm_chunk" in rec.relevant_clause_fields("train")


def test_irrelevant_clause_fields_share_scores(sequential):
    """Exactness of the projection: head-segment scores must be identical
    across combos that differ only in stack-only clause fields."""
    _, rep = sequential
    head_rows = rep.per_segment["head"]
    totals = {c.cid: t.total_s for c, t in head_rows}
    assert len(totals) > 1
    assert len(set(totals.values())) == 1


def test_cache_is_keyed_by_executor(tmp_path):
    """Analytic dry-run scores must never be served to a wall-clock sweep
    sharing the same DB file (and vice versa)."""
    db = SweepDB(str(tmp_path / "sweep.db"))
    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    space = {"remat": ("none",), "kernel": ("xla",), "block_q": (16,),
             "block_k": (16,), "scan_unroll": (1,), "mlstm_chunk": (16,)}
    t1 = ComParTuner(cfg, shape, mesh=None, db=db, project="dry",
                     mode="new", executor="dryrun", timeout_s=120)
    t1.sweep(providers=["fsdp"], clause_space=space, max_flags=0)
    t2 = ComParTuner(cfg, shape, mesh=None, db=db, project="wall",
                     mode="new", executor="wallclock", timeout_s=120)
    _, rep = t2.sweep(providers=["fsdp"], clause_space=space, max_flags=0)
    assert rep.n_cached == 0 and rep.n_scored > 0


def test_prune_disabled_under_boundary_cost_fusion():
    """The lower-bound certificate covers per-segment argmin only; under
    Viterbi fusion pruning must be switched off."""
    db = SweepDB(":memory:")
    tuner, _, _ = _tuner(db, "bc")
    plan, rep = _sweep(tuner, prune=True, boundary_costs=True,
                       use_cache=False)
    assert rep.n_pruned == 0
    assert plan.meta["fusion"] == "viterbi-boundary"


def test_wallclock_clamps_workers(monkeypatch):
    """Concurrent timed runs contend on the device: a wallclock sweep must
    run its measurements sequentially even if workers>1 is requested."""
    from repro.core import executor as E
    seen = {}
    orig = E.ParallelSweepRunner.__init__

    def spy(self, ex, cfg, shape, *, workers=1, **kw):
        seen["workers"] = workers
        orig(self, ex, cfg, shape, workers=workers, **kw)

    monkeypatch.setattr(E.ParallelSweepRunner, "__init__", spy)
    import repro.core.tuner as T
    monkeypatch.setattr(T, "ParallelSweepRunner", E.ParallelSweepRunner)
    db = SweepDB(":memory:")
    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    space = {"remat": ("none",), "kernel": ("xla",), "block_q": (16,),
             "block_k": (16,), "scan_unroll": (1,), "mlstm_chunk": (16,)}
    t = ComParTuner(cfg, shape, mesh=None, db=db, project="wc",
                    mode="new", executor="wallclock", timeout_s=120)
    t.sweep(providers=["fsdp"], clause_space=space, max_flags=0,
            workers=8, use_cache=False)
    assert seen["workers"] == 1


@pytest.mark.parametrize("backend", ["process", "remote"])
def test_wallclock_refuses_out_of_process_backends(backend):
    """A chip belongs to the process holding it: a wallclock sweep must
    not hand its timings to spawned workers or a scoring server."""
    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    t = ComParTuner(cfg, shape, mesh=None, db=SweepDB(":memory:"),
                    project="wc", mode="new", executor="wallclock")
    kw = {"remote_url": "http://127.0.0.1:9"} if backend == "remote" else {}
    with pytest.raises(ValueError, match="scores outside this process"):
        t.sweep(providers=["fsdp"], max_flags=0, backend=backend, **kw)
    from repro.core.backends import executor_to_spec
    with pytest.raises(ValueError, match="process/remote"):
        executor_to_spec(t.executor)


def test_deadline_failures_are_not_cached(tmp_path):
    db = SweepDB(str(tmp_path / "sweep.db"))
    t1, _, _ = _tuner(db, "dl")
    t1.executor.timeout_s = 0.001   # soft-fail everything scored
    with pytest.raises(ValueError):  # nothing valid left -> fuse() refuses
        _sweep(t1, use_cache=True, workers=2)
    rows = db.results("dl")
    assert rows and all(r["status"] == "failed" for r in rows)
    assert db.cache_size() == 0
    # a retry with a sane budget recompiles (nothing poisoned)...
    t2, _, _ = _tuner(db, "dl2")
    _, rep2 = _sweep(t2, use_cache=True)
    assert rep2.n_done == rep2.n_combinations
    # ...and its good scores DO land in the cache
    assert db.cache_size() == rep2.n_scored


def test_wallclock_disables_prune():
    """combo_lower_bound divides by an analytic hw peak; against measured
    wall seconds the certificate doesn't hold, so prune must switch off."""
    db = SweepDB(":memory:")
    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    space = {"remat": ("none",), "kernel": ("xla",), "block_q": (16,),
             "block_k": (16,), "scan_unroll": (1,), "mlstm_chunk": (16,)}
    t = ComParTuner(cfg, shape, mesh=None, db=db, project="wp",
                    mode="new", executor="wallclock", timeout_s=120)
    _, rep = t.sweep(providers=["fsdp"], clause_space=space, max_flags=0,
                     prune=True, use_cache=False)
    assert rep.n_pruned == 0 and rep.n_done == rep.n_combinations


def test_unexpected_worker_exception_fails_row_not_sweep(monkeypatch):
    """A non-CombinationFailed bug in scoring must become a failed row;
    an escaping exception would abort the sweep mid-batch."""
    db = SweepDB(":memory:")
    tuner, _, _ = _tuner(db, "boom")
    orig = tuner.executor.score_segment
    calls = {"n": 0}

    def flaky(cfg, shape, seg, combo, knobs=None):
        calls["n"] += 1
        if calls["n"] == 3:   # a stack group — its siblings still succeed
            raise ValueError("synthetic analysis bug")
        return orig(cfg, shape, seg, combo, knobs=knobs)

    monkeypatch.setattr(tuner.executor, "score_segment", flaky)
    plan, rep = _sweep(tuner, use_cache=False)
    assert rep.n_failed > 0
    assert rep.n_done + rep.n_failed == rep.n_combinations
    rows = [r for r in db.results("boom") if r["status"] == "failed"]
    assert any("ValueError" in r["error"] for r in rows)


# --- satellite fixes ---------------------------------------------------------

def test_db_record_unregistered_raises():
    db = SweepDB(":memory:")
    db.open_project("p", "new")
    with pytest.raises(KeyError):
        db.record("p", "g0", "deadbeef0000", status="done",
                  cost={"total_s": 1.0})


def test_db_record_many_partial_unregistered_raises_and_rolls_back():
    db = SweepDB(":memory:")
    db.open_project("p", "new")
    combo = Combination("fsdp", frozenset(), SegmentClause())
    db.register("p", "g0", combo)
    with pytest.raises(KeyError):
        db.record_many("p", [
            {"segment": "g0", "cid": combo.cid, "status": "done",
             "cost": {"total_s": 1.0}},
            {"segment": "g0", "cid": "missing000000", "status": "done"},
        ])
    assert db.status("p", "g0", combo.cid) == "pending"


def test_deadline_off_main_thread_soft_fails():
    out = {}

    def burn(cpu_s):
        t0 = time.thread_time()
        while time.thread_time() - t0 < cpu_s:
            sum(i * i for i in range(1000))

    def body():
        try:
            with deadline(1):
                burn(1.1)    # the soft deadline is CPU time, not wall
            out["raised"] = False
        except CombinationFailed as e:
            out["raised"] = True
            out["msg"] = str(e)

    t = threading.Thread(target=body)
    t.start()
    t.join()
    assert out["raised"] and "soft" in out["msg"]


def test_deadline_off_main_thread_passes_within_budget():
    out = {}

    def body():
        with deadline(30):
            out["ok"] = True

    t = threading.Thread(target=body)
    t.start()
    t.join()
    assert out.get("ok")


# --- Scheduler -> Backend -> Recorder pipeline -------------------------------


def test_backend_equivalence_sequential_thread_process(sequential):
    """The acceptance invariant: sequential, thread(2) and process(2)
    backends fuse byte-identical plans on the smoke config."""
    plan_ref, rep_ref = sequential
    ref = _plan_bytes(plan_ref)

    t_seq, _, _ = _tuner(SweepDB(":memory:"), "be-seq")
    plan_s, rep_s = _sweep(t_seq, backend="sequential", workers=4,
                           use_cache=False, prune=False)
    assert _plan_bytes(plan_s) == ref

    t_thr, _, _ = _tuner(SweepDB(":memory:"), "be-thr")
    plan_t, rep_t = _sweep(t_thr, backend="thread", workers=2,
                           use_cache=False, prune=False)
    assert _plan_bytes(plan_t) == ref

    t_prc, _, _ = _tuner(SweepDB(":memory:"), "be-prc")
    plan_p, rep_p = _sweep(t_prc, backend="process", workers=2,
                           use_cache=False, prune=False)
    assert _plan_bytes(plan_p) == ref
    assert (rep_p.n_done, rep_p.n_failed, rep_p.n_scored, rep_p.n_shared) \
        == (rep_ref.n_done, 0, rep_ref.n_scored, rep_ref.n_shared)


def test_process_backend_hard_timeout_kills_hung_worker():
    """A worker stuck past timeout_s is killed (requeued once, then failed
    transient) within ~2 * timeout_s wall-clock — the sweep cannot hang."""
    from repro.core.backends import JobSpec, ProcessBackend
    from repro.core.executor import SleepExecutor

    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    seg = next(s for s in fragment(cfg) if s.kind == "stack")
    combo = Combination("fsdp", frozenset(), SegmentClause())
    job = JobSpec("hung", seg, combo, segments=(seg.name,))

    timeout_s = 2.0
    backend = ProcessBackend(SleepExecutor(sleep_s=600.0), cfg, shape,
                             workers=2, timeout_s=timeout_s)
    try:
        backend.warmup()            # keep jax import out of the timing window
        t0 = time.monotonic()
        outs = list(backend.run([job]))
        elapsed = time.monotonic() - t0
    finally:
        backend.close()
    assert len(outs) == 1
    out = outs[0]
    assert out.status == "failed" and out.transient
    assert out.attempts == 2 and "killed" in out.error
    # two attempts, each killed at timeout_s * (1 + kill_grace) — the
    # grace window lets a worker's own SIGALRM report gracefully first
    budget = 2 * timeout_s * (1 + ProcessBackend.kill_grace) + 1.0
    assert elapsed < budget, f"hard kill too slow: {elapsed:.1f}s"


def test_process_backend_crash_requeues_once_then_fails_transient():
    from repro.core.backends import JobSpec, ProcessBackend
    from repro.core.executor import CrashExecutor

    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    seg = next(s for s in fragment(cfg) if s.kind == "stack")
    combo = Combination("fsdp", frozenset(), SegmentClause())

    backend = ProcessBackend(CrashExecutor(), cfg, shape, workers=2,
                             timeout_s=60)
    try:
        backend.warmup()
        outs = list(backend.run(
            [JobSpec("boom", seg, combo, segments=(seg.name,))]))
    finally:
        backend.close()
    assert len(outs) == 1
    out = outs[0]
    assert out.status == "failed" and out.transient and out.attempts == 2
    assert "crashed" in out.error


def test_process_backend_honors_use_cache_off(tmp_path):
    """use_cache=False must force real recompiles even on a file-backed DB
    whose score_cache is warm — workers must not get a cache reader."""
    db = SweepDB(str(tmp_path / "sweep.db"))
    t1, _, _ = _tuner(db, "warm")
    _sweep(t1, use_cache=True)                      # populate the cache
    t2, _, _ = _tuner(db, "nocache")
    _, rep = _sweep(t2, backend="process", workers=2, use_cache=False)
    assert rep.n_cached == 0
    assert rep.n_scored > 0
    assert rep.n_done == rep.n_combinations


def test_jobspec_joboutcome_wire_roundtrip():
    """The process/remote wire format: pure JSON both ways, including the
    GlobalKnobs point the program is built under."""
    from repro.core.backends import JobOutcome, JobSpec
    from repro.core.combinator import GlobalKnobs

    seg = Segment("g0", "stack", ("attn", "rec"), 3)
    combo = Combination("tensor_par", frozenset({"shard_vocab"}),
                        SegmentClause(remat="dots", block_q=64))
    spec = JobSpec("k1", seg, combo, segments=("g0", "g3"), bound_s=1.5,
                   signature="sig", eff_cid="ec",
                   knobs=GlobalKnobs(microbatches=2, donate=False))
    wire = json.loads(json.dumps(spec.to_json()))
    back = JobSpec.from_json(wire)
    assert back == spec and isinstance(back.seg.pattern, tuple)
    assert isinstance(back.segments, tuple)
    assert back.knobs == spec.knobs
    # knobless (hand-built / pre-knob) specs stay knobless
    bare = JobSpec("k2", seg, combo)
    assert JobSpec.from_json(
        json.loads(json.dumps(bare.to_json()))).knobs is None

    out = JobOutcome("k1", "failed", cost=None, error="deadline",
                     transient=True, attempts=2)
    assert JobOutcome.from_json(json.loads(json.dumps(out.to_json()))) == out


def test_executor_to_spec_serializes_mesh_as_meshspec():
    """A fixed-mesh executor crosses the wire: its mesh travels as a
    declarative MeshSpec (never device handles) and the worker-side
    rebuild materializes the same topology against local devices —
    meshed sweeps are no longer locked out of process/remote backends."""
    from repro.core.backends import executor_from_spec, executor_to_spec
    from repro.core.executor import DryRunExecutor
    from repro.core.meshspec import MeshSpec

    mesh = MeshSpec.of(data=1).to_mesh()
    spec = json.loads(json.dumps(
        executor_to_spec(DryRunExecutor(mesh, timeout_s=60))))
    assert spec["mesh"] == {"axes": [["data", 1]], "device_kind": ""}
    rebuilt = executor_from_spec(spec)
    assert rebuilt.mesh is not None
    assert tuple(rebuilt.mesh.axis_names) == ("data",)
    assert rebuilt.n_chips == 1
    # meshless executors stay meshless on the wire
    bare = executor_to_spec(DryRunExecutor(None, timeout_s=60))
    assert bare["mesh"] is None
    assert executor_from_spec(bare).mesh is None


def test_arch_shape_specs_roundtrip_via_registry():
    import dataclasses

    from repro.configs import (arch_from_spec, arch_to_spec, shape_from_spec,
                               shape_to_spec)

    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    # registry fast path: a name-resolvable spec returns the canonical cfg
    assert arch_from_spec(json.loads(json.dumps(arch_to_spec(cfg)))) == cfg
    assert shape_from_spec(
        json.loads(json.dumps(shape_to_spec(shape)))) == shape
    # ad-hoc configs (fields diverge from the registry) rebuild from fields
    custom = dataclasses.replace(cfg, d_model=cfg.d_model * 2)
    rebuilt = arch_from_spec(json.loads(json.dumps(arch_to_spec(custom))))
    assert rebuilt == custom and isinstance(rebuilt.block_pattern, tuple)


def test_deadline_failures_are_transient():
    """Cacheability is decided by the structured ``transient`` flag on the
    raising executor, not by substring-matching the error text."""
    out = {}

    def body():
        try:
            with deadline(1):
                t0 = time.thread_time()
                while time.thread_time() - t0 < 1.1:
                    sum(i * i for i in range(1000))
        except CombinationFailed as e:
            out["transient"] = e.transient

    t = threading.Thread(target=body)
    t.start()
    t.join()
    assert out["transient"] is True
    assert CombinationFailed("lowering failed").transient is False


def test_transient_rows_counted_not_scored(monkeypatch):
    """Report accounting: a transient failure neither counts as a scored
    program nor lands in the cache; deterministic failures are cached but
    not counted as compiled programs either."""
    db = SweepDB(":memory:")
    tuner, _, _ = _tuner(db, "acct")
    orig = tuner.executor.score_segment
    calls = {"n": 0}

    def flaky(cfg, shape, seg, combo, knobs=None):
        # fail two of the stack segment's four unique programs so every
        # segment keeps at least one valid row and fusion still succeeds
        if seg.kind == "stack":
            calls["n"] += 1
            if calls["n"] == 1:
                raise CombinationFailed("deadline 0s exceeded (synthetic)",
                                        transient=True)
            if calls["n"] == 2:
                raise CombinationFailed("ShardingError: synthetic")
        return orig(cfg, shape, seg, combo, knobs=knobs)

    monkeypatch.setattr(tuner.executor, "score_segment", flaky)
    # transient_retries=0: the default in-sweep retry round would score
    # the once-flaky program on its second dispatch (that recovery has
    # its own test in test_faults.py) — this test pins the accounting
    # of transients that survive to the report
    _, rep = _sweep(tuner, use_cache=True, transient_retries=0)
    assert rep.n_transient > 0
    assert rep.n_failed >= rep.n_transient
    assert rep.n_scored + rep.n_shared == rep.n_done
    # cache holds the done programs + the deterministic failure only
    assert db.cache_size() == rep.n_scored + 1
    rows = db.results("acct")
    n_det = sum(1 for r in rows if r["status"] == "failed"
                and "ShardingError" in r["error"])
    n_soft = sum(1 for r in rows if r["status"] == "failed"
                 and "synthetic" in r["error"] and "deadline" in r["error"])
    assert n_det > 0 and n_soft == rep.n_transient


def test_cache_tag_isolation_contract(tmp_path):
    """The docs/sweep_engine.md contract: an entry written under
    ``dryrun:tpu-v5e`` must never be served to ``wallclock:r5:*`` — and
    wall-clock tags embed the LOCAL PLATFORM, because empirical timings
    from different silicon are never interchangeable (the analytic
    dryrun tag embeds its hardware model name instead)."""
    from repro.core.executor import DryRunExecutor, WallClockExecutor

    import jax
    assert DryRunExecutor(None).cache_tag == "dryrun:tpu-v5e"
    assert WallClockExecutor(None).cache_tag == \
        f"wallclock:r5:{jax.devices()[0].platform}"

    db = SweepDB(str(tmp_path / "iso.db"))
    db.cache_put_many([{"signature": "sig", "shape": "train:32x4",
                        "mesh": "local/dryrun:tpu-v5e", "cid": "ec",
                        "status": "done", "cost": {"total_s": 1.0}}])
    assert db.cache_get("sig", "train:32x4", "local/dryrun:tpu-v5e",
                        "ec") is not None
    assert db.cache_get("sig", "train:32x4", "local/wallclock:r5",
                        "ec") is None


# --- the GlobalKnobs outer axis ----------------------------------------------


def test_relevant_knob_fields():
    from repro.core.combinator import DEFAULT_GLOBAL_SPACE
    stack = Segment("g0", "stack", ("attn",), 2)
    embed = Segment("embed", "embed")
    head = Segment("head", "head")
    for seg in (stack, embed, head):
        # training wraps every segment in a backward pass: microbatching
        # and donation reach all of them
        assert seg.relevant_knob_fields("train") == \
            frozenset({"microbatches", "donate"})
        # inference shapes: no knob reaches any segment program
        assert seg.relevant_knob_fields("decode") == frozenset()
        assert seg.relevant_knob_fields("prefill") == frozenset()
    # opt_state_dtype (the optimizer update) is never part of a segment
    # program — sweeping it must be free on every shape
    for kind in ("train", "decode", "prefill"):
        assert "opt_state_dtype" not in stack.relevant_knob_fields(kind)
    # every relevant field is a real GlobalKnobs field
    assert stack.relevant_knob_fields("train") <= set(DEFAULT_GLOBAL_SPACE)


def test_nonreaching_knob_sweep_adds_zero_compiles(sequential):
    """The knob-relevance projection: sweeping a knob that reaches no
    segment program compiles nothing new — the rows fold into the same
    structural groups (score sharing across the knob axis)."""
    _, rep1 = sequential
    tuner, _, _ = _tuner(SweepDB(":memory:"), "osd")
    plan, rep = _sweep(tuner, use_cache=False,
                       global_space={"opt_state_dtype":
                                     ("float32", "bfloat16")})
    assert rep.n_knob_points == 2
    assert rep.n_combinations == 2 * rep1.n_combinations
    assert rep.n_scored == rep1.n_scored           # ZERO extra compiles
    assert rep.n_done == rep.n_combinations
    # the argmin ties across the two points; the tie-break is
    # deterministic — the first grid point wins
    assert plan.knobs.opt_state_dtype == "float32"
    assert len(rep.per_knob_total_s) == 2
    assert len(set(rep.per_knob_total_s.values())) == 1   # identical totals


def test_nonreaching_knob_sweep_is_free_on_decode_shapes():
    """On inference shapes NO knob reaches the program — even the
    microbatch axis sweeps for free."""
    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("decode_32k").smoke()
    space = {"remat": ("none",), "kernel": ("xla",), "block_q": (16,),
             "block_k": (16,), "scan_unroll": (1,), "mlstm_chunk": (16,)}

    def sweep(project, **kw):
        t = ComParTuner(cfg, shape, mesh=None, db=SweepDB(":memory:"),
                        project=project, mode="new", executor="dryrun",
                        timeout_s=120)
        return t.sweep(providers=["fsdp"], clause_space=space,
                       max_flags=0, use_cache=False, **kw)

    _, rep1 = sweep("one")
    _, rep2 = sweep("two", global_space={"microbatches": (1, 2)})
    assert rep2.n_combinations == 2 * rep1.n_combinations
    assert rep2.n_scored == rep1.n_scored


def test_reaching_knob_joint_argmin_matches_brute_force(sequential):
    """The acceptance invariant: a program-reaching knob (microbatches on
    a train shape) changes per-segment scores, and the returned
    ``plan.knobs`` is the joint argmin — verified against the brute-force
    reference of one independent single-point sweep per knob point."""
    from repro.core.combinator import GlobalKnobs
    _, rep1 = sequential
    tuner, _, _ = _tuner(SweepDB(":memory:"), "mb")
    plan, rep = _sweep(tuner, use_cache=False,
                       global_space={"microbatches": (1, 2)})
    # microbatches reaches every train segment: every unique program
    # compiles once per knob point
    assert rep.n_scored == 2 * rep1.n_scored
    totals = rep.per_knob_total_s
    assert len(totals) == 2 and len(set(totals.values())) == 2

    # brute force: one fixed-knobs sweep per point, argmin of the totals
    ref = {}
    for mb in (1, 2):
        t = _tuner(SweepDB(":memory:"), f"ref{mb}")[0]
        p, _ = _sweep(t, use_cache=False,
                      knobs=GlobalKnobs(microbatches=mb))
        ref[mb] = p.meta["predicted_total_s"]
    best_mb = min(ref, key=ref.get)
    assert plan.knobs.microbatches == best_mb
    assert abs(plan.meta["predicted_total_s"] - ref[best_mb]) < 1e-15
    assert plan.meta["fusion"] == "per-segment-argmin+knob-argmin"


def test_backend_equivalence_extends_to_knob_axis(sequential):
    """sequential/thread/process sweeps over the same global_space fuse
    byte-identical plans — segments AND chosen knobs."""
    space = {"microbatches": (1, 2),
             "opt_state_dtype": ("float32", "bfloat16")}
    plans = {}
    for backend, workers in (("sequential", 1), ("thread", 2),
                             ("process", 2)):
        t, _, _ = _tuner(SweepDB(":memory:"), f"kbe-{backend}")
        plan, rep = _sweep(t, backend=backend, workers=workers,
                           use_cache=False, global_space=space)
        plans[backend] = (plan, rep)
        t.close()
    ref_bytes = _plan_bytes(plans["sequential"][0])
    ref_rep = plans["sequential"][1]
    for backend, (plan, rep) in plans.items():
        assert _plan_bytes(plan) == ref_bytes, backend
        assert (rep.n_done, rep.n_failed, rep.n_scored) == \
            (ref_rep.n_done, 0, ref_rep.n_scored), backend


def test_effective_cid_v2_never_aliases_v1_cache_rows():
    """Pre-knob score_cache rows must never be served to the knob-aware
    engine: the v2 effective cid hashes a versioned blob that includes
    the knob projection, so it differs from the v1 hash even for the
    same mapping + clause content."""
    import hashlib

    from repro.core.combinator import GlobalKnobs, effective_cid

    combo = Combination("fsdp", frozenset(), SegmentClause())
    relevant = frozenset({"remat", "kernel"})

    def v1_hash(map_key):
        cl = {f: getattr(combo.clause, f) for f in sorted(relevant)}
        blob = json.dumps({"map": map_key, "clause": cl},
                          sort_keys=True, default=str)
        return hashlib.sha1(blob.encode()).hexdigest()[:16]

    # the pre-refactor key component never equals the new one
    assert effective_cid(combo, relevant, "local") != v1_hash("local")
    assert effective_cid(combo, relevant, "local",
                         GlobalKnobs(), frozenset()) != v1_hash("local")
    # knob projection: irrelevant knob fields collapse, relevant split
    k1, k2 = GlobalKnobs(microbatches=1), GlobalKnobs(microbatches=2)
    rel = frozenset({"microbatches"})
    assert effective_cid(combo, relevant, "local", k1, rel) != \
        effective_cid(combo, relevant, "local", k2, rel)
    assert effective_cid(combo, relevant, "local", k1, frozenset()) == \
        effective_cid(combo, relevant, "local", k2, frozenset())
    # same projection -> same cid: points differing only in fields
    # outside the relevant set collapse
    osd = GlobalKnobs(opt_state_dtype="bfloat16")
    assert effective_cid(combo, relevant, "local", osd, rel) == \
        effective_cid(combo, relevant, "local", k1, rel)


def test_knob_rows_and_default_rows_share_cache_when_projection_agrees(
        tmp_path):
    """Cross-sweep score sharing over the knob axis: a warm cache written
    by a default single-point sweep serves a global_space sweep's rows
    whose knob projection matches (mb=1), so only the mb=2 programs
    compile."""
    db = SweepDB(str(tmp_path / "sweep.db"))
    t1, _, _ = _tuner(db, "warm")
    _, rep1 = _sweep(t1, use_cache=True)
    assert rep1.n_scored > 0
    t2, _, _ = _tuner(db, "knobbed")
    _, rep2 = _sweep(t2, use_cache=True,
                     global_space={"microbatches": (1, 2)})
    # mb=1 rows: all cache hits; mb=2 rows: compiled fresh
    assert rep2.n_cached == rep1.n_combinations
    assert rep2.n_scored == rep1.n_scored


def test_paper_count_charges_only_swept_knob_fields():
    from repro.core.combinator import swept_knob_fields
    assert swept_knob_fields(None) == ()
    assert swept_knob_fields({"microbatches": (1,)}) == ()
    assert swept_knob_fields({"microbatches": (1, 2),
                              "donate": (True,),
                              "opt_state_dtype": ("float32", "bfloat16")}) \
        == ("microbatches", "opt_state_dtype")

    # a fixed-knobs sweep charges rtl=0; sweeping one knob field doubles
    # the (2^{rtl+d}-1) factor (+1 in the exponent)
    t1, _, _ = _tuner(SweepDB(":memory:"), "pc1")
    _, rep1 = _sweep(t1, use_cache=False)
    t2, _, _ = _tuner(SweepDB(":memory:"), "pc2")
    _, rep2 = _sweep(t2, use_cache=False,
                     global_space={"opt_state_dtype":
                                   ("float32", "bfloat16")})
    assert rep1.paper_count < rep2.paper_count
    assert "realized=" in rep1.summary()
    assert "paper_formula_upper_bound=" in rep1.summary()


def test_process_backend_pool_survives_across_runs():
    """The worker-reuse satellite: successive run() calls on one process
    backend reuse the same warm workers instead of paying a fresh jax
    import per call (what keeps an outer knob axis cheap)."""
    from repro.core.backends import JobSpec, ProcessBackend
    from repro.core.executor import SleepExecutor

    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    seg = next(s for s in fragment(cfg) if s.kind == "stack")
    combo = Combination("fsdp", frozenset(), SegmentClause())

    backend = ProcessBackend(SleepExecutor(sleep_s=0.01), cfg, shape,
                             workers=1, timeout_s=60)
    try:
        backend.warmup()
        pids0 = sorted(w.proc.pid for w in backend._pool)
        out1 = list(backend.run(
            [JobSpec("j1", seg, combo, segments=(seg.name,))]))
        assert [o.status for o in out1] == ["done"]
        assert sorted(w.proc.pid for w in backend._pool) == pids0
        out2 = list(backend.run(
            [JobSpec("j2", seg, combo, segments=(seg.name,))]))
        assert [o.status for o in out2] == ["done"]
        assert sorted(w.proc.pid for w in backend._pool) == pids0
        assert all(w.proc.is_alive() for w in backend._pool)
    finally:
        backend.close()
    assert backend._pool == []


def test_tuner_reuses_process_engine_across_sweeps():
    """Tuner-level worker reuse: two sweeps on one tuner share one cached
    process backend (same warm pool), released by tuner.close()."""
    db = SweepDB(":memory:")
    tuner, _, _ = _tuner(db, "reuse")
    space2 = dict(SPACE, block_q=(64,))
    try:
        _sweep(tuner, backend="process", workers=1, use_cache=False)
        assert len(tuner._engines) == 1
        engine = next(iter(tuner._engines.values()))
        pids = sorted(w.proc.pid for w in engine._pool)
        assert pids, "pool should stay warm after the first sweep"
        # a second sweep with new rows reuses the same engine + workers
        tuner.sweep(providers=["tensor_par", "fsdp"], clause_space=space2,
                    max_flags=1, backend="process", workers=1,
                    use_cache=False)
        assert len(tuner._engines) == 1
        assert next(iter(tuner._engines.values())) is engine
        assert sorted(w.proc.pid for w in engine._pool) == pids
    finally:
        tuner.close()
    assert tuner._engines == {}


def test_incumbents_are_scoped_per_knob_point():
    """Pruning with a swept knob axis must compare against the SAME knob
    point's incumbents: a cheap mb=1 score must never prune an mb=2 row
    (each point needs its own per-segment argmin for the joint solve).
    Plan equality with the unpruned sweep is the observable contract."""
    t1, _, _ = _tuner(SweepDB(":memory:"), "np")
    plan_ref, rep_ref = _sweep(t1, use_cache=False, prune=False,
                               global_space={"microbatches": (1, 2)})
    t2, _, _ = _tuner(SweepDB(":memory:"), "pp")
    plan_pr, rep_pr = _sweep(t2, use_cache=False, prune=True,
                             prune_margin=0.0,
                             global_space={"microbatches": (1, 2)})
    assert _plan_bytes(plan_pr) == _plan_bytes(plan_ref)
    assert rep_pr.per_knob_total_s == rep_ref.per_knob_total_s


# --- PR 4 hardening satellites -----------------------------------------------


def test_cache_put_many_keep_best_semantics(tmp_path):
    """insert-if-absent / keep-best: a stale batch can never clobber a
    fresher equal-or-better row (the INSERT OR REPLACE regression)."""
    db = SweepDB(str(tmp_path / "kb.db"))
    key = dict(signature="s", shape="sh", mesh="m", cid="c")
    db.cache_put_many([{**key, "status": "done", "cost": {"total_s": 1.0}}])
    # a stale in-flight batch with a worse score does NOT clobber...
    db.cache_put_many([{**key, "status": "done", "cost": {"total_s": 2.0}}])
    assert db.cache_get("s", "sh", "m", "c")["cost"]["total_s"] == 1.0
    # ...a strictly better score does win...
    db.cache_put_many([{**key, "status": "done", "cost": {"total_s": 0.5}}])
    assert db.cache_get("s", "sh", "m", "c")["cost"]["total_s"] == 0.5
    # ...an equal score keeps the incumbent (first-writer-wins)...
    db.cache_put_many([{**key, "status": "done", "cost": {"total_s": 0.5,
                                                          "flops": 99.0}}])
    assert "flops" not in db.cache_get("s", "sh", "m", "c")["cost"]
    # ...and a failure never displaces a done row
    db.cache_put_many([{**key, "status": "failed", "error": "boom"}])
    hit = db.cache_get("s", "sh", "m", "c")
    assert hit["status"] == "done" and hit["cost"]["total_s"] == 0.5
    # done DOES displace failed
    key2 = dict(signature="s2", shape="sh", mesh="m", cid="c")
    db.cache_put_many([{**key2, "status": "failed", "error": "boom"}])
    db.cache_put_many([{**key2, "status": "done", "cost": {"total_s": 3.0}}])
    assert db.cache_get("s2", "sh", "m", "c")["status"] == "done"
    assert db.cache_size() == 2


def test_cache_put_many_two_interleaved_writers(tmp_path):
    """The regression scenario: two sweeps on one DB file, the slower
    one's in-flight batch lands after the fresher (better) row — the
    better row must survive, and both connections must see it."""
    path = str(tmp_path / "shared.db")
    a, b = SweepDB(path), SweepDB(path)
    key = dict(signature="s", shape="sh", mesh="m", cid="c")
    # both sweeps scored the same group; b commits first with the better
    # score, a's stale batch replays afterwards
    b.cache_put_many([{**key, "status": "done", "cost": {"total_s": 1.0}}])
    a.cache_put_many([{**key, "status": "done", "cost": {"total_s": 1.5}}])
    for conn in (a, b):
        assert conn.cache_get("s", "sh", "m", "c")["cost"]["total_s"] == 1.0
    # interleaved failure/success across connections
    key2 = dict(signature="s2", shape="sh", mesh="m", cid="c")
    a.cache_put_many([{**key2, "status": "done", "cost": {"total_s": 2.0}}])
    b.cache_put_many([{**key2, "status": "failed", "error": "stale"}])
    assert b.cache_get("s2", "sh", "m", "c")["status"] == "done"


def test_score_cache_migrates_pre_total_s_schema(tmp_path):
    """A DB created before the keep-best column exists is migrated in
    place, including backfilled totals so legacy rows stay beatable."""
    import sqlite3

    path = str(tmp_path / "old.db")
    conn = sqlite3.connect(path)
    conn.execute(
        "CREATE TABLE score_cache (signature TEXT, shape TEXT, mesh TEXT, "
        "cid TEXT, status TEXT, cost TEXT, error TEXT, created REAL, "
        "PRIMARY KEY (signature, shape, mesh, cid))")
    conn.execute(
        "INSERT INTO score_cache VALUES ('s','sh','m','c','done',"
        "'{\"total_s\": 2.0}','',0)")
    conn.commit()
    conn.close()
    db = SweepDB(path)
    assert db.cache_get("s", "sh", "m", "c")["cost"]["total_s"] == 2.0
    # keep-best works against the migrated row: better wins, worse doesn't
    db.cache_put_many([{"signature": "s", "shape": "sh", "mesh": "m",
                        "cid": "c", "status": "done",
                        "cost": {"total_s": 3.0}}])
    assert db.cache_get("s", "sh", "m", "c")["cost"]["total_s"] == 2.0
    db.cache_put_many([{"signature": "s", "shape": "sh", "mesh": "m",
                        "cid": "c", "status": "done",
                        "cost": {"total_s": 1.0}}])
    assert db.cache_get("s", "sh", "m", "c")["cost"]["total_s"] == 1.0


def test_legacy_done_row_without_total_stays_beatable(tmp_path):
    """A 'done' row whose cost blob carries no total (so the migration
    backfill left total_s NULL) must not become an unbeatable fixed
    point of the keep-best comparison."""
    db = SweepDB(str(tmp_path / "nl.db"))
    db.conn.execute(
        "INSERT INTO score_cache (signature, shape, mesh, cid, status, "
        "cost, error, created, total_s) VALUES "
        "('s','sh','m','c','done','{}','',0,NULL)")
    db.conn.commit()
    db.cache_put_many([{"signature": "s", "shape": "sh", "mesh": "m",
                        "cid": "c", "status": "done",
                        "cost": {"total_s": 5.0}}])
    assert db.cache_get("s", "sh", "m", "c")["cost"]["total_s"] == 5.0


def test_next_job_skips_excluded_worker():
    """Dispatch unit: a job is never handed back to a worker id it died
    on; a non-excluded worker still gets it, in queue order."""
    from collections import deque

    from repro.core.backends import JobSpec, ProcessBackend
    from repro.core.backends.process import _Worker
    from repro.core.executor import SleepExecutor

    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    seg = next(s for s in fragment(cfg) if s.kind == "stack")
    combo = Combination("fsdp", frozenset(), SegmentClause())
    backend = ProcessBackend(SleepExecutor(sleep_s=0.01), cfg, shape,
                             workers=2)
    j1 = JobSpec("j1", seg, combo, segments=(seg.name,))
    j2 = JobSpec("j2", seg, combo, segments=(seg.name,))
    excluded = {"j1": {0}}
    w0, w1 = _Worker(None, None, 0), _Worker(None, None, 1)

    queue = deque([j1, j2])
    job, pruned = backend._next_job(w0, queue, excluded, {})
    assert job is j2 and not pruned      # j1 skipped, left for another worker
    assert list(queue) == [j1]
    job, _ = backend._next_job(w1, queue, excluded, {})
    assert job is j1 and not queue


def test_crash_requeue_dispatches_to_a_different_worker():
    """The requeue-diversification satellite, end-to-end: a job whose
    program kills its worker is retried on a DIFFERENT worker id — the
    lost worker (and whatever inherits its slot) is excluded."""
    from repro.core.backends import JobSpec, ProcessBackend
    from repro.core.executor import CrashExecutor

    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    seg = next(s for s in fragment(cfg) if s.kind == "stack")
    combo = Combination("fsdp", frozenset(), SegmentClause())

    backend = ProcessBackend(CrashExecutor(), cfg, shape, workers=2,
                             timeout_s=60)
    try:
        backend.warmup()
        outs = list(backend.run(
            [JobSpec("boom", seg, combo, segments=(seg.name,))]))
    finally:
        backend.close()
    assert len(outs) == 1
    assert outs[0].status == "failed" and outs[0].transient
    assert outs[0].attempts == 2
    log = backend.dispatch_log
    assert [k for k, _ in log] == ["boom", "boom"]
    wids = [w for _, w in log]
    assert wids[0] != wids[1], "retry burned on the worker the job died on"


def test_sweep_after_injected_failure_completes(monkeypatch):
    """tuner exception-safety: an error mid-sweep must not leave the
    cached process engine poisoned — the next sweep on the same tuner
    culls dead workers and completes; close() stays idempotent."""
    import repro.core.tuner as T

    db = SweepDB(":memory:")
    tuner, _, _ = _tuner(db, "injected")

    class BoomRecorder(T.Recorder):
        def outcome(self, group, out):
            raise RuntimeError("injected recorder failure")

    with monkeypatch.context() as m:
        m.setattr(T, "Recorder", BoomRecorder)
        with pytest.raises(RuntimeError, match="injected"):
            _sweep(tuner, backend="process", workers=1, use_cache=False)

    assert len(tuner._engines) == 1
    engine = next(iter(tuner._engines.values()))
    # simulate the aborted sweep also stranding dead workers in the pool
    for w in list(engine._pool):
        w.proc.terminate()
        w.proc.join(timeout=10)
    # the same tuner/project sweeps to completion (rows are still pending)
    plan, rep = _sweep(tuner, backend="process", workers=1, use_cache=False)
    assert rep.n_done == rep.n_combinations and rep.n_failed == 0
    assert next(iter(tuner._engines.values())) is engine  # engine reused
    assert all(w.proc.is_alive() for w in engine._pool)
    tuner.close()
    tuner.close()                       # idempotent
    assert tuner._engines == {}


def test_build_contexts_records_substitution(caplog):
    """A plan missing a segment must substitute loudly: warning + meta."""
    import logging

    from repro.core.plan import Plan, build_contexts

    cfg = get_arch("granite-8b").smoke()
    combo = Combination("fsdp", frozenset(), SegmentClause())
    plan = Plan({"g0": combo})
    with caplog.at_level(logging.WARNING, logger="repro.plan"):
        ctxs = build_contexts(cfg, None, plan)
    assert set(ctxs) == {s.name for s in fragment(cfg)}
    subs = plan.meta["substituted_segments"]
    assert set(subs) == {"embed", "head"}
    assert subs["embed"]["from"] == "g0"
    assert any("substituting" in r.message for r in caplog.records)
