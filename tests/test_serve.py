"""Serving subsystem: PlanRegistry persistence + the continuous-batching
engine's byte-identity contract (batched streams == sequential streams).
"""
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_arch
from repro.core.db import SweepDB
from repro.core.meshspec import MeshSpec
from repro.core.plan import uniform_plan
from repro.models.context import SegmentClause
from repro.serve import (PlanRegistry, Request, ServeEngine, make_prefill,
                         serving_shape)
from repro.serve.engine import cache_batch_axes


def _cfg(name="stablelm-3b"):
    return get_arch(name).smoke()


def _plan(cfg, **kw):
    clause = SegmentClause(remat="none", kernel="xla", **kw)
    return uniform_plan(cfg, "tensor_par", set(), clause)


def _reqs(cfg, n, *, seed=0, tokens=6, prompt_len=3):
    rng = np.random.RandomState(seed)
    out = []
    for i in range(n):
        p = max(1, prompt_len + int(rng.randint(-1, 2)))
        out.append(Request(
            rid=f"r{i}",
            prompt=tuple(int(t) for t in rng.randint(0, cfg.vocab_size, p)),
            max_new_tokens=tokens + i % 3))
    return out


# --- registry ---------------------------------------------------------------

def test_registry_roundtrip_byte_identical_plan(tmp_path):
    cfg = _cfg()
    plan = _plan(cfg)
    plan.meta["predicted_total_s"] = 1.25e-4
    reg = PlanRegistry(str(tmp_path / "reg.db"))
    shape = serving_shape(4, 64)
    reg.register(cfg, shape, plan, report={"note": "t"}, cache_tag="dry")
    e = reg.lookup(cfg, shape, cache_tag="dry")
    assert e is not None and e.exact
    assert json.dumps(e.plan.to_json(), sort_keys=True) == \
        json.dumps(plan.to_json(), sort_keys=True)
    assert e.total_s == pytest.approx(1.25e-4)
    assert e.report == {"note": "t"}
    assert e.kind == "decode" and (e.seq_len, e.batch) == (64, 4)


def test_registry_mesh_mismatch_is_a_miss(tmp_path):
    cfg = _cfg()
    reg = PlanRegistry(str(tmp_path / "reg.db"))
    shape = serving_shape(4, 64)
    reg.register(cfg, shape, _plan(cfg), mesh=MeshSpec.of(data=2))
    # meshless lookup must not see the data=2 plan, nearest or not
    assert reg.lookup(cfg, shape) is None
    assert reg.lookup(cfg, serving_shape(4, 128)) is None
    # ... and the right mesh resolves it
    e = reg.lookup(cfg, shape, MeshSpec.of(data=2))
    assert e is not None and e.exact and e.mesh_mid != "local"


def test_registry_nearest_shape_fallback_deterministic(tmp_path):
    cfg = _cfg()
    reg = PlanRegistry(str(tmp_path / "reg.db"))
    reg.register(cfg, serving_shape(4, 64), _plan(cfg))
    reg.register(cfg, serving_shape(4, 256), _plan(cfg, cache_upcast=False))
    # 96 is log2-closer to 64 (0.58) than to 256 (1.41)
    e = reg.lookup(cfg, serving_shape(4, 96))
    assert e is not None and not e.exact and e.seq_len == 64
    # exact tie (64 between 32 and 128): sort-order tie-break, stable
    reg2 = PlanRegistry(str(tmp_path / "reg2.db"))
    reg2.register(cfg, serving_shape(4, 32), _plan(cfg))
    reg2.register(cfg, serving_shape(4, 128), _plan(cfg))
    picks = {reg2.lookup(cfg, serving_shape(4, 64)).shape
             for _ in range(5)}
    assert picks == {"decode:128x4"}
    # nearest=False: the fallback is opt-out
    assert reg.lookup(cfg, serving_shape(4, 96), nearest=False) is None


def test_registry_reregister_newest_wins(tmp_path):
    cfg = _cfg()
    reg = PlanRegistry(str(tmp_path / "reg.db"))
    shape = serving_shape(4, 64)
    reg.register(cfg, shape, _plan(cfg, cache_upcast=True))
    first = reg.lookup(cfg, shape).plan.to_json()
    reg.register(cfg, shape, _plan(cfg, cache_upcast=False))
    second = reg.lookup(cfg, shape).plan.to_json()
    assert first != second
    assert len(reg.entries(cfg.name)) == 1


def test_registry_shares_db_file_with_score_cache(tmp_path):
    path = str(tmp_path / "both.db")
    db = SweepDB(path)
    reg = PlanRegistry(db)
    cfg = _cfg()
    reg.register(cfg, serving_shape(2, 32), _plan(cfg))
    # a second handle on the same file sees the plan (WAL persistence)
    assert PlanRegistry(path).lookup(cfg, serving_shape(2, 32)) is not None


def test_tuner_registers_fused_plan(tmp_path):
    from repro.core.tuner import ComParTuner
    cfg = _cfg()
    shape = serving_shape(2, 32)
    db = SweepDB(str(tmp_path / "sweep.db"))
    tuner = ComParTuner(cfg, shape, db=db, project="reg-e2e",
                        executor="dryrun", registry=True)
    with tuner:
        plan, rep = tuner.sweep(
            providers=("tensor_par",),
            clause_space={"remat": ("none",), "kernel": ("xla",),
                          "cache_upcast": (True, False)},
            max_flags=0, backend="sequential")
    e = tuner.registry.lookup(cfg, shape,
                              cache_tag=tuner.executor.cache_tag)
    assert e is not None and e.exact
    assert json.dumps(e.plan.to_json(), sort_keys=True) == \
        json.dumps(plan.to_json(), sort_keys=True)
    assert e.total_s == pytest.approx(plan.meta["predicted_total_s"])
    assert "summary" in e.report
    # acceptance: overlapping requests under the REGISTERED plan stream
    # byte-identically to sequential decoding under the same plan
    eng = ServeEngine(cfg, e.plan, capacity=e.batch, cache_len=e.seq_len)
    reqs = _reqs(cfg, 4, tokens=4, prompt_len=2)
    batched, seq = eng.run(reqs), eng.run(reqs, max_active=1)
    assert all(batched[r.rid].tokens == seq[r.rid].tokens for r in reqs)


# --- engine -----------------------------------------------------------------

def test_engine_batched_equals_sequential_byte_identical():
    """The tentpole contract: >=3 overlapping requests, every stream
    byte-identical to the one-request-at-a-time loop on the same plan."""
    cfg = _cfg()
    eng = ServeEngine(cfg, _plan(cfg), capacity=4, cache_len=32)
    reqs = _reqs(cfg, 7)
    batched = eng.run(reqs)
    assert eng.stats.peak_active >= 3
    assert eng.stats.n_completed == len(reqs)
    sequential = eng.run(reqs, max_active=1)
    assert eng.stats.peak_active == 1
    for r in reqs:
        assert batched[r.rid].tokens == sequential[r.rid].tokens, r.rid
        assert batched[r.rid].finish_reason == \
            sequential[r.rid].finish_reason


def test_engine_streams_independent_of_batch_mates():
    """A request's stream must not change with WHO it shares slots with."""
    cfg = _cfg()
    eng = ServeEngine(cfg, _plan(cfg), capacity=3, cache_len=32)
    probe = Request(rid="p", prompt=(5, 9, 2), max_new_tokens=8)
    alone = eng.run([probe])["p"].tokens
    crowd = _reqs(cfg, 5, seed=7)
    mixed = eng.run([probe] + crowd)["p"].tokens
    assert mixed == alone


def test_engine_eos_recycles_slot():
    cfg = _cfg()
    eng = ServeEngine(cfg, _plan(cfg), capacity=2, cache_len=32)
    probe = Request(rid="p", prompt=(1, 2, 3), max_new_tokens=20)
    ref = eng.run([probe])["p"].tokens
    # cut the stream at a token whose value does not occur earlier, so
    # the EOS fires at exactly that index whatever the stream contents
    k = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    done = eng.run([Request(rid="p", prompt=(1, 2, 3), max_new_tokens=20,
                            eos_id=ref[k]),
                    Request(rid="q", prompt=(4, 4), max_new_tokens=12)])
    assert done["p"].finish_reason == "eos"
    assert done["p"].tokens == ref[:k + 1]
    assert done["q"].finish_reason == "length"
    # the freed slot was reusable: both fit capacity 2 regardless, but
    # the EOS'd request must have finished earlier than q
    assert done["p"].done_step <= done["q"].done_step


def test_engine_overflow_and_duplicate_rid_rejected():
    cfg = _cfg()
    eng = ServeEngine(cfg, _plan(cfg), capacity=2, cache_len=8)
    with pytest.raises(ValueError, match="cache_len"):
        eng.run([Request(rid="a", prompt=(1, 2, 3, 4), max_new_tokens=8)])
    with pytest.raises(ValueError, match="duplicate"):
        eng.run([Request(rid="a", prompt=(1,), max_new_tokens=2),
                 Request(rid="a", prompt=(2,), max_new_tokens=2)])
    with pytest.raises(ValueError, match="empty prompt"):
        Request(rid="a", prompt=())


def test_engine_recurrent_arch():
    """xLSTM decode carries recurrent state, not a KV ring — the fresh-
    prefill splice must reset it per slot just the same."""
    cfg = _cfg("xlstm-125m")
    eng = ServeEngine(cfg, _plan(cfg), capacity=3, cache_len=16)
    reqs = _reqs(cfg, 5, tokens=4, prompt_len=2)
    batched = eng.run(reqs)
    assert eng.stats.peak_active == 3
    sequential = eng.run(reqs, max_active=1)
    for r in reqs:
        assert batched[r.rid].tokens == sequential[r.rid].tokens, r.rid


def test_prefill_cache_matches_forward_logits():
    """The scan-of-decode prefill's last-position logits agree with the
    full-sequence forward (same params, same plan)."""
    from repro.models.model import init_cache, model_specs
    from repro.models.params import init_params
    cfg = _cfg()
    plan = _plan(cfg)
    from repro.serve.step import make_prefill_cache
    params = init_params(model_specs(cfg), jax.random.key(0))
    prompt = jnp.asarray([[3, 1, 4, 1, 5]], jnp.int32)
    _, last, _ = make_prefill_cache(cfg, None, plan)(
        params, init_cache(cfg, 1, 16), prompt)
    fwd, _ = make_prefill(cfg, None, plan)
    full = fwd(params, {"tokens": prompt})
    np.testing.assert_allclose(np.asarray(last[0]),
                               np.asarray(full[0, -1]),
                               rtol=2e-2, atol=2e-2)


def test_cache_batch_axes_match_cache_ranks():
    from repro.models.model import init_cache
    for name in ("stablelm-3b", "xlstm-125m"):
        cfg = _cfg(name)
        caches = init_cache(cfg, 3, 8)
        axes = cache_batch_axes(cfg)
        def check(c, ax):
            assert c.shape[ax] == 3, (name, c.shape, ax)
        jax.tree.map(check, caches, axes)


#: B=1 and B=3 decode are two compiled programs; XLA does not promise
#: the same rounding across batch sizes, so they agree to f32 tolerance
ACROSS_PROGRAMS_TOL = 1e-5


def test_vector_pos_decode_matches_scalar_rows():
    """decode with a per-row position vector reproduces the scalar-pos
    rows (the primitive under the engine contract): bit-identical within
    one compiled program whatever slot a row sits in, and within
    ``ACROSS_PROGRAMS_TOL`` of the B=1 program."""
    from repro.core.plan import build_contexts
    from repro.models.model import decode_step, init_cache, model_specs
    from repro.models.params import init_params
    cfg = _cfg()
    plan = _plan(cfg)
    ctxs = build_contexts(cfg, None, plan)
    params = init_params(model_specs(cfg), jax.random.key(1))
    B, S = 3, 8
    rng = np.random.RandomState(2)
    toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (B,)), jnp.int32)

    # scalar path: run each row alone at its own position, after seeding
    # that row's cache with `p` decode steps
    def row_state(b, p):
        c = init_cache(cfg, 1, S)
        for i in range(p):
            _, c = decode_step(params, c,
                               jnp.asarray([7 + b + i], jnp.int32),
                               jnp.int32(i), cfg, ctxs)
        return c

    pos = [2, 0, 4]
    per_row = []
    for b in range(B):
        c = row_state(b, pos[b])
        lg, _ = decode_step(params, c, toks[b:b + 1],
                            jnp.int32(pos[b]), cfg, ctxs)
        per_row.append(np.asarray(lg[0]))

    # vector path: same rows batched with a (B,) position vector
    from repro.serve.engine import _put_row, cache_batch_axes
    axes = cache_batch_axes(cfg)
    batch = init_cache(cfg, B, S)
    for b in range(B):
        batch = _put_row(batch, row_state(b, pos[b]), axes, b)
    step = jax.jit(lambda c, t, p: decode_step(params, c, t, p, cfg,
                                                ctxs)[0])
    lg = np.asarray(step(batch, toks, jnp.asarray(pos, jnp.int32)))
    for b in range(B):
        np.testing.assert_allclose(lg[b], per_row[b],
                                   rtol=ACROSS_PROGRAMS_TOL,
                                   atol=ACROSS_PROGRAMS_TOL)

    # same compiled program, rows moved to other slots: bit-identical
    perm = [2, 0, 1]
    moved = init_cache(cfg, B, S)
    for slot, b in enumerate(perm):
        moved = _put_row(moved, row_state(b, pos[b]), axes, slot)
    lg_moved = np.asarray(step(moved, toks[jnp.asarray(perm)],
                               jnp.asarray([pos[b] for b in perm],
                                           jnp.int32)))
    for slot, b in enumerate(perm):
        np.testing.assert_array_equal(lg_moved[slot], lg[b])
