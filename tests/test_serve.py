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


def _onehot_attn_decode(p, x1, cache, pos, cfg):
    """The cache write and read as the decode step once did them: a
    one-hot ``jnp.where`` over the layer's whole (B,S,KV*D) slice, then
    each head cut out of the rows for the contraction."""
    from repro.models.layers import apply_rope, dense
    q = apply_rope(dense(x1, p["wq"]), pos, cfg.rope)
    k = apply_rope(dense(x1, p["wk"]), pos, cfg.rope)
    v = dense(x1, p["wv"])
    B, S, _ = cache["k"].shape
    (H, D), KV = q.shape[1:], k.shape[1]
    slot = pos % S if cfg.window_size else pos
    if jnp.ndim(pos):
        hit = jnp.arange(S)[None, :] == slot[:, None]
        seen = jnp.arange(S)[None, :] <= jnp.minimum(pos, S - 1)[:, None]
    else:
        hit = (jnp.arange(S) == slot)[None]
        seen = (jnp.arange(S) <= jnp.minimum(pos, S - 1))[None]
    kc = jnp.where(hit[:, :, None], k.reshape(B, 1, -1), cache["k"])
    vc = jnp.where(hit[:, :, None], v.reshape(B, 1, -1), cache["v"])
    f32 = jnp.float32
    qg = q.reshape(B, KV, H // KV, D).astype(f32)
    s = jnp.einsum("bkgd,bskd->bkgs", qg,
                   kc.reshape(B, S, KV, D).astype(f32)) * D ** -0.5
    s = jnp.where(seen[:, None, None], s, -1e30)
    o = jnp.einsum("bkgs,bskd->bkgd", jax.nn.softmax(s, axis=-1),
                   vc.reshape(B, S, KV, D).astype(f32))
    y = jnp.einsum("bhd,hde->be", o.reshape(B, H, D).astype(q.dtype),
                   p["wo"]).astype(x1.dtype)
    return y, {"k": kc, "v": vc}


def _onehot_decode_step(params, caches, tokens, pos, cfg):
    """Reference decode step: every layer's cache slice scanned in as
    ``xs`` and re-emitted as ``ys``, written by ``_onehot_attn_decode``;
    recurrent blocks run ``block_decode`` on their own state."""
    from repro.models.blocks import ATTN_KINDS, block_decode
    from repro.models.context import ModelContext
    from repro.models.layers import norm_apply
    from repro.models.mlp import mlp_apply
    from repro.models.model import embed_tokens, lm_head
    ctx = ModelContext()
    x = embed_tokens(params, tokens, cfg, ctx)
    new = {}
    for gi, group in enumerate(cfg.stack_plan()):
        def layer(x, pc):
            lp, lc = pc
            nc = {}
            for j, kind in enumerate(group.pattern):
                b, p = f"b{j}", lp[f"b{j}"]
                if kind in ATTN_KINDS:
                    h = norm_apply(p["ln1"], x[:, None], cfg.norm)[:, 0]
                    y, nc[b] = _onehot_attn_decode(p["attn"], h, lc[b], pos,
                                                   cfg)
                    x = x + y
                    h = norm_apply(p["ln2"], x[:, None], cfg.norm)
                    x = x + mlp_apply(p["ffn"], h, cfg, ctx)[:, 0]
                else:
                    x, nc[b] = block_decode(kind, p, x, lc[b], pos, cfg, ctx)
            return x, nc
        seg = f"g{gi}"
        pc = (params[seg], caches[seg])
        x, new[seg] = layer(x, pc) if group.repeats == 1 \
            else jax.lax.scan(layer, x, pc)
    return lm_head(params, x, cfg, ctx), new


@pytest.mark.parametrize("name,layers", [("stablelm-3b", None),
                                         ("starcoder2-3b", None),
                                         ("recurrentgemma-2b", 7)])
@pytest.mark.parametrize("vector", [True, False], ids=["vector", "scalar"])
def test_decode_step_in_place_write_matches_onehot_reference(name, layers,
                                                             vector):
    """The carried, in-place KV write (dense, windowed ring buffer, and a
    scanned group mixing recurrent and attention blocks) gives the
    reference's logits and caches over several steps, at positions that
    include 0 and ``cache_len - 1`` (and wrap, when windowed); rows not
    written keep their bytes."""
    import dataclasses

    from repro.core.plan import build_contexts
    from repro.models.model import decode_step, init_cache, model_specs
    from repro.models.params import init_params
    cfg = _cfg(name)
    if layers:
        cfg = dataclasses.replace(cfg, num_layers=layers)
    ctxs = build_contexts(cfg, None, _plan(cfg))
    params = init_params(model_specs(cfg), jax.random.key(3))
    B, S = 3, 16
    leaves, tree = jax.tree.flatten(init_cache(cfg, B, S))
    keys = jax.random.split(jax.random.key(4), len(leaves))
    caches = jax.tree.unflatten(tree, [
        jax.random.normal(k, c.shape, c.dtype) for k, c in zip(keys, leaves)])
    L = min(S, cfg.window_size) if cfg.window_size else S  # cache_len
    last = L + 2 if cfg.window_size else L - 1
    if vector:           # row 0 from 0, row 2 through cache_len - 1
        steps = [np.array([i, 5 + i, last - 4 + i]) for i in range(5)]
    else:
        steps = [np.int32(i) for i in (0, 1, L - 2, L - 1, last)]
    ours = jax.jit(lambda c, t, p: decode_step(params, c, t, p, cfg, ctxs))
    ref = jax.jit(lambda c, t, p: _onehot_decode_step(params, c, t, p, cfg))
    rng = np.random.RandomState(5)
    mine = theirs = caches
    for pos in steps:
        toks = jnp.asarray(rng.randint(0, cfg.vocab_size, (B,)), jnp.int32)
        before = mine
        lg, mine = ours(mine, toks, jnp.asarray(pos, jnp.int32))
        lg_ref, theirs = ref(theirs, toks, jnp.asarray(pos, jnp.int32))
        np.testing.assert_allclose(np.asarray(lg), np.asarray(lg_ref),
                                   rtol=1e-5, atol=1e-5)
        jax.tree.map(lambda a, b: np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-5),
            mine, theirs)
        # the attention caches' unwritten rows are untouched, bit for bit
        slot = np.broadcast_to(np.asarray(pos) % L, (B,))
        kept = np.ones((B, L), bool)
        kept[np.arange(B), slot] = False
        for seg, g in mine.items():
            for blk, c in g.items():
                if set(c) != {"k", "v"}:
                    continue
                for n in ("k", "v"):
                    now = np.asarray(c[n])
                    was = np.asarray(before[seg][blk][n])
                    np.testing.assert_array_equal(now[..., kept, :],
                                                  was[..., kept, :])


# --- spans, stamps and scopes -------------------------------------------------

def _host_spans(run):
    """Run ``run()`` under the profiler; the host events whose names
    start with ``serve.`` as (name, start_ns, end_ns, stats), by start."""
    import tempfile
    from pathlib import Path

    from jax.profiler import ProfileData
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            out = run()
        finally:
            jax.profiler.stop_trace()
        pd = ProfileData.from_file(str(next(Path(d).rglob("*.xplane.pb"))))
        spans = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                  dict(e.stats))
                 for p in pd.planes if p.name.startswith("/host:CPU")
                 for ln in p.lines for e in ln.events
                 if e.name.startswith("serve.")]
    return out, sorted(spans, key=lambda s: (s[1], -s[2]))


def test_engine_spans_nest_and_cover_each_admission_and_step():
    from bench import serve_trace as st
    cfg = _cfg()
    eng = ServeEngine(cfg, _plan(cfg), capacity=3, cache_len=32)
    reqs = _reqs(cfg, 5, tokens=4)
    done, spans = _host_spans(lambda: eng.run(reqs))
    assert {s[0] for s in spans} == set(st.SPANS)
    by = lambda n: [s for s in spans if s[0] == n]  # noqa: E731
    (run,) = by(st.RUN_SPAN)
    admits, steps = by(st.ADMIT_SPAN), by(st.STEP_SPAN)
    assert len(admits) == len(reqs) == len(done)
    assert len(steps) == eng.stats.n_steps
    assert sorted(a[3]["rid"] for a in admits) == sorted(r.rid for r in reqs)
    for a in admits:
        c = done[a[3]["rid"]]
        assert (a[3]["slot"], a[3]["prompt_len"]) == (c.slot, c.prompt_len)
    assert [s[3]["step"] for s in steps] == list(range(len(steps)))
    assert all(1 <= s[3]["active"] <= 3 for s in steps)

    def inside(child, parent):
        return parent[1] <= child[1] and child[2] <= parent[2]

    for parents, kids in ((admits, (st.PREFILL_SPAN, st.SPLICE_SPAN)),
                          (steps, (st.DISPATCH_SPAN, st.READBACK_SPAN))):
        for k in kids:
            children = by(k)
            assert len(children) == len(parents)
            assert all(inside(c, p) for c, p in zip(children, parents))
    # admit and step spans lie in the run and never overlap one another
    top = sorted(admits + steps, key=lambda s: s[1])
    assert all(inside(s, run) for s in top)
    assert all(a[2] <= b[1] for a, b in zip(top, top[1:]))


def test_completion_stamps_are_ordered_and_sequential_admits_wait():
    cfg = _cfg()
    eng = ServeEngine(cfg, _plan(cfg), capacity=3, cache_len=32)
    reqs = _reqs(cfg, 4, tokens=3)
    for done in (eng.run(reqs), eng.run(reqs, max_active=1)):
        for c in done.values():
            assert c.arrived_s <= c.admitted_s <= c.first_token_s \
                <= c.done_s
        assert len({c.arrived_s for c in done.values()}) == 1
    # one at a time: each admission waits for the previous request
    order = [done[r.rid] for r in reqs]
    for prev, nxt in zip(order, order[1:]):
        assert nxt.admitted_s >= prev.done_s


def test_decode_step_carries_segment_block_and_kv_write_scopes():
    from bench.serve_trace import in_scope, scope_paths
    from repro.models.model import cache_specs
    cfg = _cfg()
    eng = ServeEngine(cfg, _plan(cfg), capacity=2, cache_len=16)
    sds = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)  # noqa: E731
    i32 = jax.ShapeDtypeStruct((2,), jnp.int32)
    text = eng._step.lower(jax.tree.map(sds, eng.params),
                           cache_specs(cfg, 2, 16), i32, i32) \
        .compile().as_text()
    paths = scope_paths(text).values()
    for scope in ("embed", "attn", "kv_write", "mlp", "head"):
        assert any(in_scope(p, scope) for p in paths), scope
    # the cache write sits inside attention, in the layer group's scan
    assert all(in_scope(p, "attn") and in_scope(p, "g0")
               for p in paths if in_scope(p, "kv_write"))
