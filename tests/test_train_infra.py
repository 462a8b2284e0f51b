"""Training infrastructure: loss decreases, microbatch-equivalence,
checkpoint/restart exact replay, data determinism, optimizer-state
compression, HLO analyzer."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint.store import CheckpointStore
from repro.configs import get_arch, get_shape
from repro.core.combinator import GlobalKnobs
from repro.core.plan import uniform_plan
from repro.data.pipeline import SyntheticLM
from repro.models.context import SegmentClause
from repro.optim.adamw import adamw_init, adamw_update, cosine_lr
from repro.train.step import init_train_state, jit_train_step


def tiny_setup(arch="granite-8b", mb=1, **clause_kw):
    cfg = get_arch(arch).smoke()
    # donate=False: tests re-run steps from the same initial state
    plan = uniform_plan(cfg, "fsdp",
                        clause=SegmentClause(**clause_kw),
                        knobs=GlobalKnobs(microbatches=mb, donate=False))
    step, _ = jit_train_step(cfg, None, plan)
    params, opt = init_train_state(cfg, plan, jax.random.key(0))
    return cfg, step, params, opt


def make_batch(cfg, B=4, S=16, seed=1):
    ks = jax.random.split(jax.random.key(seed), 2)
    return {"tokens": jax.random.randint(ks[0], (B, S), 0, cfg.vocab_size),
            "targets": jax.random.randint(ks[1], (B, S), 0,
                                          cfg.vocab_size)}


def test_loss_decreases_on_repeated_batch():
    cfg, step, params, opt = tiny_setup()
    batch = make_batch(cfg)
    losses = []
    for _ in range(8):
        params, opt, m = step(params, opt, batch)
        losses.append(float(m["total_loss"]))
    assert losses[-1] < losses[0] - 0.01, losses


def test_microbatch_grad_equivalence():
    """mb=2 gradient accumulation must match mb=1 on the same batch
    (same loss trajectory within fp tolerance)."""
    cfg1, step1, p1, o1 = tiny_setup(mb=1)
    cfg2, step2, p2, o2 = tiny_setup(mb=2)
    batch = make_batch(cfg1)
    for _ in range(3):
        p1, o1, m1 = step1(p1, o1, batch)
        p2, o2, m2 = step2(p2, o2, batch)
    np.testing.assert_allclose(float(m1["total_loss"]),
                               float(m2["total_loss"]), rtol=1e-3)


def test_remat_does_not_change_loss():
    cfg1, step1, p1, o1 = tiny_setup(remat="none")
    cfg2, step2, p2, o2 = tiny_setup(remat="full")
    batch = make_batch(cfg1)
    p1, o1, m1 = step1(p1, o1, batch)
    p2, o2, m2 = step2(p2, o2, batch)
    np.testing.assert_allclose(float(m1["total_loss"]),
                               float(m2["total_loss"]), rtol=1e-5)


def test_checkpoint_restart_exact_replay(tmp_path):
    """Train 6 steps straight vs train 3 + crash + restore + 3 — identical
    final loss (the fault-tolerance contract)."""
    cfg, step, params, opt = tiny_setup()
    shape = get_shape("train_4k").smoke()
    data = SyntheticLM(cfg, shape, seed=7)

    def run(params, opt, data, lo, hi):
        m = None
        for s in range(lo, hi):
            params, opt, m = step(params, opt, data.batch_at(s))
        return params, opt, float(m["total_loss"])

    pA, oA, lossA = run(params, opt, data, 0, 6)

    store = CheckpointStore(str(tmp_path), keep=2)
    pB, oB, _ = run(params, opt, data, 0, 3)
    store.save(3, {"params": pB, "opt": oB},
               extra={"data": {"seed": 7, "step": 3}})
    # simulated crash: fresh objects, restore
    stepr, _ = jit_train_step(cfg, None, uniform_plan(
        cfg, "fsdp", clause=SegmentClause()))
    s0, state, extra = store.restore({"params": pB, "opt": oB})
    assert s0 == 3 and extra["data"]["step"] == 3
    pC, oC, lossC = run(state["params"], state["opt"],
                        SyntheticLM(cfg, shape, seed=7), 3, 6)
    np.testing.assert_allclose(lossA, lossC, rtol=1e-6)


def test_checkpoint_atomicity(tmp_path):
    """A step dir without a manifest must be invisible to restore."""
    store = CheckpointStore(str(tmp_path), keep=5)
    tree = {"w": jnp.ones((4,))}
    store.save(1, {"params": tree})
    # simulate crash mid-write of step 2: dir exists, no manifest
    os.makedirs(os.path.join(str(tmp_path), "step_00000002"))
    assert store.latest_step() == 1
    step, out, _ = store.restore({"params": tree})
    assert step == 1


@given(st.integers(0, 2 ** 20), st.integers(0, 50))
@settings(max_examples=20, deadline=None)
def test_data_pure_function_of_step(seed, step):
    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    d1 = SyntheticLM(cfg, shape, seed=seed)
    d2 = SyntheticLM(cfg, shape, seed=seed)
    b1, b2 = d1.batch_at(step), d2.batch_at(step)
    np.testing.assert_array_equal(b1["tokens"], b2["tokens"])
    assert int(b1["tokens"].max()) < cfg.vocab_size


def test_data_host_slices_differ():
    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    hs = [SyntheticLM(cfg, shape, seed=1, host_index=i, host_count=4)
          for i in range(4)]
    toks = [np.asarray(h.batch_at(0)["tokens"]) for h in hs]
    for i in range(4):
        for j in range(i + 1, 4):
            assert not np.array_equal(toks[i], toks[j])


def test_optimizer_state_compression_halves_bytes():
    params = {"w": jnp.zeros((128, 128), jnp.bfloat16)}
    full = adamw_init(params, "float32")
    comp = adamw_init(params, "bfloat16")
    assert comp.m["w"].dtype == jnp.bfloat16
    assert full.m["w"].nbytes == 2 * comp.m["w"].nbytes


def test_adamw_converges_quadratic():
    w = jnp.array([4.0, -3.0])
    params = {"w": w}
    state = adamw_init(params)
    for i in range(200):
        grads = {"w": 2 * params["w"]}
        params, state, _ = adamw_update(
            grads, state, params, lr=0.1, weight_decay=0.0)
    assert float(jnp.abs(params["w"]).max()) < 0.1


def test_cosine_lr_schedule():
    assert float(cosine_lr(jnp.int32(0), peak_lr=1.0, warmup=10)) == 0.0
    assert abs(float(cosine_lr(jnp.int32(10), peak_lr=1.0, warmup=10,
                               total=100)) - 1.0) < 1e-6
    end = float(cosine_lr(jnp.int32(100), peak_lr=1.0, warmup=10,
                          total=100))
    assert end < 0.2


# --- HLO analyzer ------------------------------------------------------------

def test_hlo_flops_counts_scan_trips():
    from repro.runtime.hlo import analyze_hlo

    def body(c, w):
        return jnp.tanh(c @ w), None

    def prog(x, ws):
        return jax.lax.scan(body, x, ws)[0]

    x = jax.ShapeDtypeStruct((64, 128), jnp.float32)
    ws = jax.ShapeDtypeStruct((7, 128, 128), jnp.float32)
    compiled = jax.jit(prog).lower(x, ws).compile()
    res = analyze_hlo(compiled.as_text())
    expect = 7 * 2 * 64 * 128 * 128
    assert abs(res["flops"] - expect) / expect < 0.01
    # XLA's own cost_analysis misses the trips — that's why we parse
    ca = compiled.cost_analysis()
    assert ca["flops"] < res["flops"]


def test_hlo_collective_parsing_synthetic():
    from repro.runtime.hlo import collective_bytes
    txt = """
HloModule m
ENTRY %main (p: f32[16,128]) -> f32[16,128] {
  %p = f32[16,128]{1,0} parameter(0)
  %ag = f32[256,128]{1,0} all-gather(%p), replica_groups=[16,16]<=[256], dimensions={0}
  ROOT %ar = f32[16,128]{1,0} all-reduce(%p), replica_groups={{0,1,2,3}}, to_apply=%add
}
"""
    res = collective_bytes(txt)
    ag = 256 * 128 * 4 * 15 / 16
    ar = 2 * 16 * 128 * 4 * 3 / 4
    assert abs(res["all-gather"] - ag) < 1
    assert abs(res["all-reduce"] - ar) < 1
    assert abs(res["total"] - (ag + ar)) < 2
