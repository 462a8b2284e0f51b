"""Train cell: the jitted train step of ``train/step.py`` under the cell's
plan, fed the mix's token batches.

Set-up builds one object, the compiled step with its state, made on the
device from the seed, and drives it through the mix's ``check_steps``
first steps (the window's own call and feed); the window continues the
same object.  Each step's loss, the first gradient as the optimizer got
it (its first moment after one step over ``1 - b1``) and the parameters'
change after the check steps are compared, after the window, with the
plain float32 reference the configuration names, run from the same seed.
"""
from __future__ import annotations

import dataclasses
import math
import tempfile
import time
from collections import deque
from pathlib import Path
from typing import Dict

import numpy as np

from bench import config as C
from bench import flops, gen

ADAM_B1 = 0.9
#: leaves whose reference gradient norm is under this share of the median
#: leaf's move under Adam by round-off alone; their change is not compared
STILL_LEAF = 1e-3


def _plan(cell, fault):
    from repro.core.plan import Plan
    plan = Plan.load(str(cell["plan"]))
    if fault == "unchanged":
        # the faulty step hands back its inputs, which must stay alive
        plan = dataclasses.replace(
            plan, knobs=dataclasses.replace(plan.knobs, donate=False))
    return plan


def _sq_norms(tree):
    import jax
    import jax.numpy as jnp
    return jax.tree.map(lambda x: jnp.sum(jnp.square(x.astype(jnp.float32))),
                        tree)


def _by_path(tree) -> Dict[str, float]:
    import jax
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(p.key) for p in path): math.sqrt(float(x))
            for path, x in flat}


def leaf_gaps(prog: Dict[str, float], want: Dict[str, float],
              keep=None) -> Dict[str, float]:
    """Per leaf, |prog - want| / max(want, the median leaf's want)."""
    keys = [k for k in want if keep is None or k in keep]
    med = float(np.median([want[k] for k in keys]))
    return {k: abs(prog[k] - want[k]) / max(want[k], med, 1e-30)
            for k in keys}


def compare(losses, grads, change, r_losses, r_grads, r_change) -> Dict:
    """Every number the check can compare; the cell's limits file names
    the ones it does.

    ``loss``: the widest relative loss gap over the check steps;
    ``loss1``: the first step's.  ``grad`` / ``update``: the widest leaf
    gap of the first gradient's norm / of the change's norm;
    ``grad_median`` / ``update_median``: the median leaf's.  Leaves whose
    reference gradient is under ``STILL_LEAF`` of the median leaf's are
    left out of the change."""
    med = float(np.median(list(r_grads.values())))
    moving = {k for k, v in r_grads.items() if v >= STILL_LEAF * med}
    steps = [abs(a - b) / abs(b) for a, b in zip(losses, r_losses)]
    g = leaf_gaps(grads, r_grads)
    u = leaf_gaps(change, r_change, moving)
    return {"loss": max(steps), "loss1": steps[0],
            "grad": max(g.values()), "grad_median": float(np.median(
                list(g.values()))),
            "update": max(u.values()), "update_median": float(np.median(
                list(u.values())))}


def detail(losses, grads, change, r_losses, r_grads, r_change, k=4):
    """What lies behind the numbers: each step's loss gap and the leaves
    with the widest gaps, [leaf, program norm, reference norm, gap]."""
    med = float(np.median(list(r_grads.values())))
    moving = {kk for kk, v in r_grads.items() if v >= STILL_LEAF * med}

    def widest(prog, want, keep=None):
        g = leaf_gaps(prog, want, keep)
        return [[kk, prog[kk], want[kk], g[kk]]
                for kk in sorted(g, key=g.get, reverse=True)[:k]]
    return {"loss_by_step": [abs(a - b) / abs(b)
                             for a, b in zip(losses, r_losses)],
            "grad_widest": widest(grads, r_grads),
            "update_widest": widest(change, r_change, moving),
            "still_leaves": sorted(set(r_grads) - moving)}


def faulty_step(step, jitted, fault, batch_rows):
    """The timed step broken on purpose (tests only); ``jitted`` is the
    same step before compiling, for a fault that changes the shapes."""
    if fault is None:
        return step
    if fault == "unchanged":
        def same(params, opt, batch):
            _, _, met = step(params, opt, batch)
            return params, opt, met
        return same
    if fault == "half_batch":
        half = batch_rows // 2
        return lambda p, o, b: jitted(p, o, {k: v[:half]
                                             for k, v in b.items()})
    raise ValueError(f"fault {fault!r} does not apply to a train cell")


def run(cell, config, seed, seconds, trace, devices, *, t0, clock, fault):
    import jax
    import jax.numpy as jnp

    from bench.run import (arch_config, per_layer, program_bytes,
                           runtime_peak_bytes)
    from bench.window import Window
    from repro.train.step import init_train_state, jit_train_step

    mix, m = cell["mix"], C.model(config)
    cfg = arch_config(config)
    plan = _plan(cell, fault)
    B, S = mix["batch"], mix["seq"]
    params, opt = jax.jit(lambda k: init_train_state(cfg, plan, k))(
        gen.jax_key(seed))
    toks, tgts = gen.train_pool(mix, cfg, seed)
    pool = [{"tokens": toks[i], "targets": tgts[i]} for i in range(mix["pool"])]
    # the one compiled step the check steps and the window call
    jitted = jit_train_step(cfg, None, plan, **mix["hyper"])[0]
    compiled = jitted.lower(params, opt, pool[0]).compile()
    footprint = program_bytes(compiled)
    step = faulty_step(compiled, jitted, fault, B)
    sq = jax.jit(_sq_norms)
    sq_diff = jax.jit(lambda a, b: _sq_norms(jax.tree.map(
        lambda x, y: x.astype(jnp.float32) - y.astype(jnp.float32), a, b)))
    p0 = jax.jit(lambda t: jax.tree.map(jnp.copy, t))(params)

    # check steps: the window's call and feed, on rows that all differ
    losses, grads, norm0 = [], None, None
    n_check = mix["check_steps"]
    for i in range(n_check):
        params, opt, met = step(params, opt, pool[i])
        losses.append(float(met["total_loss"]))
        if i == 0:
            grads = {k: v / (1 - ADAM_B1)
                     for k, v in _by_path(sq(opt.m)).items()}
            norm0 = float(met["grad_norm"])
    change = _by_path(sq_diff(params, p0))
    del p0
    check_batches = [(np.asarray(pool[i]["tokens"]),
                      np.asarray(pool[i]["targets"])) for i in range(n_check)]
    jax.block_until_ready((params, opt))

    # the window
    setup_s = time.perf_counter() - t0
    compiles0 = clock.compiles
    tracedir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        jax.profiler.start_trace(tracedir.name)
    inflight = deque()
    n = 0
    tw0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            with jax.profiler.TraceAnnotation("bench.dispatch"):
                params, opt, met = step(params, opt,
                                        pool[(n_check + n) % len(pool)])
            n += 1
            inflight.append(met["total_loss"])
            if len(inflight) > 2:
                with jax.profiler.TraceAnnotation("bench.wait"):
                    inflight.popleft().block_until_ready()
            if (trace and n >= mix["trace_steps"]) or (
                    not trace and time.perf_counter() - tw0 >= seconds):
                break
        jax.block_until_ready((params, opt, met))
    tw1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    last_loss = float(met["total_loss"])
    compiles = clock.compiles - compiles0
    runtime_peak = runtime_peak_bytes(devices)
    del params, opt, met, pool, toks, tgts, inflight, step, compiled
    del jitted

    # the reference, after the program's state is freed
    tr0 = time.perf_counter()
    r_losses, r_grads, r_change, r_norm0 = C.reference(config).train_steps(
        m, gen.jax_key(seed), check_batches, mix["hyper"],
        rows=mix["ref_rows"])
    got = compare(losses, grads, change, r_losses, r_grads, r_change)
    why = detail(losses, grads, change, r_losses, r_grads, r_change)
    why["grad_norm_before_clip"] = [norm0, r_norm0]
    why["every_number"] = got
    ref_s = time.perf_counter() - tr0
    lim = cell["limits"]
    compared = {k: {"value": got[k], "limit": lim[k]} for k in lim}
    correct = all(math.isfinite(got[k]) and got[k] <= lim[k]
                  for k in lim) and math.isfinite(last_loss)

    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": footprint}
    tokens = n * B * S
    notes = {"runtime_peak_bytes_in_use": runtime_peak, "steps": n, "tokens": tokens, "window_s": tw1 - tw0,
             "setup_s": setup_s, "compiles_in_window": compiles,
             "compile_s_total": clock.seconds, "check_losses": losses,
             "reference_losses": r_losses, "reference_s": ref_s,
             "last_loss": last_loss, **why}
    out = {"correct": bool(correct), "attempted": n,
           "failed": 0 if math.isfinite(last_loss) else n,
           "device": device, "compared": compared, "notes": notes}
    if not trace:
        out["metrics"] = {
            "train_tokens_per_s": {"value": tokens / (tw1 - tw0),
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        return out
    from bench import trace as tr
    t = tr.load(next(Path(tracedir.name).rglob("*.xplane.pb")))
    tracedir.cleanup()
    span = t.host_span("bench.window")
    win = Window("train", m, mix, flops.peaks(kind), len(devices), t, span,
                 tokens=tokens)
    device["busy_s"] = tr.mean_busy_s(t, span)
    device["window_s"] = win.seconds
    out["metrics"] = per_layer(cell, win)
    out["breakdown"] = {"device_ops": tr.top_ops(t, span),
                        "idle_gaps": tr.idle_gaps(t, span)}
    return out
