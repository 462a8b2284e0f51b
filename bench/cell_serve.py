"""Serve cell: ``ServeEngine.run`` over offline waves of requests.

Set-up makes the weights on the device from the seed in one jitted call,
compiles ahead of time the engine's two compiled entries at the shapes
the mix uses (one prefill per prompt length, one batched decode step) and
runs the engine's few eager operations once at their shapes.  The
benchmark then stands in for the engine's ``_prefill`` and ``_step``
attributes with the compiled programs wrapped in host timestamps taken
when their outputs are ready (the engine reads them right after anyway);
the timestamps map to requests through FIFO admission order and each
completion's ``admitted_step``.

The window runs whole waves, another one only while the mean wave so far
still fits in ``--seconds``.  Afterwards a sample of the finished
requests, drawn from the seed with the longest among them, is replayed
through the plain float32 reference the configuration names: the number
compared is the widest gap by which a served (greedy) token's reference
logit lies below the reference's best at that position.
"""
from __future__ import annotations

import gc
import math
import tempfile
import time
from pathlib import Path
from typing import Dict, List

import numpy as np

from bench import config as C
from bench import flops, gen


class Timed:
    """A compiled engine entry, looked up by the shape of its third
    argument, with a host timestamp once its first output is ready."""

    def __init__(self, table: Dict, name: str, keep_pos: bool = False):
        self.table, self.name, self.keep_pos = table, name, keep_pos
        self.ends: List[float] = []
        self.pos: List[np.ndarray] = []
        self.alter_at = None             # (call index, row): tests only

    def __call__(self, params, caches, x, *rest):
        import jax
        with jax.profiler.TraceAnnotation(self.name):
            out = self.table[x.shape](params, caches, x, *rest)
            jax.block_until_ready(out[0])
        self.ends.append(time.perf_counter())
        if self.keep_pos:
            self.pos.append(np.asarray(rest[0]))
        if self.alter_at is not None and len(self.ends) == self.alter_at[0]:
            row = self.alter_at[1]
            out = (out[0].at[row].set(out[0][row] + 1),) + tuple(out[1:])
        return out


def pctl(xs, q: float) -> float:
    """Nearest-rank percentile: the smallest sample with at least a share
    ``q`` of the samples at or below it."""
    s = sorted(xs)
    return s[max(0, math.ceil(q * len(s)) - 1)]


def run(cell, config, seed, seconds, trace, devices, *, t0, clock, fault):
    import jax
    import jax.numpy as jnp

    from bench.run import (arch_config, per_layer, program_bytes,
                           runtime_peak_bytes)
    from bench.window import Window
    from repro.core.plan import Plan
    from repro.models.model import cache_specs, init_cache, model_specs
    from repro.models.params import init_params
    from repro.serve import engine as eng

    mix, m = cell["mix"], C.model(config)
    cfg = arch_config(config)
    plan = Plan.load(str(cell["plan"]))
    cap, L, V = mix["capacity"], mix["cache_len"], m["vocab_size"]
    key = gen.jax_key(seed)
    params = jax.jit(lambda k: init_params(model_specs(cfg), k))(key)
    engine = eng.ServeEngine(cfg, plan, capacity=cap, cache_len=L,
                             params=params)

    # compile ahead: one prefill per prompt length the mix sends, one step
    sds = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    i32 = jnp.int32
    lens = gen.prompt_lengths(mix)
    pre = {(1, P): engine._prefill.lower(
        sds(params), cache_specs(cfg, 1, L),
        jax.ShapeDtypeStruct((1, P), i32)).compile() for P in lens}
    stp = {(cap,): engine._step.lower(
        sds(params), cache_specs(cfg, cap, L),
        jax.ShapeDtypeStruct((cap,), i32),
        jax.ShapeDtypeStruct((cap,), i32)).compile()}
    footprint = max(program_bytes(c) for c in [*pre.values(), *stp.values()])
    # the engine's eager operations at their shapes: fresh caches and the
    # splice of a filled row into each slot
    batch_cache = init_cache(cfg, cap, L)
    one = init_cache(cfg, 1, L)
    axes = eng.cache_batch_axes(cfg)
    for s in range(cap):
        batch_cache = eng._put_row(batch_cache, one, axes, s)
    jax.block_until_ready(batch_cache)
    del batch_cache, one
    engine._prefill = prefill = Timed(pre, "bench.prefill")
    engine._step = step = Timed(stp, "bench.decode", keep_pos=trace)
    if fault == "altered_token":
        step.alter_at = (3, 0)
    elif fault is not None:
        raise ValueError(f"fault {fault!r} does not apply to a serve cell")

    setup_s = time.perf_counter() - t0
    compiles0 = clock.compiles
    tracedir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        jax.profiler.start_trace(tracedir.name)
    ttft, gaps, served = [], [], []
    n_req = n_tok = n_prompt = 0
    prefills: List[int] = []
    w = 0
    tw0 = time.perf_counter()
    with jax.profiler.TraceAnnotation("bench.window"):
        while True:
            reqs = [eng.Request(rid, p, n)
                    for rid, p, n in gen.wave(mix, V, seed, w)]
            prefill.ends.clear()
            step.ends.clear()
            ws = time.perf_counter()
            done = engine.run(reqs)
            we = time.perf_counter()
            for i, r in enumerate(reqs):
                c = done[r.rid]
                a, k = c.admitted_step, len(c.tokens)
                if c.done_step != a + k - 1:
                    raise RuntimeError(f"{r.rid}: admitted at step {a}, "
                                       f"{k} tokens, done at {c.done_step}")
                times = [prefill.ends[i]] + step.ends[a:a + k - 1]
                ttft.append(times[0] - ws)
                gaps.extend(np.diff(times).tolist())
                served.append((r.prompt, c.tokens))
                n_tok += k
                n_prompt += len(r.prompt)
                prefills.append(len(r.prompt))
            n_req += len(reqs)
            w += 1
            el = we - tw0
            if (trace and w >= mix["trace_waves"]) or (
                    not trace and el + el / w > seconds):
                break
    tw1 = time.perf_counter()
    if trace:
        jax.profiler.stop_trace()
    compiles = clock.compiles - compiles0
    runtime_peak = runtime_peak_bytes(devices)
    steps_pos = list(step.pos)
    n_steps = len(step.ends)
    del engine, params, pre, stp, prefill, step
    gc.collect()

    # the reference over a sample of finished requests
    tr0 = time.perf_counter()
    widest, n_checked = check(config, key, served, mix, seed)
    ref_s = time.perf_counter() - tr0
    lim = cell["limits"]
    compared = {"gap": {"value": widest, "limit": lim["gap"]}}
    correct = math.isfinite(widest) and widest <= lim["gap"]

    kind = devices[0].device_kind
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": footprint}
    window_s = tw1 - tw0
    notes = {"runtime_peak_bytes_in_use": runtime_peak, "waves": w,
             "requests": n_req, "generated_tokens": n_tok,
             "prompt_tokens": n_prompt, "ttft_samples": len(ttft),
             "tbt_samples": len(gaps), "decode_steps_last_wave": n_steps,
             "window_s": window_s, "setup_s": setup_s,
             "prefill_programs": len(lens), "compiles_in_window": compiles,
             "compile_s_total": clock.seconds, "checked_tokens": n_checked,
             "reference_s": ref_s}
    out = {"correct": bool(correct), "attempted": n_req, "failed": 0,
           "device": device, "compared": compared, "notes": notes,
           "served": served}
    if not trace:
        out["metrics"] = {
            "serve_tokens_per_s": {"value": n_tok / window_s,
                                   "unit": "tokens/s"},
            "ttft_p95_s": {"value": pctl(ttft, 0.95), "unit": "s"},
            "tbt_p95_ms": {"value": 1e3 * pctl(gaps, 0.95), "unit": "ms"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        return out
    from bench import trace as tr
    t = tr.load(next(Path(tracedir.name).rglob("*.xplane.pb")))
    tracedir.cleanup()
    span = t.host_span("bench.window")
    filled = [[int(p) for p in pos if p > 0] for pos in steps_pos]
    win = Window("serve", m, mix, flops.peaks(kind), len(devices), t, span,
                 prefills=prefills, steps=filled)
    device["busy_s"] = tr.mean_busy_s(t, span)
    device["window_s"] = win.seconds
    out["metrics"] = per_layer(cell, win)
    out["breakdown"] = {"device_ops": tr.top_ops(t, span),
                        "idle_gaps": tr.idle_gaps(t, span)}
    return out


def sample(served, want_tokens: int, seed: int):
    """Indices of finished requests to check: the longest, then others in
    an order drawn from the seed, until ``want_tokens`` served tokens."""
    order = list(np.random.default_rng(gen._seed_words(seed, 99))
                 .permutation(len(served)))
    longest = max(range(len(served)), key=lambda i: len(served[i][1]))
    order.remove(longest)
    pick, n = [longest], len(served[longest][1])
    for i in order:
        if n >= want_tokens:
            break
        pick.append(i)
        n += len(served[i][1])
    return pick, n


def check(config, key, served, mix, seed, quant=None):
    """Widest reference-logit gap of the served tokens of a sample, by the
    reference the configuration names.  With ``quant``, the gap of the
    token the lower precision ranks first at each of those positions
    instead (the control)."""
    import jax
    import jax.numpy as jnp

    ref, m = C.reference(config), C.model(config)
    L = mix["cache_len"]
    pick, n = sample(served, mix["check_tokens"], seed)
    params = jax.jit(lambda k: ref.init_params(m, k))(key)
    gap_fn = ref.make_gap_fn(m, quant)
    widest = 0.0
    for i in pick:
        prompt, toks = served[i]
        P, k = len(prompt), len(toks)
        seq = np.zeros((1, L), np.int32)
        nxt = np.zeros((1, L), np.int32)
        seq[0, :P + k - 1] = list(prompt) + list(toks[:-1])
        nxt[0, P - 1:P - 1 + k] = toks
        g = np.asarray(gap_fn(params, jnp.asarray(seq), jnp.asarray(nxt)))
        widest = max(widest, float(np.max(g[0, P - 1:P - 1 + k])))
    del params
    return widest, n
