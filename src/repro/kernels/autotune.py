"""Hierarchical kernel-schedule autotuner (the inner sweep level).

A flat clause sweep pays a full segment-program compile per (kernel,
tile) point — a T-schedule grid multiplies the outer (provider x flags x
clause) cross-product by T.  This module tunes kernels *in isolation*
instead: it enumerates kernel schedules — ``kernel in {xla, pallas}``
crossed with the ``block_q``/``block_k``/``mlstm_chunk`` grids — per
(op, shape signature, dtype, platform), times each variant as a
standalone program (wallclock median-of-k on real devices; the
``MachineProfile``-backed dryrun estimate on the CPU container), and
persists the results in a versioned ``kernel_cache`` WAL table keyed
like ``machine_cache`` so repeat sweeps re-benchmark nothing.

The outer engine (``ComParTuner.sweep(kernel_space=..., kernel_top_k=N)``)
then carries only the **top-k surviving schedules per segment** into the
cross-product: a T-schedule grid adds at most k outer combos per
affected segment instead of xT compiles.  Exactness contract: the
kernel-aware compute floor fed into ``combo_lower_bound`` is the
trip-count-exact HLO flop count of the *variant the combination actually
uses* (and therefore >= the minimum over measured variants), measured
from the same lowering the outer program embeds — so ``prune=True``
stays exact and the fused plan still pins the true per-segment schedule.

Cache key format (mirrors ``machine.profile_key``)::

    kernel:v<KERNEL_CACHE_VERSION>:<executor cache_tag>:<op>:<dims>

with one row per (key, canonical variant key).  The executor tag
(``dryrun:<hw.name>`` / ``wallclock:r<k>:<platform>``) keeps calibrated,
constant-model and empirical timings in disjoint rows; the version bump
retires old measurement semantics without aliasing.
"""
from __future__ import annotations

import itertools
import logging
from typing import Dict, List, Optional, Sequence, Tuple

log = logging.getLogger("repro.autotune")

#: bump on any change to what the microbenchmarks measure or how rows
#: are keyed — stale-version rows are then unreachable (never trusted).
KERNEL_CACHE_VERSION = 2

#: the SegmentClause fields that select each op's schedule, in the order
#: they are keyed.  ``scan_unroll`` is deliberately absent: it shapes the
#: layer scan around the ops, never an op invocation, so it rides the
#: outer clause space unmeasured.
OP_FIELDS: Dict[str, Tuple[str, ...]] = {
    "flash_attention": ("kernel", "block_q", "block_k"),
    "flash_decode": ("kernel", "block_k"),
    "mlstm_chunkwise": ("kernel", "mlstm_chunk"),
    "rglru": ("kernel", "mlstm_chunk"),
}

#: default inner grid for ``kernel_space="auto"`` — the tile/variant
#: search the tuner runs when the caller doesn't supply one.
DEFAULT_KERNEL_SPACE: Dict[str, Tuple] = {
    "kernel": ("xla", "pallas"),
    "block_q": (256, 512),
    "block_k": (512, 1024),
    "mlstm_chunk": (128, 256),
}


def schedule_key(fields: Dict[str, object]) -> str:
    """Canonical id of one schedule point (sorted ``k=v`` join) — the
    ``kernel_cache`` variant column and the tuner-side projection key."""
    return ",".join(f"{k}={fields[k]}" for k in sorted(fields))


def clause_schedule(clause, fields: Sequence[str]) -> str:
    """Project a SegmentClause onto ``fields`` -> canonical schedule key.
    This is how the outer sweep asks "which measured variant does this
    combination use?" — shared by the combo filter and the bound."""
    return schedule_key({f: getattr(clause, f) for f in fields})


def segment_ops(cfg, shape, seg) -> Dict[str, int]:
    """op name -> invocation count in one forward pass of ``seg``.

    Mirrors the model dispatch sites exactly: attention blocks call
    ``flash_attention`` on full-sequence shapes and ``flash_decode`` on
    decode — except windowed decode, whose ring-buffer path never
    reaches the kernel dispatch (``attn_decode``).  mLSTM / RG-LRU
    blocks only dispatch on full-sequence shapes (their decode paths are
    single-step updates).  sLSTM has no kernel dispatch at all.
    """
    if seg.kind != "stack":
        return {}
    full_seq = shape.kind in ("train", "prefill")
    counts: Dict[str, int] = {}

    def add(op):
        counts[op] = counts.get(op, 0) + seg.repeats

    for k in seg.pattern:
        if k.startswith("attn"):
            if full_seq:
                add("flash_attention")
            elif shape.kind == "decode" and not cfg.window_size:
                add("flash_decode")
        elif k == "mlstm" and full_seq:
            add("mlstm_chunkwise")
        elif k == "rec" and full_seq:
            add("rglru")
    return counts


def _op_dims(op: str, cfg, shape) -> str:
    """Shape-signature component of the cache key: everything that
    determines the op's input shapes/dtype and masking."""
    B, S = shape.global_batch, shape.seq_len
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    if op == "flash_attention":
        return f"B{B}S{S}H{H}KV{KV}D{D}w{cfg.window_size}:{cfg.dtype}"
    if op == "flash_decode":
        return f"B{B}Smax{S}H{H}KV{KV}D{D}:{cfg.dtype}"
    if op == "mlstm_chunkwise":
        di = int(cfg.expand_factor * cfg.d_model)
        return f"B{B}S{S}H{H}dh{di // H}:float32"
    if op == "rglru":
        dr = int(cfg.expand_factor * cfg.d_model)
        return f"B{B}S{S}dr{dr}:float32"
    raise KeyError(op)


def cache_key(op: str, cfg, shape, tag: str) -> str:
    """Versioned ``kernel_cache`` primary key (see module docstring)."""
    return (f"kernel:v{KERNEL_CACHE_VERSION}:{tag}:{op}:"
            f"{_op_dims(op, cfg, shape)}")


# --- isolated op programs ----------------------------------------------------
#
# Each builder returns ``(fn, arg_specs)`` where fn mirrors the model
# call site byte-for-byte (same clamping, same layouts), so the measured
# lowering is the one the outer segment program embeds.

def _clamp_chunk(chunk: int, S: int) -> int:
    c = min(int(chunk), S)
    while S % c:
        c -= 1
    return c


def _op_program(op: str, fields: Dict[str, object], cfg, shape):
    import jax
    import jax.numpy as jnp

    B, S = shape.global_batch, shape.seq_len
    H, KV, D = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim_
    dt = jnp.dtype(cfg.dtype)
    f32 = jnp.dtype("float32")
    kernel = fields.get("kernel", "xla")

    if op == "flash_attention":
        q = jax.ShapeDtypeStruct((B, S, H, D), dt)
        kv = jax.ShapeDtypeStruct((B, S, KV, D), dt)
        bq, bk = int(fields["block_q"]), int(fields["block_k"])
        if kernel == "pallas":
            from repro.kernels.ops import flash_attention

            def fn(q, k, v):
                return flash_attention(q, k, v, causal=True,
                                       window=cfg.window_size,
                                       block_q=bq, block_k=bk)
        else:
            from repro.models.attention import chunked_attention

            def fn(q, k, v):
                pos = jnp.arange(S)
                return chunked_attention(q, k, v, pos_q=pos, pos_k=pos,
                                         window=cfg.window_size, q_chunk=bq)
        return fn, (q, kv, kv)

    if op == "flash_decode":
        q = jax.ShapeDtypeStruct((B, H, D), dt)
        cache = jax.ShapeDtypeStruct((B, S, KV * D), dt)   # stored rows
        bk = int(fields["block_k"])
        pos = S // 2                       # mid-cache: the typical token
        if kernel == "pallas":
            from repro.kernels.ops import flash_decode

            def fn(q, k, v):
                heads = (B, S, KV, D)
                return flash_decode(q, k.reshape(heads), v.reshape(heads),
                                    pos, block_k=bk)
        else:
            from repro.models.attention import decode_attention

            def fn(q, k, v):
                # measured with the cheaper bf16-read path: the floor
                # must stay under BOTH cache_upcast settings
                return decode_attention(q, k, v, pos, upcast=False)
        return fn, (q, cache, cache)

    if op == "mlstm_chunkwise":
        di = int(cfg.expand_factor * cfg.d_model)
        dh = di // H
        qkv = jax.ShapeDtypeStruct((B, H, S, dh), f32)
        g = jax.ShapeDtypeStruct((B, H, S), f32)
        c = _clamp_chunk(fields["mlstm_chunk"], S)
        if kernel == "pallas":
            from repro.kernels.ops import mlstm_chunkwise

            def fn(q, k, v, li, lf):
                return mlstm_chunkwise(q, k, v, li, lf, chunk=c)
        else:
            from repro.models.xlstm import mlstm_chunk

            def fn(q, k, v, li, lf):
                nc = S // c
                rs = lambda t: jnp.moveaxis(
                    t.reshape(*t.shape[:2], nc, c, *t.shape[3:]), 2, 0)
                state0 = (jnp.zeros((B, H, dh, dh), jnp.float32),
                          jnp.zeros((B, H, dh), jnp.float32),
                          jnp.zeros((B, H), jnp.float32))

                def step(state, inp):
                    h, new = mlstm_chunk(*inp, state)
                    return new, h
                _, hs = jax.lax.scan(step, state0,
                                     (rs(q), rs(k), rs(v), rs(li), rs(lf)))
                return jnp.moveaxis(hs, 0, 2).reshape(B, H, S, dh)
        return fn, (qkv, qkv, qkv, g, g)

    if op == "rglru":
        dr = int(cfg.expand_factor * cfg.d_model)
        ab = jax.ShapeDtypeStruct((B, S, dr), f32)
        if kernel == "pallas":
            from repro.kernels.ops import rglru
            c = _clamp_chunk(fields["mlstm_chunk"], S)

            def fn(log_a, b):
                return rglru(log_a, b, chunk=c)
        else:
            from repro.models.rglru import rglru_scan

            def fn(log_a, b):
                return rglru_scan(jnp.exp(log_a), b)
        return fn, (ab, ab)

    raise KeyError(op)


# --- measurement -------------------------------------------------------------

def op_variants(op: str, space: Dict[str, Tuple]) -> List[Dict[str, object]]:
    """The variant grid of one op under a (merged) clause space: the
    cross-product of its :data:`OP_FIELDS` values.  Fields absent from
    the space fall back to the SegmentClause default, so every projection
    of an outer-space combination is a measured variant."""
    from repro.models.context import SegmentClause
    default = SegmentClause()
    fields = OP_FIELDS[op]
    values = [tuple(space.get(f) or (getattr(default, f),)) for f in fields]
    return [dict(zip(fields, point))
            for point in itertools.product(*values)]


def _measure_one(op: str, fields: Dict[str, object], cfg, shape,
                 executor) -> Dict[str, object]:
    """Time one (op, schedule) variant in isolation.

    Dryrun (executor has an ``hw`` model): compile + trip-count-exact
    HLO analysis — ``time_s`` is the modeled roofline total, ``flops``
    the exact count feeding the kernel-aware pruning floor.  Wallclock:
    median-of-k measured seconds, ``flops=0`` (no floor — pruning is
    force-disabled for wallclock sweeps anyway).

    Transient failures (deadline) return ``status="transient"`` and are
    NEVER persisted; deterministic failures are cached as ``"failed"``
    so a broken variant is rejected for free on the next sweep.
    """
    from repro.core.executor import (CombinationFailed, analyze_compiled,
                                     deadline, lower_and_compile)
    try:
        # static pre-check: the op programs call the kernels directly, so
        # a tile-divisibility ERROR from the schedule lint is exactly the
        # assert the compile would die on — reject it without compiling.
        # Deterministic (rule-set) verdict, so caching it as "failed" is
        # as sound as caching the compile failure it predicts.
        from repro.analysis.rules import lint_schedule
        errs = [d for d in lint_schedule(op, fields, cfg, shape)
                if d.is_error]
        if errs:
            return {"status": "failed", "error": "static: " +
                    "; ".join(f"{d.rule}: {d.message}" for d in errs)}
        with deadline(getattr(executor, "timeout_s", None)):
            fn, args = _op_program(op, fields, cfg, shape)
            hw = getattr(executor, "hw", None)
            if hw is not None:
                lowered, compiled = lower_and_compile(fn, args, None, None)
                terms = analyze_compiled(lowered, compiled, 1, hw)
                return {"status": "done", "time_s": terms.total_s,
                        "flops": terms.flops}
            import time as _time

            import jax
            import numpy as np
            from repro.core.executor import _materialize
            concrete = [_materialize(a) for a in args]
            jitted = jax.jit(fn)
            jax.block_until_ready(jitted(*concrete))        # compile + warm
            repeats = max(1, int(getattr(executor, "repeats", 3)))
            times = []
            for _ in range(repeats):
                t0 = _time.perf_counter()
                jax.block_until_ready(jitted(*concrete))
                times.append(_time.perf_counter() - t0)
            return {"status": "done", "time_s": float(np.median(times)),
                    "flops": 0.0}
    except CombinationFailed as e:
        if getattr(e, "transient", False):
            return {"status": "transient", "error": str(e)}
        return {"status": "failed", "error": str(e)}
    except Exception as e:
        return {"status": "failed", "error": f"{type(e).__name__}: {e}"}


def measure_op(db, op: str, cfg, shape, space: Dict[str, Tuple],
               executor, use_cache: bool = True
               ) -> Tuple[Dict[str, Dict], int, int]:
    """Measure (or cache-resolve) every variant of one op.

    Returns ``(results, n_timed, n_cached)`` where results maps the
    canonical variant key -> {"status", "time_s", "flops", ...}.
    """
    tag = getattr(executor, "cache_tag", "unknown")
    key = cache_key(op, cfg, shape, tag)
    cached = db.kernel_get(key) if (db is not None and use_cache) else {}
    results: Dict[str, Dict] = {}
    fresh: Dict[str, Dict] = {}
    n_timed = 0
    for fields in op_variants(op, space):
        vkey = schedule_key(fields)
        if vkey in results:
            continue
        hit = cached.get(vkey)
        if hit is not None:
            results[vkey] = hit
            continue
        entry = _measure_one(op, fields, cfg, shape, executor)
        n_timed += 1
        results[vkey] = entry
        if entry["status"] != "transient":    # never persist load-dependent
            fresh[vkey] = entry
    if fresh and db is not None and use_cache:
        db.kernel_put_many(key, fresh)
    n_cached = len(results) - n_timed
    return results, n_timed, n_cached


# --- per-segment ranking -----------------------------------------------------

class KernelTuning:
    """The inner sweep's verdict, consumed by the outer engine.

    * ``fields``    segment name -> sorted tuple of tuned clause fields
    * ``surviving`` segment name -> set of top-k schedule keys (over the
      segment's ``fields`` projection); segments with no tuned ops are
      absent — they stay unrestricted.
    * ``floors``    segment name -> {schedule key -> certified isolated
      kernel flops} (dryrun only; wallclock measures no flops)
    * ``report``    the ``SweepReport.kernel_tuning`` observability dict
    """

    def __init__(self):
        self.fields: Dict[str, Tuple[str, ...]] = {}
        self.surviving: Dict[str, set] = {}
        self.floors: Dict[str, Dict[str, float]] = {}
        self.report: Dict[str, object] = {}

    def keeps(self, seg_name: str, clause) -> bool:
        """Does the outer sweep carry this combination for ``seg_name``?"""
        keep = self.surviving.get(seg_name)
        if keep is None:
            return True
        return clause_schedule(clause, self.fields[seg_name]) in keep

    def floor_flops(self, seg_name: str, clause) -> float:
        """Certified isolated kernel flops for this combination's
        schedule (0.0 when unmeasured — always sound)."""
        table = self.floors.get(seg_name)
        if not table:
            return 0.0
        return table.get(
            clause_schedule(clause, self.fields[seg_name]), 0.0)


def tune_segments(db, cfg, shape, segs, space: Dict[str, Tuple],
                  executor, top_k: int = 2,
                  use_cache: bool = True) -> KernelTuning:
    """Run the inner kernel sweep for every segment and rank schedules.

    Per segment: enumerate the schedule grid over the union of its ops'
    tuned fields, score each schedule as ``sum_op(count * time)`` from
    the per-op measurements, keep the ``top_k`` cheapest.  Schedules
    with any failed op variant are excluded (ComPar rejects failed
    combinations); a segment whose schedules ALL failed stays
    unrestricted — degraded, loud, never wrong.
    """
    out = KernelTuning()
    # measure each distinct op once (segments share op measurements)
    all_ops: Dict[str, int] = {}
    seg_ops: Dict[str, Dict[str, int]] = {}
    for seg in segs:
        ops = segment_ops(cfg, shape, seg)
        seg_ops[seg.name] = ops
        for op in ops:
            all_ops[op] = 1
    measured: Dict[str, Dict[str, Dict]] = {}
    n_timed = n_cached = n_failed = 0
    for op in sorted(all_ops):
        res, t, c = measure_op(db, op, cfg, shape, space, executor,
                               use_cache=use_cache)
        measured[op] = res
        n_timed += t
        n_cached += c
        n_failed += sum(1 for e in res.values() if e["status"] != "done")

    per_op_best = {
        op: min((e["time_s"], k) for k, e in res.items()
                if e["status"] == "done")[1]
        for op, res in measured.items()
        if any(e["status"] == "done" for e in res.values())}

    per_segment: Dict[str, Dict[str, int]] = {}
    has_flops = hasattr(executor, "hw")
    from repro.models.context import SegmentClause
    default = SegmentClause()
    for seg in segs:
        ops = seg_ops[seg.name]
        if not ops:
            continue
        fields = tuple(sorted({f for op in ops for f in OP_FIELDS[op]}))
        values = [tuple(space.get(f) or (getattr(default, f),))
                  for f in fields]
        ranked: List[Tuple[float, str]] = []
        floors: Dict[str, float] = {}
        n_sched = 0
        for point in itertools.product(*values):
            sched = dict(zip(fields, point))
            skey = schedule_key(sched)
            n_sched += 1
            cost = flops = 0.0
            ok = True
            for op, count in ops.items():
                vkey = schedule_key(
                    {f: sched[f] for f in OP_FIELDS[op]})
                e = measured[op].get(vkey)
                if e is None or e["status"] != "done":
                    ok = False
                    break
                cost += count * float(e["time_s"])
                flops += count * float(e.get("flops") or 0.0)
            if not ok:
                continue
            ranked.append((cost, skey))
            if has_flops:
                floors[skey] = flops
        if not ranked:
            log.warning("kernel tuning: every schedule of segment %s "
                        "failed — leaving it unrestricted", seg.name)
            per_segment[seg.name] = {"schedules": n_sched, "kept": n_sched}
            continue
        ranked.sort()                       # (cost, key): deterministic ties
        keep = {k for _, k in ranked[:max(1, int(top_k))]}
        out.fields[seg.name] = fields
        out.surviving[seg.name] = keep
        if floors:
            out.floors[seg.name] = floors
        per_segment[seg.name] = {"schedules": n_sched, "kept": len(keep)}

    out.report = {
        "n_variants": sum(len(r) for r in measured.values()),
        "n_timed": n_timed,
        "n_cached": n_cached,
        "n_failed": n_failed,
        "top_k": int(top_k),
        "per_op_best": per_op_best,
        "per_segment": per_segment,
    }
    return out
