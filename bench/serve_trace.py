"""What the serving engine's own spans and the model's named scopes give
the per-layer readers of a serve cell's traced window.

Host spans: ``ServeEngine.run`` writes ``serve.run`` per call,
``serve.admit`` per admission (children ``serve.prefill``,
``serve.splice``) and ``serve.step`` per batched decode iteration
(children ``serve.dispatch``, ``serve.readback``); admit and step spans
never overlap.  Device scopes: the model runs each segment under
``jax.named_scope`` (``embed``, ``g<i>``, ``head``), each block's halves
under ``attn`` and ``mlp``, and the decode cache write under
``kv_write``; they reach the compiled program as ``op_name`` metadata,
and the trace names an operation by its instruction alone.  So the
scope of a traced operation is looked up in the compiled text of the
program it ran in: the decode step, lowered and compiled again here
exactly as the serve cell compiles it (same engine, plan and shapes; the
persistent compile cache holds it already).  An instruction name is
unique within one program, and repeats across programs, so operations
are first placed in the program run that contains them.

A program without the spans or the scopes reads nothing: every function
here then gives ``None`` or an empty result.
"""
from __future__ import annotations

import bisect
import functools
import heapq
import json
import re
import sys
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench.trace import Device, Event, Interval, Trace, clip, length, \
    subtract, union

#: program (HLO module) name of the serving engine's decode step
STEP_PROGRAM = "jit_serve_step"
#: the engine's host spans (src/repro/serve/engine.py)
RUN_SPAN = "serve.run"
ADMIT_SPAN = "serve.admit"
PREFILL_SPAN = "serve.prefill"
SPLICE_SPAN = "serve.splice"
STEP_SPAN = "serve.step"
DISPATCH_SPAN = "serve.dispatch"
READBACK_SPAN = "serve.readback"
SPANS = (RUN_SPAN, ADMIT_SPAN, PREFILL_SPAN, SPLICE_SPAN, STEP_SPAN,
         DISPATCH_SPAN, READBACK_SPAN)

_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = ")
_OP_NAME = re.compile(r"metadata=\{[^}]*?op_name=\"([^\"]*)\"")


# --- trace reductions ----------------------------------------------------------

def ops_by_run(dev: Device, prefix: str,
               window: Interval) -> List[Tuple[Event, List[Event]]]:
    """Each run of the programs whose name starts with ``prefix`` that
    starts inside ``window``, with the operations that start inside it;
    every operation is placed in the one program run that contains it."""
    runs = sorted(dev.modules, key=lambda e: e.start)
    starts = [e.start for e in runs]
    placed: List[List[Event]] = [[] for _ in runs]
    for op in dev.ops:
        i = bisect.bisect_right(starts, op.start) - 1
        if i >= 0 and op.start < runs[i].end:
            placed[i].append(op)
    lo, hi = window
    return [(r, ops) for r, ops in zip(runs, placed)
            if r.name.startswith(prefix) and lo <= r.start < hi]


def exclusive_ns(ops: Sequence[Event]) -> List[int]:
    """Each operation's own time: every instant that operations cover is
    given to the innermost one, the one that started last (a ``while``
    holds the operations of its body; their time is theirs, not its)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i].start, -ops[i].end))
    out = [0] * len(ops)
    times = sorted({t for e in ops for t in (e.start, e.end)})
    heap: List[Tuple[int, int]] = []
    j, cur = 0, None
    for t in times:
        while heap and ops[heap[0][1]].end <= cur:
            heapq.heappop(heap)
        if heap:
            out[heap[0][1]] += t - cur
        while j < len(order) and ops[order[j]].start == t:
            heapq.heappush(heap, (-j, order[j]))
            j += 1
        cur = t
    return out


def spans(trace: Trace, name: str, window: Interval) -> List[Interval]:
    """The host spans called ``name``, clipped to ``window``."""
    return clip([(e.start, e.end) for e in trace.host if e.name == name],
                window)


def idle_in_spans_s(trace: Trace, window: Interval, name: str) -> float:
    """Device-idle seconds inside the host spans called ``name`` (and
    inside ``window``), averaged over the trace's devices."""
    inside = union(spans(trace, name, window))
    if not trace.devices or not inside:
        return 0.0
    idle = 0
    for d in trace.devices:
        busy = clip(union((e.start, e.end) for e in d.ops), window)
        idle += length(subtract(inside, busy))
    return idle / len(trace.devices) / 1e9


def queue_waits_s(trace: Trace, window: Interval) -> List[float]:
    """Per admission inside ``window``: from the start of the
    ``serve.run`` call that holds it (an offline run's requests all
    arrive at the call) to the start of its ``serve.admit`` span."""
    runs = sorted((e.start, e.end) for e in trace.host if e.name == RUN_SPAN)
    starts = [s for s, _ in runs]
    out = []
    for a in spans(trace, ADMIT_SPAN, window):
        i = bisect.bisect_right(starts, a[0]) - 1
        if i >= 0 and a[0] < runs[i][1]:
            out.append((a[0] - runs[i][0]) / 1e9)
    return out


# --- scopes of the compiled decode step ------------------------------------------

def scope_paths(hlo_text: str) -> Dict[str, str]:
    """Instruction name (no ``%``) -> its ``op_name`` metadata (``""``
    where it carries none, as the copies XLA puts in), for every
    instruction of a compiled program's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            meta = _OP_NAME.search(line)
            out[m.group(1)] = meta.group(1) if meta else ""
    return out


def in_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


def serve_programs(model: Dict, mix: Dict, prompt_lens: Sequence[int] = (),
                   root: Optional[Path] = None):
    """Compiled text of the serve cell's decode step (key ``"step"``) and
    of its prefill for each of ``prompt_lens`` (key: the length), lowered
    as ``bench/cell_serve.py`` lowers them; ``None`` when no cell of
    ``BENCHMARK.json`` (under ``root``, the checkout's by default) runs
    this model under this mix."""
    import jax
    import jax.numpy as jnp

    from bench import gen
    from bench.run import arch_config, load_cell, load_json, ROOT
    from repro.core.plan import Plan
    from repro.models.model import cache_specs, model_specs
    from repro.models.params import init_params
    from repro.serve import engine as eng

    root = ROOT if root is None else root
    cell = None
    for w in load_json(root / "BENCHMARK.json")["workloads"]:
        c = load_cell(w["name"], root)
        if c["config"]["model"] == model and c["mix"] == mix:
            cell = c
            break
    if cell is None:
        return None
    cfg = arch_config({"model": model})
    cap, L = mix["capacity"], mix["cache_len"]
    sds = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    params = sds(jax.eval_shape(lambda k: init_params(model_specs(cfg), k),
                                gen.jax_key(0)))
    engine = eng.ServeEngine(cfg, Plan.load(str(cell["plan"])), capacity=cap,
                             cache_len=L, params=params)
    i32 = jnp.int32
    out = {"step": engine._step.lower(
        params, cache_specs(cfg, cap, L), jax.ShapeDtypeStruct((cap,), i32),
        jax.ShapeDtypeStruct((cap,), i32)).compile().as_text()}
    for P in prompt_lens:
        out[P] = engine._prefill.lower(
            params, cache_specs(cfg, 1, L),
            jax.ShapeDtypeStruct((1, P), i32)).compile().as_text()
    return out


@functools.lru_cache(maxsize=2)
def _step_scopes(key: str) -> Optional[Dict[str, str]]:
    model, mix = json.loads(key)
    progs = serve_programs(model, mix)
    return None if progs is None else scope_paths(progs["step"])


def step_scopes(win) -> Optional[Dict[str, str]]:
    """Instruction -> scope path of the window's decode step program."""
    return _step_scopes(json.dumps([win.model, win.mix], sort_keys=True))


def step_scope_ms(win, scope: str) -> Optional[float]:
    """Device ms per decode step of the operations whose scope path holds
    ``scope`` (their own time, nested operations apart), on the first
    chip; ``None`` where the window ran no decode step or the program
    has no such scope."""
    if win.kind != "serve" or not win.trace.devices:
        return None
    runs = ops_by_run(win.trace.devices[0], STEP_PROGRAM, win.span)
    if not runs:
        return None
    paths = step_scopes(win)
    if not paths or not any(in_scope(p, scope) for p in paths.values()):
        return None
    missing = {op.name.lstrip("%") for _, ops in runs for op in ops} \
        - set(paths)
    if missing:
        print(f"[bench] {STEP_PROGRAM}: {len(missing)} traced operations "
              f"are not instructions of the step compiled again "
              f"({sorted(missing)[:3]}); {scope!r} not read", file=sys.stderr)
        return None
    total = 0
    for _, ops in runs:
        for op, ns in zip(ops, exclusive_ns(ops)):
            if in_scope(paths.get(op.name.lstrip("%"), ""), scope):
                total += ns
    return total / len(runs) / 1e6
