"""Where a serve cell's traced wave spends its time, by the engine's spans
and the model's scopes (on the chip).

    python3 bench/tools/step_scopes.py --workload <serve cell> --seed <n> \
        [--op %copy.87 ...]

Runs the cell as ``bench/run.py --trace 1`` does, prints its result line,
then one JSON line: the decode step's device time split by scope
(``embed``, ``g<i>/attn/kv_write``, the rest of ``g<i>/attn``,
``g<i>/mlp``, the layer loop's own work in ``g<i>`` by its last scope,
``head``, unscoped; operations' own time, so the parts sum to the
step's busy time), the top operations of the step and of the
prefills with their scope, each ``--op`` by program, and the window's
device-idle time by the host span it falls in.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != Path(__file__).resolve().parent]
sys.path.insert(0, str(ROOT))


def part(path: str) -> str:
    """The decomposition's part of an operation's scope path."""
    comps = path.split("/")
    for seg in ("embed", "head"):
        if seg in comps:
            return seg
    group = next((c for c in comps if c[:1] == "g" and c[1:].isdigit()), None)
    if group is None:
        return "unscoped"
    if "kv_write" in comps:
        return f"{group}/attn/kv_write"
    for half in ("attn", "mlp"):
        if half in comps:
            return f"{group}/{half}"
    return f"{group} {comps[-1]}"     # the layer loop: slices, the while


def own_times(runs, paths_of):
    """{instruction: [own seconds summed over runs, scope path]} of the
    operations of ``runs`` ([(run, ops)]); ``paths_of(i)`` is the scope
    map of run ``i``'s program."""
    from bench import serve_trace as st
    tot = {}
    for i, (_, ops) in enumerate(runs):
        paths = paths_of(i)
        for op, ns in zip(ops, st.exclusive_ns(ops)):
            name = op.name.lstrip("%")
            row = tot.setdefault(name, [0.0, paths.get(name, "")])
            row[0] += ns / 1e9
    return tot


def analyse(win, ops_wanted):
    from bench import gen
    from bench import serve_trace as st
    from bench import trace as tr
    from bench.window import PREFILL_PROGRAM
    dev, span = win.trace.devices[0], win.span
    lens = gen.prompt_lengths(win.mix)
    progs = st.serve_programs(win.model, win.mix, lens)
    maps = {k: st.scope_paths(v) for k, v in progs.items()}
    steps = st.ops_by_run(dev, st.STEP_PROGRAM, span)
    pres = st.ops_by_run(dev, PREFILL_PROGRAM, span)
    step_ops = own_times(steps, lambda i: maps["step"])
    # prefill runs come in admission order, one per request
    pre_ops = own_times(pres, lambda i: maps[win.prefills[i]])
    n = len(steps)
    by_part = {}
    for sec, path in step_ops.values():
        by_part[part(path)] = by_part.get(part(path), 0.0) + sec / n * 1e3
    busy = sum(tr.busy_ns(tr.Device("d", ops), (r.start, r.end))
               for r, ops in steps) / n / 1e6
    top = lambda d: sorted(([k, v[0], v[1]] for k, v in d.items()),  # noqa
                           key=lambda r: -r[1])[:12]
    idle = {name: st.idle_in_spans_s(win.trace, span, name)
            for name in st.SPANS}
    idle["window"] = win.seconds - tr.mean_busy_s(win.trace, span)
    unscoped = sorted(k for k, v in step_ops.items() if not v[1])
    return {
        "step_runs": n, "prefill_runs": len(pres),
        "step_run_ms": sum(r.end - r.start for r, _ in steps) / n / 1e6,
        "step_busy_ms": busy,
        "step_ms_by_scope": dict(sorted(by_part.items())),
        "step_top_ops": top(step_ops), "prefill_top_ops": top(pre_ops),
        "ops": {o: {"step": step_ops.get(o.lstrip("%")),
                    "prefill": pre_ops.get(o.lstrip("%"))}
                for o in ops_wanted},
        "idle_s_in_spans": idle,
        "span_counts": {s: len(st.spans(win.trace, s, span))
                        for s in st.SPANS},
        "step_ops_without_scope": unscoped}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--op", action="append", default=[])
    args = ap.parse_args(argv)
    from bench import run as R
    kept = {}
    read = R.per_layer

    def keep(cell, win, *a, **k):
        kept["win"] = win
        return read(cell, win, *a, **k)
    R.per_layer = keep           # bench/cell_serve.py looks it up per call
    rc = R.main(["--workload", args.workload, "--seed", str(args.seed),
                 "--seconds", "1", "--trace", "1"])
    if rc or "win" not in kept:
        return rc or 1
    print(json.dumps(analyse(kept["win"], args.op)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
