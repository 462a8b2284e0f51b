"""Benchmark entry: run one cell of ``BENCHMARK.json`` on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<config>.json``), a traffic
mix (``bench/traffic/<mix>.json``, whose ``kind`` picks the train or the
serve runner), and by its own name a plan (``bench/plans/<cell>.json``,
loaded with ``Plan.load``) and the limits of its correctness check
(``bench/limits/<cell>.json``).  Per-layer metrics are readers
``bench/metrics/<metric>.py``.  A configuration names its plain reference
module (``reference``) and, where its layers are not its ``block_pattern``
alone, its ``stack``; each layer kind is ``bench/blocks/<kind>.py``, its
leaves, equations and operation counts (``bench/config.py``).  Everything
is found by name: a new cell, mix, configuration, layer kind or metric is
a new file.

Without a TPU, or with fewer chips than the cell asks for, it prints no
result and exits 2.  The last line of stdout is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the end-to-end
metrics with ``--trace 0``, the per-layer ones with ``--trace 1``),
``device``, with ``--trace 1`` ``breakdown``, and last ``compared``: each
number of the correctness check beside its limit.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
# import the benchmark as the package ``bench``; its own directory off the
# path, so that ``bench/trace.py`` never shadows the standard library
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != BENCH]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def load_json(path: Path):
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, root: Path = ROOT) -> dict:
    """Everything one cell needs, found by the names in BENCHMARK.json."""
    spec = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; have {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    bench = root / "bench"
    applies = lambda m: name in m.get("workloads", [name])  # noqa: E731
    return {
        "name": name, "chips": w["chips"],
        "config": load_json(root / conf["file"]),
        "mix": load_json(bench / "traffic" / f"{w['traffic']}.json"),
        "plan": bench / "plans" / f"{name}.json",
        "limits": load_json(bench / "limits" / f"{name}.json"),
        "end_to_end": [m for m in spec["end_to_end"] if applies(m)],
        "per_layer": [m for m in spec["per_layer"] if applies(m)],
    }


def metric_reader(name: str, root: Path = BENCH / "metrics"):
    """``read(window) -> float | None`` of ``bench/metrics/<name>.py``."""
    path = root / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def arch_config(config: dict):
    """The program's ArchConfig built from the configuration file."""
    from repro.configs.base import ArchConfig
    m = dict(config["model"])
    m["block_pattern"] = tuple(m["block_pattern"])
    return ArchConfig(**m)


class CompileClock:
    """Sums JAX's own compile-duration events and counts backend
    compiles (copied from chip_smoke.py)."""

    def __init__(self):
        self.seconds, self.compiles = 0.0, 0

    def __call__(self, event: str, secs: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1


def program_bytes(compiled) -> int:
    """Device bytes a compiled program holds at its peak: its arguments,
    outputs not aliased to them, and temporaries (copied from
    chip_smoke.py)."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def runtime_peak_bytes(devices) -> int:
    """``peak_bytes_in_use`` as the runtime reports it, on the fullest
    chip; printed beside the programs' own footprint, never reported."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def run_cell(cell: dict, seed: int, seconds: float, trace: bool, devices,
             *, t0: float, fault=None, model=None) -> dict:
    """Set up, measure, check.  ``fault`` and ``model`` (a model dict
    replacing the configuration's) serve the tests that break the timed
    path on purpose and run it at a small size."""
    import jax
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    config = dict(cell["config"])
    if model is not None:
        config["model"] = model
    kind = cell["mix"]["kind"]
    if kind == "train":
        from bench import cell_train as runner
    elif kind == "serve":
        from bench import cell_serve as runner
    else:
        raise SystemExit(f"unknown traffic kind {kind!r}")
    out = runner.run(cell, config, seed, seconds, trace, devices,
                     t0=t0, clock=clock, fault=fault)
    if not trace:
        # the result line holds the cell's end-to-end metrics; others a
        # runner measures print on an earlier line
        names = {m["name"] for m in cell["end_to_end"]}
        for k, v in out["metrics"].items():
            if k not in names:
                out["notes"][k] = v["value"]
        out["metrics"] = {k: v for k, v in out["metrics"].items()
                          if k in names}
    gc.collect()
    return out


def per_layer(cell: dict, window, root: Path = BENCH / "metrics") -> dict:
    """The cell's per-layer metrics that found something to read."""
    got = {}
    for m in cell["per_layer"]:
        v = metric_reader(m["name"], root)(window)
        if v is not None:
            got[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return got


def emit(result: dict):
    """Earlier lines, the compared numbers last on stderr, then the
    result line last on stdout."""
    for k, v in result.pop("notes").items():
        print(f"[bench] {k}: {v}", file=sys.stderr)
    compared = result["compared"]
    for k, v in compared.items():
        print(f"[check] {k} = {v['value']!r} limit {v['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    line = {k: result[k] for k in ("correct", "attempted", "failed",
                                   "metrics", "device")}
    if "breakdown" in result:
        line["breakdown"] = result["breakdown"]
    line["compared"] = compared
    print(json.dumps(line), flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"bench: no program under {ROOT / 'src'}; nothing to run",
              file=sys.stderr)
        return 2
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"bench: cell {cell['name']} needs {cell['chips']} TPU "
              f"chip(s); found {len(devices)} {devices[0].platform} "
              f"device(s); no result", file=sys.stderr)
        return 2
    # one fixed cache directory inside the checkout: only a cell's first
    # run there compiles
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    sys.path.insert(0, str(ROOT / "src"))
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices[:cell["chips"]], t0=T0)
    emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())
