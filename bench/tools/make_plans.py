"""Write the plan files ``bench/plans/<cell>.json``.

    python3 bench/tools/make_plans.py --cell <cell>

Serve cells get the serving ``default_plan`` that ``launch/serve.py``
resolves when no plan file and no registry is given, at the cell's
capacity and cache length; train cells the training ``default_plan`` at
the cell's shape.
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
sys.path.insert(0, str(ROOT / "src"))


def main(argv=None) -> int:
    from bench.run import arch_config
    from repro.configs.base import ShapeConfig
    from repro.core.plan import default_plan

    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    args = ap.parse_args(argv)
    with open(ROOT / "BENCHMARK.json") as f:
        spec = json.load(f)
    w = {c["name"]: c for c in spec["workloads"]}[args.cell]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]
    with open(ROOT / conf["file"]) as f:
        cfg = arch_config(json.load(f))
    with open(ROOT / "bench" / "traffic" / f"{w['traffic']}.json") as f:
        mix = json.load(f)
    out = ROOT / "bench" / "plans" / f"{args.cell}.json"
    if mix["kind"] == "serve":
        from repro.launch.serve import resolve_plan
        from repro.serve.registry import serving_shape
        plan, _ = resolve_plan(cfg, serving_shape(mix["capacity"],
                                                  mix["cache_len"]))
    else:
        shape = ShapeConfig(f"bench-{args.cell}", mix["seq"], mix["batch"],
                            "train")
        plan = default_plan(cfg, shape)
    plan.save(str(out))
    print(plan.describe())
    return 0


if __name__ == "__main__":
    sys.exit(main())
