"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent without real
hardware: ``jax.jit(step).lower(**input_specs).compile()`` must succeed on
the single-pod (16,16) mesh and the 2-pod (2,16,16) mesh for every cell,
and the compiled artifact yields the roofline terms (EXPERIMENTS §Roofline).

Results are written incrementally to a JSON file; already-done cells are
skipped on restart (the DB Continue mode, applied to the dry-run itself).

Usage:
  python -m repro.launch.dryrun --arch granite-8b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out out.json]
"""
import argparse
import json
import os
import time
import traceback
from typing import Dict, Optional

import jax

from repro.configs import ARCHS, SHAPES, get_arch, get_shape, shape_applies
from repro.configs.base import ArchConfig, ShapeConfig
from repro.core.executor import analyze_compiled, deadline
from repro.core.plan import Plan, default_plan
from repro.launch.mesh import make_production_mesh, mesh_chips


def force_host_devices():
    """Give the CPU backend 512 placeholder devices for the production
    meshes.  Appends to ``XLA_FLAGS``; takes effect only before JAX
    initializes its backends, so entry points call it first thing."""
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count=512").strip()


def input_specs(arch: str, shape_name: Optional[str] = None) -> Dict:
    """ShapeDtypeStruct stand-ins for every model input of a cell."""
    cfg = get_arch(arch)
    shape = get_shape(shape_name or "train_4k")
    if shape.kind == "train":
        from repro.train.step import batch_specs
        return {"batch": batch_specs(cfg, shape)}
    if shape.kind == "prefill":
        from repro.serve.step import prefill_input_specs
        return {"batch": prefill_input_specs(cfg, shape)}
    from repro.serve.step import decode_input_specs
    return decode_input_specs(cfg, shape)


def lower_cell(cfg: ArchConfig, shape: ShapeConfig, mesh,
               plan: Optional[Plan] = None, verbose: bool = True):
    """Build + lower + compile one cell. Returns (lowered, compiled)."""
    plan = plan or default_plan(cfg, shape)
    from repro.models.params import abstract_params, param_pspecs
    from repro.models.model import model_specs, cache_specs
    from repro.train.step import (abstract_train_state, make_train_step)
    from repro.serve.step import (cache_shardings, decode_input_specs,
                                  make_decode_step, make_prefill,
                                  prefill_input_specs)

    with jax.set_mesh(mesh):
        if shape.kind == "train":
            step, sh = make_train_step(cfg, mesh, plan)
            params, opt = abstract_train_state(cfg, plan)
            batch = input_specs(cfg.name, shape.name)["batch"]
            jitted = jax.jit(
                step,
                in_shardings=(sh["params"], sh["opt"], None),
                out_shardings=(sh["params"], sh["opt"], None),
                donate_argnums=(0, 1) if plan.knobs.donate else ())
            lowered = jitted.lower(params, opt, batch)
        elif shape.kind == "prefill":
            fn, sh = make_prefill(cfg, mesh, plan)
            from repro.models.params import abstract_params
            params = abstract_params(model_specs(cfg))
            batch = prefill_input_specs(cfg, shape)
            jitted = jax.jit(fn, in_shardings=(sh["params"], None))
            lowered = jitted.lower(params, batch)
        else:
            fn, sh = make_decode_step(cfg, mesh, plan)
            params = abstract_params(model_specs(cfg))
            caches = cache_specs(cfg, shape.global_batch, shape.seq_len)
            csh = cache_shardings(cfg, shape, mesh, plan)
            ins = decode_input_specs(cfg, shape)
            jitted = jax.jit(
                fn, in_shardings=(sh["params"], csh, None, None),
                donate_argnums=(1,))
            lowered = jitted.lower(params, caches, ins["tokens"], ins["pos"])
        compiled = lowered.compile()
    return lowered, compiled


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             plan: Optional[Plan] = None, timeout_s: int = 1800,
             verbose: bool = True) -> Dict:
    cfg = get_arch(arch)
    shape = get_shape(shape_name)
    if not shape_applies(cfg, shape):
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skip",
                "reason": "full-attention arch; long_500k needs "
                          "sub-quadratic attention (DESIGN.md)"}
    mesh = make_production_mesh(multi_pod=multi_pod)
    t0 = time.time()
    try:
        with deadline(timeout_s):
            lowered, compiled = lower_cell(cfg, shape, mesh, plan,
                                           verbose=verbose)
            terms = analyze_compiled(lowered, compiled, mesh_chips(mesh))
            mem_txt = str(compiled.memory_analysis())
    except Exception as e:
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "fail", "elapsed_s": time.time() - t0,
                "error": f"{type(e).__name__}: {e}",
                "trace": traceback.format_exc()[-2000:]}
    rec = {"arch": arch, "shape": shape_name,
           "mesh": "multi" if multi_pod else "single",
           "chips": mesh_chips(mesh), "status": "ok",
           "elapsed_s": round(time.time() - t0, 1),
           "cost": terms.as_dict(),
           "detail": terms.detail, "dominant": terms.dominant}
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} "
              f"({'2x16x16' if multi_pod else '16x16'}): "
              f"compute={terms.compute_s:.4f}s memory={terms.memory_s:.4f}s "
              f"collective={terms.collective_s:.4f}s "
              f"dominant={terms.dominant} "
              f"bytes/dev={terms.bytes_per_device/2**30:.2f}GiB "
              f"[{rec['elapsed_s']}s]")
        print(f"  memory_analysis: {mem_txt[:300]}")
    return rec


def main():
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--timeout", type=int, default=1800)
    ap.add_argument("--plan", default=None,
                    help="path to a Plan json (default: per-cell baseline)")
    args = ap.parse_args()

    plan = Plan.load(args.plan) if args.plan else None
    results = {}
    if os.path.exists(args.out):          # Continue mode
        with open(args.out) as f:
            results = json.load(f)

    cells = []
    archs = [args.arch] if args.arch else list(ARCHS)
    shapes = [args.shape] if args.shape else list(SHAPES)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for a in archs:
        for s in shapes:
            for mp in meshes:
                cells.append((a, s, mp))

    for a, s, mp in cells:
        key = f"{a}|{s}|{'multi' if mp else 'single'}"
        if key in results and results[key].get("status") in ("ok", "skip"):
            print(f"[dryrun] {key}: cached ({results[key]['status']})")
            continue
        results[key] = run_cell(a, s, multi_pod=mp, plan=plan,
                                timeout_s=args.timeout)
        with open(args.out, "w") as f:      # incremental commit
            json.dump(results, f, indent=1)

    n_ok = sum(1 for r in results.values() if r["status"] == "ok")
    n_skip = sum(1 for r in results.values() if r["status"] == "skip")
    n_fail = sum(1 for r in results.values() if r["status"] == "fail")
    print(f"[dryrun] done: ok={n_ok} skip={n_skip} fail={n_fail}")
    if n_fail:
        for k, r in results.items():
            if r["status"] == "fail":
                print(f"  FAIL {k}: {r['error']}")
        raise SystemExit(1)


if __name__ == "__main__":
    main()
