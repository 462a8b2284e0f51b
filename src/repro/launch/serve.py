"""Serving driver: continuous-batching greedy decoding under a ComParX
plan (CPU-runnable with --smoke).

Thin CLI over :class:`repro.serve.engine.ServeEngine` and
:class:`repro.serve.registry.PlanRegistry`.  The plan resolves in order:
``--plan`` file > ``--registry-db`` lookup (keyed by the *actual*
``--batch``/``--cache-len`` serving shape, nearest-traffic-shape
fallback) > the built-in default plan.

Usage:
  python -m repro.launch.serve --arch granite-8b --smoke --tokens 32
  python -m repro.launch.serve --arch stablelm-3b --smoke --batch 4 \\
      --cache-len 64 --registry-db /tmp/registry.db --requests 6
"""
from __future__ import annotations

import argparse

import numpy as np

from repro.configs import get_arch
from repro.core.plan import Plan, default_plan
from repro.runtime.backend import enable_compile_cache
from repro.serve.engine import Request, ServeEngine
from repro.serve.registry import PlanRegistry, serving_shape


def synthetic_requests(n: int, vocab: int, *, prompt_len: int,
                       tokens: int, seed: int):
    """Deterministic seeded request stream (varying prompts/lengths)."""
    rng = np.random.RandomState(seed)
    reqs = []
    for i in range(n):
        p = max(1, prompt_len + int(rng.randint(-1, 2)))
        prompt = tuple(int(t) for t in rng.randint(0, vocab, size=p))
        reqs.append(Request(rid=f"r{i}", prompt=prompt,
                            max_new_tokens=tokens))
    return reqs


def resolve_plan(cfg, shape, *, plan_path=None, registry_db=None):
    """--plan file > registry lookup (nearest shape) > default plan."""
    if plan_path:
        return Plan.load(plan_path), f"file:{plan_path}"
    if registry_db:
        entry = PlanRegistry(registry_db).lookup(cfg, shape)
        if entry is None:
            raise SystemExit(
                f"[serve] no plan registered for {cfg.name} "
                f"{shape.kind}:{shape.seq_len}x{shape.global_batch} in "
                f"{registry_db} — run a sweep with registry= first "
                f"(python -m repro.serve.registry)")
        src = "registry" if entry.exact else f"registry~{entry.shape}"
        return entry.plan, src
    return default_plan(cfg, shape), "default"


def serve(argv=None):
    ap = argparse.ArgumentParser(prog="python -m repro.launch.serve")
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="slot capacity (the compiled batch)")
    ap.add_argument("--tokens", type=int, default=32,
                    help="max new tokens per request")
    ap.add_argument("--cache-len", type=int, default=128)
    ap.add_argument("--plan", default=None, help="plan JSON file")
    ap.add_argument("--registry-db", default=None,
                    help="resolve the plan from this PlanRegistry DB")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=4)
    ap.add_argument("--max-active", type=int, default=None,
                    help="admission throttle (1 = sequential baseline)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    # the serving shape IS the CLI's deployment: --cache-len x --batch
    shape = serving_shape(args.batch, args.cache_len)
    plan, src = resolve_plan(cfg, shape, plan_path=args.plan,
                             registry_db=args.registry_db)
    print(f"[serve] arch={cfg.name} batch={args.batch} "
          f"cache={args.cache_len} plan={src}")

    engine = ServeEngine(cfg, plan, capacity=args.batch,
                         cache_len=args.cache_len, seed=args.seed)
    reqs = synthetic_requests(args.requests, cfg.vocab_size,
                              prompt_len=args.prompt_len,
                              tokens=args.tokens, seed=args.seed)
    done = engine.run(reqs, max_active=args.max_active)
    for r in reqs:
        c = done[r.rid]
        print(f"[serve] {r.rid}: prompt={c.prompt_len} "
              f"-> {len(c.tokens)} tokens ({c.finish_reason}) "
              f"{c.tokens[:8]}{'...' if len(c.tokens) > 8 else ''}")
    print(f"[serve] {engine.stats.summary()}")
    return done


if __name__ == "__main__":
    enable_compile_cache()
    serve()
