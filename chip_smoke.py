"""Chip smoke test: drive ComPar's main path once on a TPU.

One process runs the system's entry points at published widths, with
random weights made from ``--seed``, one line per phase (seconds, compile
seconds, what was checked):

  device   platform, device kind, device count, JAX version
  kernels  the five Pallas kernels, compiled, vs the jnp references in
           ``repro/kernels/ref.py``
  train    xlstm-125m (12 layers, d=768, vocab 50304) at seq 2048 through
           ``launch/train.py``: the default plan and a kernel="pallas" plan
  sweep    a wallclock ComPar sweep on that train shape (in-process); the
           fused plan then trains 3 steps through ``launch/train.py``
  serve    stablelm-3b (32 layers, d=2560, vocab 50304) through
           ``ServeEngine``, the ``launch/serve.py`` path: the default plan
           and a kernel="pallas" plan

``--chips 4`` runs only the multi-chip path: stablelm-3b training, which
one chip cannot hold, on a 1x4 (data x model) mesh: a wallclock sweep of
the fsdp / hybrid2d / tensor_par providers, then 5 steps each with the
fused, the best uniform and the default plan.

Every check raises on failure, so the script exits non-zero and prints
no result; without a TPU it refuses to run.  The last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.

    python chip_smoke.py [--chips 4] [--seed 0]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import sys
import tempfile
import time
from pathlib import Path

#: kernel -> (atol, rtol) against its reference, elementwise
#: |out - ref| <= atol + rtol * |ref|.  bf16 outputs round at ~2^-8;
#: the f32 recurrences accumulate over 2048 steps
KERNEL_TOL = {"flash_attention": (2e-2, 2e-2), "flash_decode": (2e-2, 2e-2),
              "rmsnorm": (2e-2, 2e-2), "rglru": (1e-4, 1e-3),
              "mlstm": (1e-3, 1e-2)}
#: xla vs pallas plan: relative L2 distance of the first-token logits
SERVE_LOGIT_RTOL = 2e-2
#: plans of one model: |step-0 loss difference| / step-0 loss
LOSS_RTOL = 2e-2
#: share of HBM a compiled train step must leave free
HBM_HEADROOM = 0.15

TRAIN_ARCH, TRAIN_SEQ, TRAIN_BATCH = "xlstm-125m", 2048, 2
SERVE_ARCH, SERVE_CAPACITY, SERVE_CACHE = "stablelm-3b", 4, 512
SERVE_NEW, SERVE_PROMPTS, SERVE_REQUESTS = 32, (16, 40), 8
MULTI_ARCH, MULTI_SEQ, MULTI_BATCH = "stablelm-3b", 1024, 2
MULTI_MESH = {"data": 1, "model": 4}


class CheckFailed(AssertionError):
    pass


def require(ok: bool, what: str):
    if not ok:
        raise CheckFailed(what)


class CompileClock:
    """Sums JAX's own compile-duration events (trace, lowering, backend
    compile) and counts backend compiles."""

    def __init__(self):
        self.seconds, self.compiles = 0.0, 0

    def __call__(self, event: str, secs: float, **_):
        if event.startswith("/jax/core/compile/"):
            self.seconds += secs
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1


def run_phase(name: str, fn, clock: CompileClock, *args, **kw):
    t0, c0, n0 = time.perf_counter(), clock.seconds, clock.compiles
    info = fn(*args, **kw)
    line = {"phase": name, "ok": True,
            "seconds": time.perf_counter() - t0,
            "compile_seconds": clock.seconds - c0,
            "compiles": clock.compiles - n0, **info}
    print(json.dumps(line), flush=True)
    return info


def with_kernel(plan, kernel: str):
    """``plan`` with every segment's clause switched to ``kernel``."""
    from repro.core.combinator import Combination
    from repro.core.plan import Plan
    return Plan({s: Combination(c.provider, c.flags,
                                dataclasses.replace(c.clause, kernel=kernel))
                 for s, c in plan.segments.items()},
                plan.knobs, dict(plan.meta), plan.mesh)


def program_bytes(compiled) -> int:
    """Device bytes a compiled program holds at its peak."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.output_size_in_bytes
            - ma.alias_size_in_bytes + ma.temp_size_in_bytes)


def peak_bytes(device):
    """Process-lifetime peak of ``device`` (None where not reported)."""
    return (device.memory_stats() or {}).get("peak_bytes_in_use")


def has_kernel(compiled) -> bool:
    return "tpu_custom_call" in compiled.as_text()


def on_tpu() -> bool:
    """Chip-only checks (Mosaic kernels in the program, HBM headroom) are
    skipped only when a phase is rehearsed on the CPU; main() refuses to
    run anywhere but on a TPU."""
    import jax
    return jax.default_backend() == "tpu"


# --- phases ------------------------------------------------------------------

def kernel_cases(seed: int, widths: dict):
    """(fn, reference, args) per kernel at ``widths``."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ref
    from repro.kernels.flash_attention import flash_attention_fwd
    from repro.kernels.flash_decode import flash_decode_fwd
    from repro.kernels.mlstm import mlstm_chunkwise_fwd
    from repro.kernels.rglru import rglru_fwd
    from repro.kernels.rmsnorm import rmsnorm_fwd

    keys = iter(jax.random.split(jax.random.key(seed), 16))

    def rnd(shape, dtype=jnp.float32, scale=1.0):
        return (jax.random.normal(next(keys), shape) * scale).astype(dtype)

    bf = jnp.bfloat16
    B, H, S, D = widths["attention"]
    Bd, Hd, Sd, Dd = widths["decode"]
    pos = Sd * 3 // 5
    Br, Sr, dr = widths["rglru"]
    Bm, Hm, Sm, dh = widths["mlstm"]
    N, d = widths["rmsnorm"]
    k_dec = rnd((Bd, Hd, Sd, Dd), bf)
    v_dec = rnd((Bd, Hd, Sd, Dd), bf)
    return {
        "flash_attention": (
            flash_attention_fwd, ref.flash_attention_ref,
            (rnd((B, H, S, D), bf), rnd((B, H, S, D), bf),
             rnd((B, H, S, D), bf))),
        # kernel layout (B,KV,S,D); the reference takes (B,S,KV,D)
        "flash_decode": (
            lambda q, k, v: flash_decode_fwd(q, k, v, pos),
            lambda q, k, v: ref.flash_decode_ref(
                q, k.swapaxes(1, 2), v.swapaxes(1, 2), pos),
            (rnd((Bd, Hd, Dd), bf), k_dec, v_dec)),
        "rglru": (
            rglru_fwd, ref.rglru_ref,
            (-jnp.abs(rnd((Br, Sr, dr))) * 0.2, rnd((Br, Sr, dr)))),
        "mlstm": (
            mlstm_chunkwise_fwd, ref.mlstm_ref,
            (rnd((Bm, Hm, Sm, dh), scale=dh ** -0.5), rnd((Bm, Hm, Sm, dh)),
             rnd((Bm, Hm, Sm, dh)), rnd((Bm, Hm, Sm)),
             jax.nn.log_sigmoid(rnd((Bm, Hm, Sm)) + 2.0))),
        "rmsnorm": (
            rmsnorm_fwd, ref.rmsnorm_ref,
            (rnd((N, d), bf), 1.0 + rnd((d,), scale=0.1))),
    }


#: published widths: stablelm-3b attention (H=32, D=80) at S=4096 and its
#: decode cache (B=4, 512 slots); recurrentgemma-2b RG-LRU (d_rnn=2560);
#: xlstm-125m mLSTM (H=4, dh=384) at S=2048; rmsnorm at d=2560
KERNEL_WIDTHS = {"attention": (1, 32, 4096, 80), "decode": (4, 32, 512, 80),
                 "rglru": (1, 2048, 2560), "mlstm": (1, 4, 2048, 384),
                 "rmsnorm": (4096, 2560)}


def kernels_phase(seed: int, widths: dict = KERNEL_WIDTHS):
    import jax
    import numpy as np

    out = {}
    for name, (fn, ref_fn, args) in kernel_cases(seed, widths).items():
        compiled = jax.jit(fn).lower(*args).compile()
        if on_tpu():
            require(has_kernel(compiled), f"{name}: no tpu_custom_call")
        got = np.asarray(compiled(*args), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(jax.jit(ref_fn)(*args), np.float32)
        atol, rtol = KERNEL_TOL[name]
        err = np.abs(got - want)
        require(got.shape == want.shape and np.isfinite(got).all(),
                f"{name}: shape {got.shape} vs {want.shape} or non-finite")
        worst = float(np.max(err - rtol * np.abs(want)))
        require(worst <= atol, f"{name}: error {worst:.3g} > atol {atol}")
        out[name] = {"shape": list(got.shape),
                     "max_abs_err": float(err.max()), "atol": atol,
                     "rtol": rtol}
    return {"kernels": out}


def train_phase(seed: int, workdir: str, arch: str = TRAIN_ARCH,
                seq: int = TRAIN_SEQ, batch: int = TRAIN_BATCH):
    """Default and pallas plans through ``launch/train.py``; each step
    program is first compiled ahead of time to check its HBM headroom and
    (pallas) that the kernels are in it."""
    import jax

    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.core.plan import default_plan
    from repro.launch.train import train
    from repro.train.step import (abstract_train_state, batch_specs,
                                  jit_train_step)

    cfg = get_arch(arch)
    shape = ShapeConfig("train_4k-cli", seq, batch, "train")
    base = default_plan(cfg, shape)
    dev = jax.devices()[0]
    limit = dev.memory_stats()["bytes_limit"] if on_tpu() else None
    out, first = {"arch": arch, "seq": seq, "batch": batch}, {}
    for name, plan in (("xla", base), ("pallas", with_kernel(base, "pallas"))):
        step, _ = jit_train_step(cfg, None, plan)
        compiled = step.lower(*abstract_train_state(cfg, plan),
                              batch_specs(cfg, shape)).compile()
        need = program_bytes(compiled)
        if limit is not None:
            require(need <= (1 - HBM_HEADROOM) * limit,
                    f"train {name}: step needs {need} of {limit} bytes")
            if name == "pallas":
                require(has_kernel(compiled),
                        "pallas train step has no tpu_custom_call")
        losses = train_with_plan(train, cfg, plan, workdir, name, seq,
                                 batch, steps=5, seed=seed)
        first[name] = losses[0]
        out[name] = {"losses": losses, "step_bytes": need}
    gap = abs(first["xla"] - first["pallas"]) / abs(first["xla"])
    require(gap <= LOSS_RTOL, f"step-0 losses {first} differ by {gap:.3g}")
    out["step0_rel_gap"] = gap
    if limit is not None:
        out["hbm_bytes_limit"] = limit
        out["peak_bytes_in_use"] = peak_bytes(dev)
    return out


def train_with_plan(train, cfg, plan, workdir: str, name: str, seq: int,
                    batch: int, *, steps: int, seed: int):
    """``launch/train.py`` with ``plan`` saved to a file, a fresh
    checkpoint directory, and no resume."""
    path = Path(workdir) / f"plan_{name}.json"
    plan.save(str(path))
    losses = train(["--arch", cfg.name, "--plan", str(path),
                    "--seq", str(seq), "--batch", str(batch),
                    "--steps", str(steps), "--seed", str(seed),
                    "--resume", "never", "--log-every", "1",
                    "--ckpt-dir", str(Path(workdir) / f"ckpt_{name}")])
    require(len(losses) == steps and all(map(math.isfinite, losses)),
            f"train {name}: losses {losses}")
    return losses


def failed_rows(tuner):
    rows = tuner.db.results(tuner.project)
    return rows, [f"{r['segment']} {r['combo'].label()}: {r['error']}"
                  for r in rows if r["status"] == "failed"]


def sweep_phase(seed: int, workdir: str, arch: str = TRAIN_ARCH,
                seq: int = TRAIN_SEQ, batch: int = TRAIN_BATCH):
    """Wallclock ComPar sweep in this process, then the fused plan trains
    through ``launch/train.py``.  Every point is valid on one chip, so
    any failed row fails the phase."""
    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.core import ComParTuner
    from repro.core.plan import default_plan
    from repro.launch.train import train

    cfg = get_arch(arch)
    shape = ShapeConfig("train_4k-cli", seq, batch, "train")
    tuner = ComParTuner(cfg, shape, mesh=None, executor="wallclock",
                        project="chip_smoke", timeout_s=900)
    plan, rep = tuner.sweep(
        providers=["hybrid2d", "fsdp"], max_flags=1, backend="sequential",
        clause_space={"remat": ("none", "dots"), "kernel": ("xla", "pallas")},
        knobs=default_plan(cfg, shape).knobs)
    rows, failed = failed_rows(tuner)
    for msg in failed:
        print(f"[sweep] failed row: {msg}", flush=True)
    require(not failed, f"{len(failed)} of {len(rows)} sweep rows failed")
    losses = train_with_plan(train, cfg, plan, workdir, "fused", seq, batch,
                             steps=3, seed=seed)
    return {"rows": len(rows), "failed": len(failed),
            "programs_timed": rep.n_scored,
            "fused_plan": {s: c.label() for s, c in plan.segments.items()},
            "fused_losses": losses}


def serve_phase(seed: int, arch: str = SERVE_ARCH,
                capacity: int = SERVE_CAPACITY, cache_len: int = SERVE_CACHE,
                new_tokens: int = SERVE_NEW, prompt_lens=SERVE_PROMPTS,
                n_requests: int = SERVE_REQUESTS):
    """ServeEngine under the default plan (the ``launch/serve.py``
    fallback) and a pallas plan, on one set of weights."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_arch
    from repro.launch.serve import resolve_plan
    from repro.models.model import init_cache, model_specs
    from repro.models.params import init_params
    from repro.serve.engine import Request, ServeEngine
    from repro.serve.registry import serving_shape

    cfg = get_arch(arch)
    base, _ = resolve_plan(cfg, serving_shape(capacity, cache_len))
    params = init_params(model_specs(cfg), jax.random.key(seed))
    rng = np.random.RandomState(seed)
    reqs = [Request(f"r{i}", tuple(int(t) for t in rng.randint(
        0, cfg.vocab_size, prompt_lens[i % len(prompt_lens)])), new_tokens)
        for i in range(n_requests)]
    probe = jnp.asarray([reqs[0].prompt], jnp.int32)
    out, logits = {"arch": arch, "capacity": capacity,
                   "cache_len": cache_len, "requests": n_requests}, {}
    for name, plan in (("xla", base), ("pallas", with_kernel(base, "pallas"))):
        engine = ServeEngine(cfg, plan, capacity=capacity,
                             cache_len=cache_len, params=params)
        if on_tpu() and name == "pallas":
            # the scalar-position prefill is where flash_decode runs
            prefill = engine._prefill.lower(
                params, init_cache(cfg, 1, cache_len), probe).compile()
            require(has_kernel(prefill),
                    "pallas prefill has no tpu_custom_call")
        done = engine.run(reqs)
        stats = engine.stats
        require(len(done) == n_requests and all(
            len(c.tokens) == new_tokens for c in done.values()),
            f"serve {name}: not every request completed")
        alone = engine.run(reqs, max_active=1)
        require(all(alone[r.rid].tokens == done[r.rid].tokens for r in reqs),
                f"serve {name}: batched and one-at-a-time tokens differ")
        _, last, _ = engine._prefill(params, init_cache(cfg, 1, cache_len),
                                     probe)
        logits[name] = np.asarray(last[0], np.float32)
        out[name] = {"steps": stats.n_steps, "prefills": stats.n_prefills,
                     "tokens": stats.n_tokens, "peak_active": stats.peak_active}
        del engine
    rel = float(np.linalg.norm(logits["xla"] - logits["pallas"])
                / np.linalg.norm(logits["xla"]))
    require(rel <= SERVE_LOGIT_RTOL,
            f"first-token logits differ by {rel:.3g} (relative L2)")
    out["first_logits_rel_l2"] = rel
    return out


def multi_chip_phase(seed: int, arch: str = MULTI_ARCH,
                     seq: int = MULTI_SEQ, batch: int = MULTI_BATCH,
                     mesh_axes: dict = MULTI_MESH, steps: int = 5):
    """Sweep the sharding providers on the mesh, then train the fused,
    best uniform and default plans; step-0 losses must agree."""
    import jax

    from repro.configs import get_arch
    from repro.configs.base import ShapeConfig
    from repro.core import ComParTuner
    from repro.core.plan import default_plan, uniform_plan
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_test_mesh
    from repro.train.step import init_train_state, jit_train_step

    cfg = get_arch(arch)
    shape = ShapeConfig("train_4k-cli", seq, batch, "train")
    mesh = make_test_mesh(**mesh_axes)
    base = default_plan(cfg, shape)
    clause = next(iter(base.segments.values())).clause
    tuner = ComParTuner(cfg, shape, mesh=mesh, executor="wallclock",
                        project="chip_smoke_mesh", timeout_s=900)
    fused, rep = tuner.sweep(
        providers=["fsdp", "hybrid2d", "tensor_par"], max_flags=0,
        backend="sequential", knobs=base.knobs,
        clause_space={f.name: (getattr(clause, f.name),)
                      for f in dataclasses.fields(clause)})
    rows, failed = failed_rows(tuner)
    for msg in failed:
        print(f"[multi] failed row: {msg}", flush=True)
    uniform_s = tuner.baselines(base.knobs)
    best = min(uniform_s, key=uniform_s.get)
    plans = {"fused": fused,
             "uniform": uniform_plan(cfg, best, clause=clause,
                                     knobs=base.knobs),
             "default": base}
    out = {"arch": arch, "seq": seq, "batch": batch, "mesh": mesh_axes,
           "rows": len(rows), "failed": len(failed),
           "fused_plan": {s: c.label() for s, c in fused.segments.items()},
           "uniform_s": uniform_s, "best_uniform": best}
    first = {}
    for name, plan in plans.items():
        step, sh = jit_train_step(cfg, mesh, plan)
        params, opt = init_train_state(cfg, plan, jax.random.key(seed), sh)
        data = SyntheticLM(cfg, shape, seed=seed)
        losses = []
        for s in range(steps):
            params, opt, metrics = step(params, opt, data.batch_at(s))
            losses.append(float(metrics["total_loss"]))
        del params, opt
        require(all(map(math.isfinite, losses)), f"{name}: losses {losses}")
        first[name] = losses[0]
        out[name] = {"losses": losses,
                     "peak_bytes_in_use": [peak_bytes(d)
                                           for d in mesh.devices.flat]}
    gap = (max(first.values()) - min(first.values())) / abs(first["default"])
    require(gap <= LOSS_RTOL, f"step-0 losses {first} differ by {gap:.3g}")
    out["step0_rel_gap"] = gap
    return out


# --- entry point -------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 = run only the multi-chip path")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    if device["platform"] != "tpu":
        print(f"chip_smoke: no TPU ({device}); nothing is run on another "
              f"backend", file=sys.stderr)
        return 2
    if device["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} "
              f"devices, found {device['count']}", file=sys.stderr)
        return 2

    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.runtime.backend import enable_compile_cache
    cache = enable_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    run_phase("device", lambda: dict(device, jax=jax.__version__,
                                     compile_cache=cache), clock)
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as workdir:
        if args.chips == 4:
            run_phase("multi_chip", multi_chip_phase, clock, args.seed)
        else:
            run_phase("kernels", kernels_phase, clock, args.seed)
            run_phase("train", train_phase, clock, args.seed, workdir)
            run_phase("sweep", sweep_phase, clock, args.seed, workdir)
            run_phase("serve", serve_phase, clock, args.seed)
    entries = sum(p.is_file() for p in Path(cache).rglob("*"))
    print(json.dumps({"compile_cache": cache, "entries": entries}),
          flush=True)
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
