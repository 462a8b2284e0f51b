"""Readings that set a cell's correctness limits (on the chip).

    python3 bench/tools/readings.py --cell <cell> --seeds 12 --control 3

In one process: the numbers the check compares for sound runs of the
program on ``--seeds`` seeds (the lower readings), the same numbers for
the control, the float32 reference put in the program's place but
computed on fp8 operands, on the first ``--control`` seeds (the upper
readings), and for a train cell the half-batch fault on those seeds.  A
serve cell's run is one wave; a train cell's run needs no window.  One
JSON line per reading; ``bench/limits/<cell>.json`` is set from them by
hand, with the readings recorded in PERF.md.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:] = [p for p in sys.path
               if Path(p or ".").resolve() != Path(__file__).resolve().parent]
sys.path.insert(0, str(ROOT))
sys.path.insert(0, str(ROOT / "src"))

FIRST_SEED = 3_000_000_017
#: notes of a run that show what lies behind its numbers, and its times
DETAIL = ("loss_by_step", "grad_widest", "update_widest", "still_leaves",
          "grad_norm_before_clip", "every_number", "check_losses",
          "reference_losses", "setup_s", "window_s", "reference_s",
          "decode_steps_last_wave", "checked_tokens")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=FIRST_SEED)
    ap.add_argument("--seed-list", type=int, nargs="*", default=None,
                    help="these seeds instead of --seeds from --first-seed")
    args = ap.parse_args(argv)

    import jax
    if jax.default_backend() != "tpu":
        print("readings: the limits come from the chip only", file=sys.stderr)
        return 2
    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    from bench import cell_serve, cell_train, gen
    from bench import config as C
    from bench.run import arch_config, load_cell, run_cell

    cell = load_cell(args.cell)
    cell["limits"] = {k: float("inf") for k in cell["limits"]}
    dev = jax.devices()[:cell["chips"]]
    kind = cell["mix"]["kind"]
    ref, m = C.reference(cell["config"]), C.model(cell["config"])

    def emit(what, seed, numbers, **kw):
        print(json.dumps({"reading": what, "seed": seed, **numbers, **kw}),
              flush=True)

    seeds = args.seed_list or [args.first_seed + 7919 * i
                               for i in range(args.seeds)]
    for i, seed in enumerate(seeds):
        t = time.perf_counter()
        out = run_cell(cell, seed, 0.5, False, dev, t0=t)
        emit("program", seed, {k: v["value"]
                               for k, v in out["compared"].items()},
             seconds=time.perf_counter() - t,
             notes={k: v for k, v in out["notes"].items()
                    if k in DETAIL})
        if i >= args.control:
            continue
        if kind == "train":
            t = time.perf_counter()
            bad = run_cell(cell, seed, 0.5, False, dev, t0=t,
                           fault="half_batch")
            emit("fault:half_batch", seed,
                 {k: v["value"] for k, v in bad["compared"].items()},
                 notes={k: v for k, v in bad["notes"].items()
                        if k in DETAIL})
            mix = cell["mix"]
            toks, tgts = gen.train_pool(mix, arch_config(cell["config"]),
                                        seed)
            batches = [(toks[j], tgts[j]) for j in range(mix["check_steps"])]
            del toks, tgts
            key = gen.jax_key(seed)
            want = ref.train_steps(m, key, batches, mix["hyper"],
                                   rows=mix["ref_rows"])
            low = ref.train_steps(m, key, batches, mix["hyper"],
                                  rows=mix["ref_rows"], quant="fp8")
            pair = low[:3] + want[:3]
            emit("control:fp8", seed, cell_train.compare(*pair),
                 notes=cell_train.detail(*pair))
        else:
            widest, n = cell_serve.check(cell["config"], gen.jax_key(seed),
                                         out["served"], cell["mix"], seed,
                                         quant="fp8")
            emit("control:fp8", seed, {"gap": widest}, tokens=n)
    return 0


if __name__ == "__main__":
    sys.exit(main())
