"""EXPERIMENTS §Perf driver: hypothesis -> change -> measure -> validate.

Three hillclimb cells (chosen from the baseline roofline table):
  A stablelm-3b x train_4k   — worst roofline fraction (dense train)
  B qwen3-moe   x train_4k   — most collective-bound (paper-representative:
                               the tuner's provider/dispatch choice)
  C granite-8b  x decode_32k — serving path, memory-bound KV reads

Each iteration is a (plan-delta, hypothesis) pair; the driver lowers the
cell on the single-pod mesh, records the three roofline terms, and prints
before/after vs the previous accepted iteration.  Results accumulate in
perf_results.json (Continue-mode like the dry-run).

    PYTHONPATH=src python -m benchmarks.perf_iterations [--cell A|B|C]
"""
import argparse
import dataclasses
import json
import os

from repro.configs import get_arch, get_shape
from repro.core.combinator import GlobalKnobs
from repro.core.plan import default_plan, uniform_plan
from repro.launch.dryrun import force_host_devices, run_cell
from repro.models.context import SegmentClause

OUT = os.path.join(os.path.dirname(__file__), "..", "perf_results.json")


def plan_variant(cfg, shape, *, provider=None, flags=None, clause_kw=None,
                 knob_kw=None):
    base = default_plan(cfg, shape)
    combo = next(iter(base.segments.values()))
    provider = provider or combo.provider
    flags = frozenset(flags) if flags is not None else combo.flags
    clause = dataclasses.replace(combo.clause, **(clause_kw or {}))
    knobs = dataclasses.replace(base.knobs, **(knob_kw or {}))
    return uniform_plan(cfg, provider, flags, clause, knobs)


ITERATIONS = {
    "A": [
        ("A0-baseline", "paper-faithful default: hybrid2d TP16, remat=dots,"
         " mb=1. Expect collective-heavy (2 ARs/layer of the bf16 residual"
         " x fwd+bwd+remat) and >16GiB/dev peak.", {}),
        ("A1-fsdp", "switch provider to fsdp[shard_both_axes]: per-layer"
         " param all-gathers (~170MB/layer) replace residual ARs"
         " (~4x335MB/layer). Napkin: collective 5.7s -> ~0.6s.",
         dict(provider="fsdp", flags={"shard_both_axes"})),
        ("A2-mb4", "A1 + microbatches=4: 4x smaller live activations ->"
         " peak bytes/dev ~/4 (fits 16GiB); terms ~unchanged (same total"
         " work).", dict(provider="fsdp", flags={"shard_both_axes"},
                         knob_kw=dict(microbatches=4))),
        ("A3-noremat", "A2 + remat=none: drop recompute; compute term"
         " -~25% (no fwd replay) at the cost of saved activations;"
         " mb=4 keeps the peak bounded.",
         dict(provider="fsdp", flags={"shard_both_axes"},
              clause_kw=dict(remat="none"), knob_kw=dict(microbatches=4))),
        ("A4-fsdp-dpom", "A1 was REFUTED because pure FSDP idles the"
         " model axis (batch only 16-way -> 16x per-chip FLOPs). Add"
         " dp_over_model: batch 256-way, params 256-way. Napkin: compute"
         " back to 0.44s, collective = per-layer param AG ~0.4s.",
         dict(provider="fsdp",
              flags={"shard_both_axes", "dp_over_model"},
              knob_kw=dict(microbatches=1))),
        ("A5-seqpar", "alternative: hybrid2d + Megatron sequence"
         " parallelism (residual stream sharded over model between"
         " blocks): AR -> RS+AG pairs, sharded saved activations."
         " Napkin: collective ~same bytes, peak /~4.",
         dict(provider="hybrid2d", flags={"shard_vocab", "seq_parallel"},
              knob_kw=dict(microbatches=4))),
    ],
    "B": [
        ("B0-baseline", "paper-faithful default: expert_par"
         "[tp_attention,fsdp_dense,2d_experts], sorted-dispatch MoE."
         " SPMD partitioner gathers dispatch buffers across expert shards"
         " -> collective-dominant (~36s est).", {}),
        ("B1-a2a", "shard_map expert-parallel dispatch: tokens stay"
         " data-sharded + replicated over model; each shard runs only its"
         " E/16 experts; ONE psum(T_local,d)/layer. Napkin: collective"
         " ~36s -> <2s.", dict(clause_kw=dict(moe_dispatch="a2a"))),
        ("B2-a2a-mb4", "B1 + microbatches=4 for peak fit"
         " (142GiB/dev baseline): activations /4.",
         dict(clause_kw=dict(moe_dispatch="a2a"),
              knob_kw=dict(microbatches=4))),
        ("B3-bf16psum", "B2 + combine partials in bf16 before the psum"
         " (f32 partial sums halve to bf16): per-layer collective bytes"
         " /2 on the MoE combine.",
         dict(clause_kw=dict(moe_dispatch="a2a"),
              knob_kw=dict(microbatches=4))),
    ],
    "D": [
        ("D0-baseline", "hybrid2d default. starcoder2 has 24 heads and"
         " kv=2: NEITHER divides the 16-way model axis, so attention"
         " falls back to fully-replicated over model = 16x redundant"
         " attention compute+memory (MF/HLO ratio ~0.1).", {}),
        ("D1-fsdp-dpom", "providers that never shard heads dodge the"
         " divisibility wall: fsdp[shard_both_axes,dp_over_model]"
         " shards batch 256-way. Napkin: compute 3.15 -> ~0.4s,"
         " memory 40 -> ~4s. This is the paper's core claim in action:"
         " the best 'compiler' differs per architecture.",
         dict(provider="fsdp",
              flags={"shard_both_axes", "dp_over_model"})),
        ("D2-mb4", "D1 + microbatches=4 to bring peak under HBM.",
         dict(provider="fsdp",
              flags={"shard_both_axes", "dp_over_model"},
              knob_kw=dict(microbatches=4))),
    ],
    "C": [
        ("C0-baseline", "paper-faithful default: tensor_par decode,"
         " f32-upcast KV reads (naive). Memory-bound: cache read traffic"
         " ~3x the bf16 cache size.", {}),
        ("C1-bf16read", "read the KV cache in bf16 with f32 accumulation"
         " (preferred_element_type): same MXU math, 1/3 the bytes."
         " Napkin: memory 0.70s -> ~0.25s.",
         dict(clause_kw=dict(cache_upcast=False))),
        ("C2-fsdp-batch", "alternative sharding: fsdp provider shards"
         " batch only (cache not seq-sharded) — hypothesis: WORSE for"
         " kv=8 (cache replicated over model axis 16); refutation case"
         " demonstrating the baseline TP choice was right.",
         dict(provider="fsdp", flags=set())),
        ("C3-shardmap", "root cause of C0's 0.68s: SPMD handles the dus"
         " into the seq-sharded cache by INVOLUNTARY FULL"
         " REMATERIALIZATION (replicate+reshard per layer, ~36x cache"
         " traffic). shard_map decode: local dus when pos is in-range +"
         " one LSE psum combine. Napkin: memory -> ~0.01s.",
         dict(clause_kw=dict(decode_shardmap=True, cache_upcast=False))),
    ],
}

CELLS = {
    "A": ("stablelm-3b", "train_4k"),
    "B": ("qwen3-moe-30b-a3b", "train_4k"),
    "C": ("granite-8b", "decode_32k"),
    "D": ("starcoder2-3b", "train_4k"),
}


def run_iterations(cell: str, timeout_s: int = 1700):
    arch, shape_name = CELLS[cell]
    cfg, shape = get_arch(arch), get_shape(shape_name)
    results = {}
    if os.path.exists(OUT):
        with open(OUT) as f:
            results = json.load(f)
    prev = None
    for name, hypothesis, delta in ITERATIONS[cell]:
        key = f"{cell}/{name}"
        if key in results and results[key].get("status") == "ok":
            rec = results[key]
            print(f"[perf] {key}: cached")
        else:
            plan = plan_variant(cfg, shape, **delta) if delta else None
            rec = run_cell(arch, shape_name, multi_pod=False, plan=plan,
                           timeout_s=timeout_s, verbose=False)
            rec["hypothesis"] = hypothesis
            results[key] = rec
            with open(OUT, "w") as f:
                json.dump(results, f, indent=1)
        if rec["status"] != "ok":
            print(f"[perf] {key} FAILED: {rec.get('error')}")
            continue
        c = rec["cost"]
        line = (f"[perf] {key}: compute={c['compute_s']:.4f} "
                f"memory={c['memory_s']:.4f} "
                f"collective={c['collective_s']:.4f} "
                f"total={c['total_s']:.4f} dom={rec['dominant']} "
                f"peak={c['bytes_per_device']/2**30:.1f}GiB")
        if prev is not None and prev["status"] == "ok":
            p = prev["cost"]
            line += (f"  [total {p['total_s']:.4f} -> {c['total_s']:.4f}, "
                     f"{p['total_s']/max(c['total_s'],1e-12):.2f}x]")
        print(line)
        prev = rec
    return results


def main():
    force_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", default=None, choices=[None, "A", "B", "C", "D"])
    ap.add_argument("--timeout", type=int, default=1700)
    args = ap.parse_args()
    cells = [args.cell] if args.cell else ["A", "B", "C", "D"]
    for c in cells:
        print(f"=== hillclimb cell {c}: {CELLS[c]} ===")
        run_iterations(c, args.timeout)


if __name__ == "__main__":
    main()
