"""Execution context threaded through model apply functions.

``SegmentClause`` is ComParX's analogue of an OpenMP ``parallel for``
directive clause set: per-segment execution hyper-parameters that the
Combinator sweeps and the Optimal Plan Generator fuses.
"""
from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Optional, Tuple

from repro.runtime.sharding import Rules


@dataclass(frozen=True)
class SegmentClause:
    remat: str = "none"          # none | dots | full
    kernel: str = "xla"          # xla | pallas
    block_q: int = 512           # attention q-chunk (xla) / q-block (pallas)
    block_k: int = 1024          # pallas k-block
    scan_unroll: int = 1         # layer-scan unroll factor
    mlstm_chunk: int = 256       # chunk length for mLSTM / linear-recurrence
    # --- beyond-paper clauses (EXPERIMENTS §Perf) ---
    moe_dispatch: str = "sorted"  # sorted | a2a (shard_map expert-parallel)
    cache_upcast: bool = True     # f32-upcast KV reads (naive) vs bf16 reads
    decode_shardmap: bool = False  # shard_map seq-sharded KV decode (LSE)

    def key(self) -> str:
        return (f"remat={self.remat},kernel={self.kernel},bq={self.block_q},"
                f"bk={self.block_k},unroll={self.scan_unroll},"
                f"mc={self.mlstm_chunk},md={self.moe_dispatch},"
                f"cu={int(self.cache_upcast)},"
                f"dsm={int(self.decode_shardmap)}")


@dataclass(frozen=True)
class ModelContext:
    rules: Rules = field(default_factory=Rules.null)
    clause: SegmentClause = SegmentClause()
    moe_groups: int = 1          # GShard-style dispatch groups
    #: pallas interpret mode; None = compiled on a TPU backend,
    #: interpreted elsewhere (repro.runtime.backend.interpret_mode)
    interpret: Optional[bool] = None
    decode: bool = False

    def with_(self, **kw) -> "ModelContext":
        return replace(self, **kw)

    def constrain(self, x, axes: Tuple[str, ...]):
        return self.rules.constrain(x, axes)
