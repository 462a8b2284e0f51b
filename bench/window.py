"""What a per-layer metric reader gets: one traced window of a cell."""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Tuple

from bench.trace import Trace

#: program (HLO module) name of the serving engine's prefill
PREFILL_PROGRAM = "jit_prefill"


@dataclass
class Window:
    kind: str                      # "train" | "serve"
    model: Dict                    # the configuration's model dict
    mix: Dict                      # the traffic mix
    peak: Dict                     # peaks.json entry of the device kind
    chips: int
    trace: Trace
    span: Tuple[int, int]          # the window on the trace's clock (ns)
    tokens: int = 0                # train: tokens trained in the window
    prefills: List[int] = field(default_factory=list)
    #: serve: per decode step, the filled positions of each active row
    steps: List[List[int]] = field(default_factory=list)

    @property
    def seconds(self) -> float:
        return (self.span[1] - self.span[0]) / 1e9

    def program_seconds(self, prefix: str) -> List[float]:
        """Device seconds of each run of a program in the window, on the
        first chip."""
        from bench.trace import module_runs
        if not self.trace.devices:
            return []
        return [(e.end - e.start) / 1e9 for e in
                module_runs(self.trace.devices[0], prefix, self.span)]
