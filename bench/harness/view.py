"""What a per-layer metric reads: the run's calls, counters and trace."""

from __future__ import annotations

import sys
from dataclasses import dataclass, field


@dataclass
class RunView:
    cell: object
    calls: list                 # driver.Call records of the window
    lowerings: int              # lowerings to MLIR inside the window
    trace: object = None        # trace.TraceView, traced runs only
    peaks: dict = field(default_factory=dict)   # peaks.json for this device

    @property
    def completed(self):
        return [c for c in self.calls if c.out is not None]

    def work(self, kernel: str):
        return self.cell.module("work", kernel)

    def note(self, text: str):
        print(f"[metric] {text}", file=sys.stderr, flush=True)
