"""What one run measured: the window, its work, and the trace."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional


@dataclasses.dataclass
class Window:
    t0: float                        # host perf_counter at the window's start
    t1: float                        # ... at its end
    items: List[dict]                # completed units (reads, requests)
    attempted: int
    failed: int
    counters: Dict[str, float]       # the program's counters, end - start
    steps: List[tuple] = dataclasses.field(default_factory=list)
    info: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


@dataclasses.dataclass
class Run:
    """Everything a metric reader may read."""
    config: dict
    window: Window
    peaks: dict
    trace: Optional[Any] = None      # bench.trace.Trace of a --trace 1 run
    setup_s: float = 0.0             # process start to the window's start


def delta(before: dict, after: dict) -> Dict[str, float]:
    return {k: after[k] - before.get(k, 0) for k in after
            if isinstance(after[k], (int, float))}
