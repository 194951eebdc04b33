"""What the LM traffic kinds share: request records, the drain after the
window, and the check over finished requests."""

from __future__ import annotations

import time
from typing import Dict, List

import jax

from bench.record import Window, delta
from bench.systems.lm import System


class LMLoad:
    """Base of the LM drivers. A subclass sets ``self.system`` and
    implements ``run``."""

    system: System
    seed: int
    mix: dict

    def __init__(self):
        self.records: Dict[int, dict] = {}

    def warm(self):
        self.system.warm(self.seed)

    def send(self, prompt, max_new: int) -> int:
        with jax.profiler.TraceAnnotation("bench.submit"):
            rid = self.system.submit(prompt, max_new)
        self.records[rid] = {"prompt": prompt, "max_new": max_new}
        return rid

    def step(self, steps: List[tuple]):
        s = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            done = self.system.step()
        e = time.perf_counter()
        steps.append((s, e))
        for c in done:
            self.records[c.rid]["tokens"] = c.tokens
        return done

    def drain(self, limit_s: float):
        """Step until every request is finished (no new arrivals), for at
        most ``limit_s``."""
        t = time.perf_counter()
        extra: List[tuple] = []
        with jax.profiler.TraceAnnotation("bench.drain"):
            while self.system.busy and time.perf_counter() - t < limit_s:
                self.step(extra)
        return time.perf_counter() - t

    def window(self, t0, t1, before, after, steps, info) -> Window:
        """Every request sent in the window. One still unfinished after
        the drain is missing: it counts as failed."""
        missing = 0
        for r in self.records.values():
            if "tokens" not in r:
                missing += 1
                r.update(missing=True, tokens=None)
        info = dict(info, prefill_chunk=self.system.prefill_chunk)
        return Window(t0=t0, t1=t1, items=list(self.records.values()),
                      attempted=len(self.records), failed=missing,
                      counters=delta(before, after), steps=steps,
                      info=info)

    def check(self, window: Window):
        self.system.free()
        return self.system.check(window.items, self.seed)
