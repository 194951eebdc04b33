"""Reduction of a JAX profiler trace to device busy time, idle share,
kernel time by name, and the longest idle gaps by what the harness was
doing.

A run with ``--trace 1`` records one trace of its measured window. The
harness wraps its own calls in ``jax.profiler.TraceAnnotation`` spans
named ``bench.*`` (the window, each step or batch, each submit); the
device planes hold the operations the chip ran. Both lie on the
profiler's clock, so an idle gap on the device is attributed to the
innermost harness span that covers it.

``load`` keeps only what the reduction reads, as plain lists, so a
recorded trace can be checked in beside its test and read back without
the profiler.
"""

from __future__ import annotations

import bisect
import collections
import functools
import glob
import os
import re
from typing import Dict, Iterable, List, Optional, Tuple

HARNESS_PREFIX = "bench."
WINDOW = "bench.window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")

Interval = Tuple[int, int]          # [start_ns, end_ns)


def find_xplane(logdir: str) -> str:
    found = glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {logdir}, "
                                f"found {found}")
    return found[0]


def load(path: str) -> dict:
    """The parts of an ``.xplane.pb`` that the reduction reads:
    ``{"devices": {plane: {line: [[name, start_ns, dur_ns], ...]}},
    "host": [[name, start_ns, dur_ns], ...]}``, with host events limited
    to the harness's ``bench.*`` spans."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    devices: Dict[str, Dict[str, list]] = {}
    host: List[list] = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {}
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [[e.name, int(e.start_ns),
                                         int(e.duration_ns)]
                                        for e in line.events]
            devices[plane.name] = lines
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, int(e.start_ns), int(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith(HARNESS_PREFIX))
    return {"devices": devices, "host": sorted(host, key=lambda e: e[1])}


def union(intervals: Iterable[Interval]) -> List[Interval]:
    """Merge overlapping intervals; result sorted and disjoint."""
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals: Iterable[Interval], lo: int, hi: int) -> List[Interval]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


class Trace:
    """One traced window, reduced."""

    def __init__(self, raw: dict):
        self.host = [tuple(e) for e in raw["host"]]
        wins = [e for e in self.host if e[0] == WINDOW]
        if len(wins) != 1:
            raise ValueError(f"expected one {WINDOW} span, found {len(wins)}")
        _, start, dur = wins[0]
        self.lo, self.hi = start, start + dur
        self.devices = {name: lines for name, lines in raw["devices"].items()
                        if lines.get(OPS_LINE) or lines.get(MODULES_LINE)}
        if not self.devices:
            raise ValueError("no device plane ran an operation")
        self._busy: Dict[str, List[Interval]] = {}

    # -- the window ---------------------------------------------------------

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def _events(self, device: str, line: str) -> List[tuple]:
        lines = self.devices[device]
        evs = lines.get(line) or []
        return [e for e in evs if e[1] < self.hi and e[1] + e[2] > self.lo]

    def op_events(self, device: str) -> List[tuple]:
        """Operation events of ``device`` in the window (module events
        where the trace has no op line)."""
        lines = self.devices[device]
        line = OPS_LINE if lines.get(OPS_LINE) else MODULES_LINE
        return self._events(device, line)

    def busy_intervals(self, device: str) -> List[Interval]:
        if device not in self._busy:
            self._busy[device] = union(clip(
                ((e[1], e[1] + e[2]) for e in self.op_events(device)),
                self.lo, self.hi))
        return self._busy[device]

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices
        that ran any."""
        total = sum(e - s for d in self.devices
                    for s, e in self.busy_intervals(d))
        return total / len(self.devices) / 1e9

    def idle_share(self) -> float:
        return 1.0 - self.busy_s() / self.window_s

    # -- kernels and programs --------------------------------------------

    def op_seconds(self, pattern: str) -> Tuple[float, int]:
        """(summed device seconds, event count) of operations whose name
        matches ``pattern`` (a regular expression, searched), over all
        devices, clipped to the window."""
        rx = re.compile(pattern)
        total, n = 0, 0
        for d in self.devices:
            for name, s, dur in self.op_events(d):
                if rx.search(name):
                    total += min(s + dur, self.hi) - max(s, self.lo)
                    n += 1
        return total / 1e9, n

    def modules(self) -> List[tuple]:
        """Program executions (name, start_ns, dur_ns) in the window, over
        all devices, by start."""
        return sorted((e for d in self.devices
                       for e in self._events(d, MODULES_LINE)),
                      key=lambda e: e[1])

    def spans(self, name: str) -> List[tuple]:
        """Harness spans called ``name`` that lie in the window."""
        return [e for e in self.host if e[0] == name
                and e[1] >= self.lo and e[1] + e[2] <= self.hi]

    def busy_within(self, start: int, end: int) -> float:
        """Device-busy seconds inside [start, end), averaged over devices."""
        total = 0
        for d in self.devices:
            busy = self.busy_intervals(d)
            i = max(0, bisect.bisect_right(busy, (start, start)) - 1)
            while i < len(busy) and busy[i][0] < end:
                total += max(0, min(busy[i][1], end) - max(busy[i][0], start))
                i += 1
        return total / len(self.devices) / 1e9

    # -- breakdown -----------------------------------------------------------

    def top_ops(self, n: int = 10) -> List[list]:
        """The ``n`` operation names that took most device time, with
        their seconds (averaged over devices)."""
        tot: Dict[str, int] = collections.Counter()
        for d in self.devices:
            for name, s, dur in self.op_events(d):
                tot[name] += min(s + dur, self.hi) - max(s, self.lo)
        k = len(self.devices)
        return [[name, ns / k / 1e9] for name, ns in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]

    @functools.cached_property
    def _spans_by_name(self) -> Dict[str, tuple]:
        """Harness spans of each name, by start: (starts, spans). Spans of
        one name never overlap (the harness opens one at a time)."""
        by: Dict[str, list] = collections.defaultdict(list)
        for e in self.host:
            by[e[0]].append(e)
        return {k: ([e[1] for e in v], v) for k, v in by.items()}

    def _innermost(self, start: int, end: int) -> str:
        """Name of the shortest harness span covering [start, end)."""
        best: Optional[tuple] = None
        for starts, spans in self._spans_by_name.values():
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and spans[i][1] + spans[i][2] >= end:
                if best is None or spans[i][2] < best[2]:
                    best = spans[i]
        return best[0] if best is not None else "outside harness spans"

    def idle_gaps(self, n: int = 10) -> List[list]:
        """Idle device time grouped by the innermost harness span around
        each gap: the ``n`` largest groups, with their seconds (on the
        first device that ran anything)."""
        dev = sorted(self.devices)[0]
        busy = self.busy_intervals(dev)
        gaps, cur = [], self.lo
        for s, e in busy:
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.hi:
            gaps.append((cur, self.hi))
        tot: Dict[str, int] = collections.Counter()
        for s, e in gaps:
            tot[self._innermost(s, e)] += e - s
        return [[name, ns / 1e9] for name, ns in
                sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def from_logdir(logdir: str) -> Trace:
    return Trace(load(find_xplane(logdir)))
