"""Structured tracer: spans on the JAX profiler's clock, and a bounded
ring of typed span/instant events.

The paper reasons about where *cycles* go (sync overhead vs compute,
Fig. 7); the serving runtime needs the same story for where *ticks* go —
which slot was prefilling, decoding, swapped out or idle at every
moment, and which host stage kept the device waiting.

Two sinks, one API:

  * the profiler's trace — while ``jax.profiler`` is recording (e.g.
    ``jax.profiler.start_trace``), ``Tracer.span(name, track, **args)``
    opens a ``jax.profiler.TraceAnnotation`` named
    ``repro.<track>.<name>`` with ``args`` as its stats, so program
    stages land on the same clock as the device's operations and a
    device idle gap can be attributed to the host stage around it.
    ``instrumented_jit`` wraps each call in ``repro.jit.<name>``.
  * the ring — with ``Tracer(enabled=True)``, spans, instants and
    counter samples go into a bounded ring buffer (oldest events drop,
    ``dropped`` counts them — tracing never OOMs a long serve), for
    Perfetto-by-hand use through the exporters below.

Spans whose ends the caller stamps (``complete()``: per-slot phases that
straddle many ticks, ``jit-compile``) are ring-only: a profiler
annotation must open and close around the code it covers. Instants and
counter samples are ring-only too. With the profiler off and the ring
disabled, an event site costs one check (a span site returns the shared
no-op context manager).

Event kinds (``name`` on a ``track``):

  scheduler track  — ``decode-tick``, ``prefill-chunk``, ``admit`` spans;
                     ``submit`` / ``steal`` instants
  slot<N> tracks   — per-request phase spans ``prefill`` / ``decode``
                     (args carry the rid) bracketed by ``admit`` /
                     ``retire`` / ``preempt`` / ``swap-out`` /
                     ``swap-in`` instants
  map track        — the mapper's stages: ``seed``, ``chain``, ``align``
                     and, inside it, ``align.walk`` (one per shape group;
                     args ``tiles``, ``batch``) and ``align.fetch``
  runtime track    — ``fence``: the pipeline waiting on a batch in flight
  dispatcher track — ``bucket-dispatch`` spans, ``jit-compile`` spans
                     (recorded by ``instrumented_jit`` wrappers)

Exporters (of the ring):

  * ``export_jsonl``  — one event dict per line (grep/pandas-friendly).
  * ``export_chrome`` — Chrome trace-event JSON: open chrome://tracing
    or https://ui.perfetto.dev and drop the file in. One thread (track)
    per slot plus scheduler/dispatcher threads, named and sorted.

``get_tracer()`` returns the process-wide tracer (ring disabled by
default); benchmarks/examples enable the ring by installing their own
with ``set_tracer(Tracer(enabled=True))`` or by passing a Tracer
explicitly to the component (``Scheduler(..., tracer=t)``).
"""

from __future__ import annotations

import collections
import dataclasses
import json
import time
from typing import Any, Dict, List, Optional

import jax

from repro.obs import metrics as _metrics

#: True while the JAX profiler records host annotations
_profiling = jax.profiler.TraceAnnotation.is_enabled


@dataclasses.dataclass
class Event:
    """One trace event. ``ph`` follows the Chrome trace-event phases:
    'X' = complete span (``dur`` > 0 possible), 'i' = instant,
    'C' = counter sample (args carry the numeric series values)."""
    name: str
    track: str
    ph: str                     # 'X' | 'i' | 'C'
    ts: float                   # perf_counter seconds (span start)
    dur: float = 0.0            # seconds ('X' only)
    args: Optional[Dict[str, Any]] = None


class _Noop:
    """Shared do-nothing context manager — the disabled-tracer fast
    path allocates nothing per span."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NOOP = _Noop()


def _annotation(name: str, track: str, args):
    return jax.profiler.TraceAnnotation(f"repro.{track}.{name}",
                                        **(args or {}))


class _Span:
    """Open span: records a complete event at __exit__, and covers the
    same code with a profiler annotation when one was given."""

    __slots__ = ("tracer", "name", "track", "args", "t0", "ann")

    def __init__(self, tracer: "Tracer", name: str, track: str, args,
                 ann=None):
        self.tracer = tracer
        self.name = name
        self.track = track
        self.args = args
        self.t0 = 0.0
        self.ann = ann

    def __enter__(self):
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.tracer.complete(self.name, self.track, self.t0,
                             time.perf_counter(), **(self.args or {}))
        if self.ann is not None:
            self.ann.__exit__(*exc)
        return False


class Tracer:
    """Spans on the profiler's clock while it records, and a bounded
    ring buffer of Events when ``enabled``; neither == hard no-op."""

    def __init__(self, enabled: bool = False, capacity: int = 65536):
        self.enabled = enabled
        self.capacity = capacity
        self.events: "collections.deque[Event]" = collections.deque(
            maxlen=capacity)
        self.dropped = 0        # ring overwrites (oldest-first)

    # -- recording -------------------------------------------------------

    def _push(self, ev: Event):
        if len(self.events) == self.capacity:
            # ring overflow is data LOSS, not just recycling: count it
            # both locally (export metadata) and in the registry so a
            # sampler/SLO rule can alarm on a drop rate — a silent ring
            # overwrite would undermine every trace-derived conclusion
            self.dropped += 1
            _metrics.REGISTRY.counter("obs.trace.dropped").inc()
        self.events.append(ev)

    def span(self, name: str, track: str, **args):
        """``with tracer.span("decode-tick", "scheduler", live=3):`` —
        a ``repro.scheduler.decode-tick`` profiler annotation while the
        profiler records, and a complete ring event at exit when
        ``enabled``; the shared no-op when neither."""
        if not (self.enabled or _profiling()):
            return _NOOP
        if not self.enabled:
            return _annotation(name, track, args)
        return _Span(self, name, track, args or None,
                     _annotation(name, track, args) if _profiling()
                     else None)

    def instant(self, name: str, track: str, **args):
        if not self.enabled:
            return
        self._push(Event(name, track, "i", time.perf_counter(),
                         args=args or None))

    def counter(self, name: str, track: str, **values):
        """Counter sample ('C'): Perfetto renders one counter track per
        ``name`` with the numeric ``values`` series stacked — the
        sampler's live metric feeds (``tokens_per_s``, ``blocks_free``)
        next to the span tracks, so a throttling decision lines up with
        the level that triggered it."""
        if not self.enabled:
            return
        self._push(Event(name, track, "C", time.perf_counter(),
                         args={k: float(v) for k, v in values.items()}))

    def complete(self, name: str, track: str, t0: float, t1: float,
                 **args):
        """Record a span whose endpoints the caller stamped (phases that
        straddle many scheduler ticks can't use the context manager).
        Ring-only: the profiler's trace never sees it."""
        if not self.enabled:
            return
        self._push(Event(name, track, "X", t0, max(t1 - t0, 0.0),
                         args=args or None))

    def clear(self):
        self.events.clear()
        self.dropped = 0

    # -- export ----------------------------------------------------------

    @staticmethod
    def _track_order(track: str):
        """scheduler, dispatcher, then slots in numeric order."""
        if track == "scheduler":
            return (0, 0, track)
        if track == "dispatcher":
            return (1, 0, track)
        if track.startswith("slot") and track[4:].isdigit():
            return (2, int(track[4:]), track)
        return (3, 0, track)

    def chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON object (Perfetto-loadable): one pid,
        one named+sorted tid per track, ts/dur in microseconds relative
        to the first event."""
        evs = list(self.events)
        t_base = min((e.ts for e in evs), default=0.0)
        tracks = sorted({e.track for e in evs}, key=self._track_order)
        tid = {t: i for i, t in enumerate(tracks)}
        out: List[Dict[str, Any]] = []
        for t in tracks:
            out.append({"ph": "M", "pid": 1, "tid": tid[t],
                        "name": "thread_name", "args": {"name": t}})
            out.append({"ph": "M", "pid": 1, "tid": tid[t],
                        "name": "thread_sort_index",
                        "args": {"sort_index": tid[t]}})
        for e in evs:
            d: Dict[str, Any] = {"name": e.name, "ph": e.ph, "pid": 1,
                                 "tid": tid[e.track],
                                 "ts": (e.ts - t_base) * 1e6}
            if e.ph == "X":
                d["dur"] = e.dur * 1e6
            elif e.ph == "i":
                d["s"] = "t"                # instant scope: thread
            # 'C' (counter) carries its series in args, nothing extra
            if e.args:
                d["args"] = dict(e.args)
            out.append(d)
        return {"traceEvents": out, "displayTimeUnit": "ms",
                "otherData": {"dropped_events": self.dropped}}

    def export_chrome(self, path: str):
        with open(path, "w") as f:
            json.dump(self.chrome_trace(), f)

    def export_jsonl(self, path: str):
        with open(path, "w") as f:
            for e in self.events:
                f.write(json.dumps({
                    "name": e.name, "track": e.track, "ph": e.ph,
                    "ts": e.ts, "dur": e.dur, "args": e.args or {}},
                    default=str) + "\n")


#: process-wide tracer, ring disabled by default (every event site is
#: then a single check while the profiler is off)
_TRACER = Tracer(enabled=False)


def get_tracer() -> Tracer:
    return _TRACER


def set_tracer(tracer: Tracer) -> Tracer:
    """Install ``tracer`` process-wide; returns the previous one."""
    global _TRACER
    prev, _TRACER = _TRACER, tracer
    return prev


# ---------------------------------------------------------------------------
# jit instrumentation: compile-vs-execute split for cached programs
# ---------------------------------------------------------------------------

def instrumented_jit(jfn, name: str, prefix: str):
    """Wrap a ``jax.jit``-ed callable: while the profiler records, each
    call runs inside a ``repro.jit.<name>`` annotation (so programs that
    share a traced name, such as the serving steps' ``jit_run``, are
    told apart on the host plane); each call is timed, and a call
    that grew the function's compile cache (``_cache_size()`` — a new
    (shape, dtype) signature traced+compiled) is counted as a *compile*
    and recorded as a ``jit-compile`` span on the dispatcher track;
    steady-state calls count as cache hits.

    Registry names (under ``prefix``): ``.cache_hits``,
    ``.cache_misses`` counters; ``.compile_ms``, ``.execute_ms``
    histograms. Execute time is the *dispatch* wall (JAX dispatch is
    async; the pipeline fences later), so treat it as a lower bound.
    """
    cache_size = getattr(jfn, "_cache_size", None)
    reg = _metrics.REGISTRY
    hits = reg.counter(f"{prefix}.cache_hits")
    misses = reg.counter(f"{prefix}.cache_misses")
    h_compile = reg.histogram(f"{prefix}.compile_ms")
    h_execute = reg.histogram(f"{prefix}.execute_ms")

    def wrapper(*args, **kwargs):
        n0 = cache_size() if cache_size is not None else -1
        t0 = time.perf_counter()
        with (jax.profiler.TraceAnnotation(f"repro.jit.{name}")
              if _profiling() else _NOOP):
            out = jfn(*args, **kwargs)
        t1 = time.perf_counter()
        if cache_size is not None and cache_size() > n0:
            misses.inc()
            h_compile.observe((t1 - t0) * 1e3)
            get_tracer().complete("jit-compile", "dispatcher", t0, t1,
                                  fn=name)
        else:
            hits.inc()
            h_execute.observe((t1 - t0) * 1e3)
        return out

    wrapper.__name__ = getattr(jfn, "__name__", name)
    wrapper.__wrapped__ = jfn
    return wrapper
