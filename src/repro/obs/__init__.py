"""repro.obs — unified observability: metrics registry + structured
tracer + live sampling + SLO monitors + controllers, and the schemas
that pin the surface.

  * metrics — Counter/Gauge/Histogram under stable dotted names
              (``serve.decode_steps``, ``paging.blocks_free``,
              ``runtime.dispatch.compile_ms``) plus weakref *providers*
              so the legacy per-component ``stats()`` dicts stay the
              source of truth and one ``REGISTRY.snapshot()`` sees the
              whole stack.
  * trace   — spans on the JAX profiler's clock: while the profiler
              records, each span (the scheduler's admit / prefill-chunk /
              decode-tick, the mapper's seed / chain / align stages, the
              pipeline's fences, each instrumented jit call) is a
              ``repro.<track>.<name>`` annotation in its trace, beside
              the device's operations. With the ring enabled, typed
              span/instant/counter events (also preempt / swap / retire
              / bucket-dispatch / jit-compile / slo-fire /
              backpressure-on / metric counter tracks) go into a
              bounded ring buffer for Perfetto-by-hand use, exported to
              JSONL or Chrome trace-event JSON (drop into
              https://ui.perfetto.dev: one track per slot plus
              scheduler/dispatcher/slo/control/metrics tracks). A no-op
              when neither records.
  * sampler — tick-driven snapshot ring over the registry: timestamped
              samples, counter rates (tokens/sec, swap bytes/sec), a
              JSONL time-series export and Perfetto counter tracks —
              live numbers, no background thread.
  * slo     — declarative rules over sampled series with hysteresis
              (N consecutive breaches to fire, M to clear), alerts as
              trace events + ``obs.slo.*`` metrics.
  * control — actuators driven by fired monitors: overload backpressure
              on the scheduler, bounded online autotune re-sweeps —
              timing/admission only, never outputs.
  * schema  — documented stats() keys/types and Chrome-trace structural
              validation (what CI gates the smoke export on).
"""

from repro.obs.control import (AutotuneController, BackpressureController,
                               build_serve_loop, dispatch_imbalance_rule)
from repro.obs.metrics import (REGISTRY, Counter, Gauge, Histogram,
                               Registry, get_registry)
from repro.obs.sampler import Sample, Sampler, get_sampler, set_sampler
from repro.obs.schema import (PAGED_STATS, SCHEDULER_STATS, SLOTS_STATS,
                              validate_chrome_trace, validate_stats)
from repro.obs.slo import Monitor, Rule, SLOManager, default_serve_rules
from repro.obs.trace import (Event, Tracer, get_tracer, instrumented_jit,
                             set_tracer)

__all__ = ["REGISTRY", "Counter", "Gauge", "Histogram", "Registry",
           "get_registry", "PAGED_STATS", "SCHEDULER_STATS",
           "SLOTS_STATS", "validate_chrome_trace", "validate_stats",
           "Event", "Tracer", "get_tracer", "instrumented_jit",
           "set_tracer", "Sample", "Sampler", "get_sampler",
           "set_sampler", "Monitor", "Rule", "SLOManager",
           "default_serve_rules", "AutotuneController",
           "BackpressureController", "build_serve_loop",
           "dispatch_imbalance_rule"]
