"""Worker-pool dispatcher — "one Squire accelerator pool per core", in JAX.

The paper attaches a pool of low-overhead workers to each host core; kernel
calls are farmed to the pool instead of running on the core. Here the pool
is the device mesh: a bucket's batch of same-shape requests is ``vmap``-ed
(the fine-grain parallel workers) and, when a mesh is installed, the batch
axis is mapped over devices with ``jax.shard_map`` (one pool per device,
mirroring ``repro.sharding``'s data axis). On the single-CPU container the
shard_map path is degenerate but identical in results, so tests exercise it
and production meshes (``repro.launch.mesh``) drop in unchanged.

Two entry points:
  * ``run(fn, leaves)``     — batched dispatch: jit(vmap(fn)) [+ shard_map],
    compiled once per (fn, in_axes, shapes) — the per-bucket compile cache.
  * ``run_one(fn, leaves)`` — single-request dispatch with the same cache
    discipline (used by ReadMapper's per-read path).

Every dispatch is timed and classified (did this call grow the compile
cache?) into the metrics registry: ``runtime.dispatch.cache_hits`` /
``cache_misses`` counters and ``compile_ms`` / ``execute_ms`` histograms
process-wide, plus a per-bucket split under
``runtime.dispatch.bucket.<fn>[b<batch>].*`` — the numbers the Autotuner
stamps into its candidate records and fig_runtime reports. With a Tracer
enabled each ``run`` also records a ``bucket-dispatch`` span on the
dispatcher track.
"""

from __future__ import annotations

import functools
import time
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

import jax
from jax.sharding import Mesh, PartitionSpec as P

from repro.obs import metrics as obs_metrics
from repro.obs import sampler as obs_sampler
from repro.obs import trace as obs_trace


# Canonical constructor lives in repro.launch.mesh (validates worker count
# against available devices up front); re-exported here for callers that
# only know the runtime layer.
from repro.launch.mesh import make_worker_mesh  # noqa: E402,F401


# Process-wide compile caches: the stage fns are already identity-stable
# (module-level / lru_cache factories), so every Dispatcher instance —
# each ReadMapper, each KernelService — shares one compiled program per
# (fn, in_axes, mesh) instead of retracing per instance.

@functools.lru_cache(maxsize=None)
def _jit_single(fn):
    return obs_trace.instrumented_jit(
        jax.jit(fn), name=getattr(fn, "__name__", "fn"),
        prefix="runtime.dispatch")


@functools.lru_cache(maxsize=None)
def _jit_batched(fn, in_axes: Tuple, mesh: Optional[Mesh], axis):
    vfn = jax.vmap(fn, in_axes=in_axes)
    if mesh is not None:
        specs = tuple(P(axis) if ax == 0 else P() for ax in in_axes)
        vfn = jax.shard_map(vfn, mesh=mesh, in_specs=specs,
                            out_specs=P(axis))
    return jax.jit(vfn)


class _BucketStats:
    """Per-bucket dispatch accounting: ``<fn>[b<batch>]`` -> hit/miss
    counts and compile/execute wall-ms totals. Registry provider
    ``runtime.dispatch.bucket`` — what the Autotuner's candidate records
    and fig_runtime's dispatch table read."""

    def __init__(self):
        self.buckets: Dict[str, Dict[str, Any]] = {}

    def record(self, key: str, compiled: bool, ms: float):
        b = self.buckets.setdefault(
            key, {"hits": 0, "misses": 0,
                  "compile_ms": 0.0, "execute_ms": 0.0})
        if compiled:
            b["misses"] += 1
            b["compile_ms"] += ms
        else:
            b["hits"] += 1
            b["execute_ms"] += ms

    def metrics(self) -> Dict[str, Any]:
        return {f"{key}.{k}": (round(v, 3) if isinstance(v, float) else v)
                for key, b in sorted(self.buckets.items())
                for k, v in b.items()}

    def clear(self):
        self.buckets.clear()


#: process-wide per-bucket dispatch stats (cleared by benchmarks that
#: want a per-run table)
BUCKET_STATS = _BucketStats()
obs_metrics.REGISTRY.register_provider("runtime.dispatch.bucket",
                                       BUCKET_STATS)


class Dispatcher:
    """Batched kernel dispatch over an optional device mesh.

    ``mesh=None`` (the default) runs jit(vmap(fn)) on the default device;
    with a 1-D mesh the vmapped program is shard_mapped over ``axis`` and
    the batch is padded to a multiple of the worker count (padding rows
    repeat the last request and are sliced off — results are positionally
    identical to the vmap path).
    """

    def __init__(self, mesh: Optional[Mesh] = None, axis: Optional[str] = None):
        self.mesh = mesh
        self.axis = axis or (mesh.axis_names[0] if mesh is not None else None)

    @property
    def num_workers(self) -> int:
        if self.mesh is None:
            return 1
        return self.mesh.devices.shape[0]

    # -- dispatch ------------------------------------------------------------

    def run(self, fn, leaves: Sequence, in_axes: Optional[Sequence] = None):
        """Dispatch one bucket batch. ``leaves`` are positional args of the
        single-request ``fn``; batched leaves carry the batch on axis 0,
        shared leaves (in_axes entry None) are broadcast to every worker.

        Returns fn's outputs with a leading batch axis (device arrays —
        dispatch is async; the pipeline fences with block_until_ready).
        """
        leaves = tuple(leaves)
        axes = tuple(0 for _ in leaves) if in_axes is None else tuple(in_axes)
        bsz = next(np.asarray(l).shape[0]
                   for l, ax in zip(leaves, axes) if ax == 0)
        w = self.num_workers
        pad = (-bsz) % w
        if pad:
            leaves = tuple(
                np.concatenate([np.asarray(l),
                                np.repeat(np.asarray(l)[-1:], pad, axis=0)])
                if ax == 0 else l
                for l, ax in zip(leaves, axes))
        jfn = _jit_batched(fn, axes, self.mesh, self.axis)
        # qualname keeps factory closures apart ('_sort_fn.run' vs
        # '_scan_fn.run' — plain __name__ is 'run' for both)
        name = getattr(fn, "__qualname__",
                       getattr(fn, "__name__", "fn")).replace(
                           ".<locals>", "")
        cache_size = getattr(jfn, "_cache_size", None)
        n0 = cache_size() if cache_size is not None else -1
        t0 = time.perf_counter()
        out = jfn(*leaves)
        t1 = time.perf_counter()
        compiled = cache_size is not None and cache_size() > n0
        reg = obs_metrics.REGISTRY
        ms = (t1 - t0) * 1e3
        if compiled:
            reg.counter("runtime.dispatch.cache_misses").inc()
            reg.histogram("runtime.dispatch.compile_ms").observe(ms)
        else:
            reg.counter("runtime.dispatch.cache_hits").inc()
            reg.histogram("runtime.dispatch.execute_ms").observe(ms)
        BUCKET_STATS.record(f"{name}[b{bsz + pad}]", compiled, ms)
        obs_trace.get_tracer().complete(
            "bucket-dispatch", "dispatcher", t0, t1, fn=name,
            batch=bsz + pad, workers=w, compiled=compiled)
        obs_sampler.tick("dispatch.run")
        if pad:
            out = jax.tree_util.tree_map(lambda x: x[:bsz], out)
        return out

    def run_one(self, fn, leaves: Sequence, jit: bool = True):
        """Single-request dispatch; jit-compiled and cached per fn unless
        ``jit=False`` (tile-jitted eager schedules manage their own cache)."""
        if not jit:
            return fn(*leaves)
        return _jit_single(fn)(*leaves)
