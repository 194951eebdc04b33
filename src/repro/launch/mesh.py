"""Production meshes.

Functions, not module-level constants — importing this module never
touches jax device state (the dry-run sets
XLA_FLAGS=--xla_force_host_platform_device_count before any jax init; the
smoke tests see the single real CPU device).

Production topology (TPU v5e target):
  * single pod: (data=16, model=16) = 256 chips,
  * multi-pod:  (pod=2, data=16, model=16) = 512 chips; the 'pod' axis is
    the DCN-connected dimension — only data parallelism (gradient
    all-reduce) crosses it, never tensor/expert collectives.
"""

from __future__ import annotations

from typing import Optional

import numpy as np

import jax
from jax.sharding import AxisType, Mesh


def make_worker_mesh(num_workers: Optional[int] = None,
                     axis: str = "workers") -> Mesh:
    """1-D mesh over the first ``num_workers`` local devices (default all).

    Canonical home of the worker-mesh constructor
    (``runtime.dispatch.make_worker_mesh`` re-exports it). Raises
    ``ValueError`` up front when more workers are requested than devices
    exist — the alternative is an opaque shard_map shape error deep
    inside the first dispatch.
    """
    devs = jax.devices()
    if num_workers is None:
        n = len(devs)
    else:
        if num_workers < 1:
            raise ValueError(f"num_workers must be >= 1, got {num_workers}")
        if num_workers > len(devs):
            raise ValueError(
                f"requested {num_workers} workers but only {len(devs)} "
                f"device(s) are available; set "
                f"XLA_FLAGS=--xla_force_host_platform_device_count="
                f"{num_workers} before importing jax to force host devices")
        n = num_workers
    return Mesh(np.asarray(devs[:n]), (axis,))


def auto_mesh(shape, axes) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the installed jax defaults to
    Explicit axes, under which ``with_sharding_constraint`` (the
    sharding.partition rules) and the sharded gathers are refused."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_smoke_mesh(*, multi_pod: bool = False):
    """Tiny mesh for CI-style tests (8 forced host devices)."""
    shape = (2, 2, 2) if multi_pod else (2, 4)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)
