"""Double-buffered executor: host data movement overlapped with compute.

The paper's workers sit adjacent to L2 so operand delivery overlaps the
host's own progress; the runtime equivalent is pipelining the *host* work
(padding/stacking the next bucket, the data movement) against the *device*
work (the batch in flight). Two mechanisms compose:

  1. a prefetch thread pulls items from the (lazy, host-side) work
     generator so padding for bucket i+1 happens while bucket i computes;
  2. JAX async dispatch keeps up to ``depth`` launched batches in flight;
     ``jax.block_until_ready`` fences only when a result is yielded.

``run_pipelined`` preserves input order, so callers can scatter results
back to request slots positionally.
"""

from __future__ import annotations

import queue
import threading
from collections import deque
from typing import Callable, Iterable, Iterator, TypeVar

import jax

from repro.obs import trace as obs_trace

T = TypeVar("T")
R = TypeVar("R")

_STOP = object()


def prefetched(items: Iterable[T], buffer: int = 2) -> Iterator[T]:
    """Iterate ``items`` through a background thread with a bounded queue,
    so producing the next item (host padding) overlaps consumer work.
    Exceptions in the producer re-raise at the consumer; abandoning the
    iterator (consumer raised / stopped early) stops the producer rather
    than leaving it blocked on the full queue."""
    q: "queue.Queue" = queue.Queue(maxsize=max(buffer, 1))
    stop = threading.Event()

    def put(item) -> bool:
        """Bounded put that gives up when the consumer went away."""
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue.Full:
                continue
        return False

    def worker():
        try:
            for it in items:
                if not put(it):
                    return
        except BaseException as e:            # propagate to consumer
            put((_STOP, e))
            return
        put((_STOP, None))

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    try:
        while True:
            got = q.get()
            if isinstance(got, tuple) and len(got) == 2 \
                    and got[0] is _STOP:
                if got[1] is not None:
                    raise got[1]
                return
            yield got
    finally:
        stop.set()                            # unblock a mid-put producer


def run_pipelined(items: Iterable[T], launch: Callable[[T], R],
                  depth: int = 2, buffer: int = 2) -> Iterator[R]:
    """Launch ``launch(item)`` for each work item, keeping up to ``depth``
    results in flight; yield completed results in input order.

    ``launch`` should *dispatch* device work and return promptly (JAX's
    async dispatch does this for jitted calls); the fence happens here,
    just before the result is handed to the caller — by which time the
    next batches are already padded (prefetch thread) and launched.
    Each fence is a ``repro.runtime.fence`` span: how long results in
    flight keep the host waiting (near-zero fences mean the overlap is
    doing its job).
    """
    def fence(x):
        with obs_trace.get_tracer().span("fence", "runtime"):
            return jax.block_until_ready(x)

    inflight: deque = deque()
    for item in prefetched(items, buffer=buffer):
        inflight.append(launch(item))
        while len(inflight) > max(depth, 1):
            yield fence(inflight.popleft())
    while inflight:
        yield fence(inflight.popleft())
