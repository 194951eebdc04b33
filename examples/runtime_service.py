"""KernelService demo: heterogeneous dependency-bound kernel traffic
through the batched runtime (the software Squire accelerator pool).

Builds a mixed workload — chain, Smith-Waterman, DTW, radix sort, 1-D
scans, plus end-to-end read mapping against a synthetic reference — and
serves it twice: one request at a time (per-request dispatch, the
1-caller configuration the paper starts from) and as one bulk
``submit`` (bucketed, batched, pipelined). Results are asserted
identical; the wall-clock ratio is the dispatch-layer win.

    PYTHONPATH=src python examples/runtime_service.py [--requests 64]

Observability: ``--trace out.json`` records every bucket dispatch as
Chrome trace events on the dispatcher track, the pipeline's fences
(``fence`` spans on the runtime track: how long a batch in flight kept
the host waiting) and the mapper's stages on the map track (load in
https://ui.perfetto.dev); ``--metrics`` dumps the metrics registry —
``runtime.dispatch.*`` compile-cache hit/miss counts and
compile-vs-execute wall time, per-bucket splits, and per-kernel request
counts — as JSON on exit.
"""

import argparse
import json
import time

import numpy as np

from repro.data import genomics
from repro.obs import REGISTRY, Tracer, get_tracer, set_tracer
from repro.runtime import KernelService, Request, ServiceConfig


def make_workload(rng, n_requests: int, ref: np.ndarray):
    """A traffic-like mix: mostly light kernels, a few end-to-end maps."""
    reqs = []
    prof = genomics.ReadProfile("DEMO", 350, 60, 0.93)
    reads = [r for r, _ in genomics.sample_reads(ref, prof,
                                                 max(n_requests // 8, 1),
                                                 seed=7)]
    for i in range(n_requests):
        kind = i % 5
        if kind == 0:
            n = int(rng.integers(64, 256))
            reqs.append(Request("chain", {
                "q": np.sort(rng.integers(0, 400, n)).astype(np.int32),
                "r": np.sort(rng.integers(0, 5000, n)).astype(np.int32)}))
        elif kind == 1:
            reqs.append(Request("sw", {
                "a": rng.integers(0, 4, int(rng.integers(24, 96))),
                "b": rng.integers(0, 4, int(rng.integers(24, 96)))}))
        elif kind == 2:
            reqs.append(Request("dtw", {
                "s": rng.normal(size=int(rng.integers(24, 64))),
                "r": rng.normal(size=int(rng.integers(24, 64)))}))
        elif kind == 3:
            reqs.append(Request("sort", {
                "keys": rng.integers(0, 2**32, int(rng.integers(50, 400)),
                                     dtype=np.uint32)}))
        else:
            t = int(rng.integers(16, 64))
            reqs.append(Request("scan1d", {
                "a": rng.normal(size=t).astype(np.float32),
                "b": rng.normal(size=t).astype(np.float32),
                "x0": np.float32(0.0)}))
    for rd in reads:
        reqs.append(Request("map", {"read": rd}))
    return reqs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--ref", type=int, default=12_000)
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="record bucket dispatches as a Chrome trace "
                         "(open in https://ui.perfetto.dev)")
    ap.add_argument("--metrics", action="store_true",
                    help="dump the metrics registry as JSON on exit")
    args = ap.parse_args()

    if args.trace:
        set_tracer(Tracer(enabled=True))

    rng = np.random.default_rng(0)
    ref = genomics.make_reference(args.ref, seed=0)
    svc = KernelService(ServiceConfig(dtw_tile=16, sw_tile=16,
                                      seq_bucket=64), reference=ref)
    reqs = make_workload(rng, args.requests, ref)
    kinds = sorted({r.kernel for r in reqs})
    print(f"workload: {len(reqs)} requests over kernels {kinds}")

    print("warming compile caches (one program per kernel x bucket)...")
    svc.submit(reqs)
    singles = []
    for r in reqs:
        singles.extend(svc.submit([r]))

    t0 = time.perf_counter()
    batched = svc.submit(reqs)
    t_batch = time.perf_counter() - t0
    t0 = time.perf_counter()
    for r in reqs:
        svc.submit([r])
    t_single = time.perf_counter() - t0

    same = all(
        a == b if not isinstance(a, dict)
        else all(np.array_equal(a[k], b[k]) for k in a)
        for a, b in zip(batched, singles))
    print(f"batched submit   : {len(reqs)/t_batch:8.0f} req/s "
          f"({t_batch*1e3:.0f} ms)")
    print(f"per-request loop : {len(reqs)/t_single:8.0f} req/s "
          f"({t_single*1e3:.0f} ms)")
    print(f"dispatch speedup : {t_single/t_batch:.2f}x; "
          f"results identical: {same}")

    mapped = [r for r, req in zip(batched, reqs) if req.kernel == "map"]
    if mapped:
        ok = sum(1 for m in mapped if m.pos >= 0)
        print(f"mapper           : {ok}/{len(mapped)} reads mapped "
              f"(batched seed->chain->align)")

    if args.trace:
        get_tracer().export_chrome(args.trace)
        print(f"trace            : {args.trace} "
              f"({len(get_tracer().events)} events; "
              f"load in https://ui.perfetto.dev)")
    if args.metrics:
        print(json.dumps(REGISTRY.snapshot(), indent=1, sort_keys=True))


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.configure()
    main()
