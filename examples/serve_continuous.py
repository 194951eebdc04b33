"""Continuous-batching serving demo: requests arrive mid-stream, slots
recycle per decode step.

A 4-slot pool serves 10 mixed-length requests that arrive in waves. Watch
the slot lifecycle: a request is admitted the moment a slot frees (no
pad-to-the-slowest batch), its prompt is consumed as full chunks through
the batched chunk step plus a teacher-forced decode ramp, and EOS /
max-tokens eviction hands the slot to the next arrival on the same tick.
Repeat prompts at the end hit the memoizing request cache and finish
without touching the pool.

    PYTHONPATH=src python examples/serve_continuous.py --requests 10

Observability (PR 6): ``--trace out.json`` records the serve as Chrome
trace events (load in https://ui.perfetto.dev — one track per slot plus
scheduler/dispatcher tracks); ``--metrics`` dumps the flat metrics
registry (``serve.*``, ``serve.engine.*``, paging) as JSON on exit.

Sharded pool: ``--mesh N`` (with ``--paged``) splits the pool into N
shards, each owning ``--slots`` slots and its own block pool; requests
are placed on the least-loaded shard and blocked queue heads migrate to
idle shards (work stealing). With >= N devices (e.g.
``XLA_FLAGS=--xla_force_host_platform_device_count=8``) the fused steps
run through a real shard_map mesh, otherwise the vmap path — streams
are identical either way.

Closed loop (PR 7): ``--sample out.jsonl`` installs a live Sampler
ticking off every scheduler step and exports the sample ring as a JSONL
time-series (with ``--trace`` the levels also land as Perfetto counter
tracks); ``--slo`` wires a queue-wait SLO monitor with hysteresis to a
BackpressureController — pair with ``--paged --num-blocks <small>`` and
watch the admission cap engage while the alert fires and release when
the queue drains.
"""

import argparse
import json
import time

import numpy as np

import jax

from repro import configs
from repro.models import transformer as T
from repro.obs import (REGISTRY, BackpressureController, Rule, Sampler,
                       SLOManager, Tracer, set_sampler, set_tracer)
from repro.serve import Scheduler, SchedulerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv6-1.6b")
    ap.add_argument("--requests", type=int, default=10)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-prompt", type=int, default=40)
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--paged", action="store_true",
                    help="block-granular slot allocator (try with an "
                         "attention arch, e.g. --arch gemma-2b)")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged: shrink below the equal-memory default "
                         "to watch preemptions happen")
    ap.add_argument("--num-window-blocks", type=int, default=None,
                    help="paged: block budget for window-ring groups "
                         "(try --arch gemma3-12b — its sliding-window "
                         "rings page next to the global KV)")
    ap.add_argument("--dense-windows", action="store_true",
                    help="paged: keep sliding-window rings dense per "
                         "slot instead of paging them")
    ap.add_argument("--swap-budget", type=int, default=None,
                    help="preempt=swap: SwapStore byte cap — over-budget "
                         "victims fall back to recompute")
    ap.add_argument("--preempt", choices=["recompute", "swap"],
                    default="recompute",
                    help="paged: what preempt-on-OOB discards — 'swap' "
                         "parks the victim's blocks host-side and "
                         "resumes it with zero recomputed decode steps")
    ap.add_argument("--mesh", type=int, default=0, metavar="N",
                    help="paged: shard the pool over N shards (--slots "
                         "slots + --num-blocks blocks EACH); uses a real "
                         "device mesh when >= N devices exist, the vmap "
                         "path otherwise")
    ap.add_argument("--reserved", action="store_true",
                    help="paged: book blocks for prompt+max_new at "
                         "admission (QoS: admitted requests are never "
                         "preempted)")
    ap.add_argument("--trace", metavar="OUT.json", default=None,
                    help="record a Chrome trace of the serve to OUT.json "
                         "(open in https://ui.perfetto.dev)")
    ap.add_argument("--metrics", action="store_true",
                    help="dump the metrics registry as JSON on exit")
    ap.add_argument("--sample", metavar="OUT.jsonl", default=None,
                    help="live-sample the registry every scheduler step "
                         "and export the ring as JSONL (with --trace the "
                         "levels also become Perfetto counter tracks)")
    ap.add_argument("--slo", action="store_true",
                    help="close the loop: a queue-wait SLO monitor drives "
                         "a BackpressureController (admission cap + swap "
                         "preempt while firing; restored on clear)")
    args = ap.parse_args()

    if args.mesh and not args.paged:
        ap.error("--mesh requires --paged (shards own block pools)")

    if args.trace:
        set_tracer(Tracer(enabled=True))

    cfg = configs.reduced_config(args.arch)
    params = T.init_model(jax.random.PRNGKey(args.seed), cfg)
    rng = np.random.default_rng(args.seed)

    mesh = None
    if args.mesh > 1 and jax.device_count() >= args.mesh:
        from repro.launch import mesh as mesh_lib
        mesh = mesh_lib.make_worker_mesh(args.mesh, axis="slots")

    sched = Scheduler(cfg, params, SchedulerConfig(
        num_slots=args.slots, max_len=args.max_prompt + args.max_new + 8,
        prefill_chunk=16, eos_token=cfg.vocab - 1,
        allocator="paged" if args.paged else "contiguous",
        block_size=args.block_size, num_blocks=args.num_blocks,
        paged_window_attn=not args.dense_windows,
        num_window_blocks=args.num_window_blocks,
        swap_bytes_budget=args.swap_budget,
        preempt=args.preempt,
        mesh_shards=args.mesh or None,
        admission="reserved" if args.reserved else "optimistic"),
        mesh=mesh)
    if args.mesh:
        path = (f"shard_map over {args.mesh} devices" if mesh is not None
                else "vmap (single device)")
        print(f"[serve_continuous] sharded pool: {args.mesh} shards x "
              f"{args.slots} slots, {path}")

    smp = slo = None
    if args.sample or args.slo:
        smp = Sampler(counter_tracks=(
            ("serve.pending", "value"), ("serve.live", "value"),
            ("serve.generated_tokens", "rate")) if args.trace else ())
        set_sampler(smp)
    if args.slo:
        slo = SLOManager([Rule("queue_wait",
                               key="serve.queue_head_wait_s", op="<",
                               threshold=0.01, fire_after=2,
                               clear_after=2)])
        ctrl = BackpressureController(sched, admit_cap=1, preempt="swap")
        slo.subscribe(ctrl)
        smp.add_listener(slo.on_sample)

    prompts = [rng.integers(0, cfg.vocab,
                            int(rng.integers(4, args.max_prompt))
                            ).astype(np.int32)
               for _ in range(args.requests)]
    budgets = [int(rng.integers(2, args.max_new)) for _ in prompts]

    print(f"[serve_continuous] {cfg.name}: pool={args.slots} slots, "
          f"{args.requests} requests, prompt lens "
          f"{[len(p) for p in prompts]}")

    # wave 1 now, wave 2 after a few ticks — arrivals interleave decode
    half = len(prompts) // 2
    t0 = time.time()
    for p, m in zip(prompts[:half], budgets[:half]):
        sched.submit([p], max_new_tokens=m)
    tick = 0
    submitted = half
    while sched.pending or sched.live or submitted < len(prompts):
        done = sched.step()
        tick += 1
        for c in done:
            print(f"  t={tick:3d} rid={c.rid} done ({c.reason}): "
                  f"{len(c.tokens)} tokens, latency {c.latency*1e3:.0f} ms")
        if tick % 5 == 0 and submitted < len(prompts):   # wave 2 trickles in
            sched.submit([prompts[submitted]],
                         max_new_tokens=budgets[submitted])
            print(f"  t={tick:3d} arrival rid={submitted} "
                  f"(live={sched.live}, free={sched.slots.free_count})")
            submitted += 1
    wall = time.time() - t0

    # zipfian repeats: served from the request cache, zero decode steps
    rep = sched.submit([prompts[0], prompts[0], prompts[0]],
                       max_new_tokens=budgets[0])
    sched.drain()
    st = sched.stats()
    print(f"[serve_continuous] {st['completed']} servings in {wall:.1f}s "
          f"({st['generated_tokens']} tokens, {st['decode_steps']} decode "
          f"steps, {st['chunk_steps']} chunk steps)")
    print(f"[serve_continuous] repeat submits: "
          f"{[sched.results[r].reason for r in rep]} "
          f"(cache hit rate {sched.request_cache.hit_rate:.2f})")
    if args.paged:
        rings = {k: v for k, v in st.items()
                 if k.startswith("ring") and k.endswith("_total")}
        print(f"[serve_continuous] paged allocator: "
              f"{st['blocks_total']} blocks x {st['block_size']} positions "
              f"in {st['page_groups']} page-table group(s)"
              + (f" (window rings: {rings})" if rings else "") + ", "
              f"{st.get('preempted', 0)} preemptions "
              f"({args.preempt}: {st.get('recomputed_decode_steps', 0)} "
              f"recomputed decode steps, "
              f"{st.get('swap_bytes_out', 0)} bytes swapped out, "
              f"{st.get('swap_rejected', 0)} swap rejections), "
              f"mean occupancy {st.get('mean_occupancy', 0):.2f}")
    if args.mesh:
        sm = sched._shard_obs.metrics()
        per = [f"shard{s}: placed={sm[f'shard{s}.placed']} "
               f"stolen_in={sm[f'shard{s}.steals']} "
               f"blocks_used={sm[f'shard{s}.blocks_used']}"
               for s in range(sm["num_shards"])]
        print(f"[serve_continuous] shards ({sm['steals']} steals): "
              + "; ".join(per))
    if args.trace:
        from repro.obs import get_tracer
        get_tracer().export_chrome(args.trace)
        print(f"[serve_continuous] trace -> {args.trace} "
              f"({len(get_tracer().events)} events; "
              f"load in https://ui.perfetto.dev)")
    if args.slo:
        snap = REGISTRY.snapshot()
        print(f"[serve_continuous] closed loop: queue_wait fired "
              f"{snap['obs.slo.queue_wait.fired']}x, backpressure "
              f"engaged {snap['obs.control.backpressure.engaged']}x "
              f"(firing now: {slo.monitors['queue_wait'].firing})")
    if args.sample:
        smp.export_jsonl(args.sample)
        print(f"[serve_continuous] samples -> {args.sample} "
              f"({smp.sample_count} samples, "
              f"{len(smp.samples)} retained)")
    if args.metrics:
        print(json.dumps(REGISTRY.snapshot(), indent=1, sort_keys=True))
    print("[serve_continuous] OK")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.configure()
    main()
