"""Continuous-batching LM scheduler on the serve engine's slot pool.

Decode is serving's request-scale 1-D dependency-bound recurrence: each
step consumes the previous step's cache. A static batch pads every
request to the slowest member; this scheduler instead admits, interleaves
and retires requests *per decode step* (the paper's fine-grain scheduling
argument applied to traffic):

  admit    — FCFS queue; a request claims a free cache slot the moment
             one exists (SlotManager.alloc zeroes the slot rows).
  prefill  — prompts are consumed as full ``prefill_chunk`` chunks
             through the batched chunk step (exact: chunks are never
             padded), the < chunk remainder rides the decode ramp as
             teacher-forced single tokens.
  decode   — ONE fused step over the whole pool each tick: per-slot
             position vector, per-slot temperature, masked sampling;
             free slots compute junk that is never read.
  retire   — EOS / max-tokens eviction frees the slot immediately; the
             next queued request is admitted on the same tick.

Under greedy sampling the emitted streams are token-identical to
per-request ``engine.generate`` (same chunk policy, same kernels) for
dense/SSM architectures. MoE capacity is shared across the pool batch,
so MoE token streams can legitimately diverge from B=1 at tight capacity
(documented per-group semantics, models/moe.py).

``speculate=k`` replaces the one-token decode tick with a speculative
verify tick (attention-only models): each greedy slot drafts k tokens
(teacher-forced prompt tokens through the ramp, prompt-lookup self-draft
past it), ONE fused chunk call verifies all k+1 positions, the longest
prefix of drafts agreeing with the model's own greedy predictions is
accepted, and the cache rows of rejected positions are rolled back
in-program — so decode's serial dependency chain advances up to k+1
positions per tick while greedy streams stay bit-identical to the
oracle. ``score(prompts)`` rides the same per-chunk-logits seam: prompt
tokens are teacher-forced through chunk/verify steps and every
position's logprob is collected (``Completion.logprobs``), no tokens
generated. Per-slot ``SamplingPolicy`` (temperature/top-k/top-p)
threads through the fused steps; sampled rows never speculate (they
accept nothing and sample exactly one policy-correct token per tick).

A memoizing request cache (prompt+params -> tokens) fronts the pool for
zipfian traffic — deterministic (greedy) requests only; hit/miss
counters feed the fig_serve benchmark.

With ``allocator='paged'`` the slot pool stores attention KV at block
granularity (serve.paging): admission gates on free *blocks* in every
page-table group — the global-KV group plus (``paged_window_attn``, the
default) one ring-mode group per distinct sliding-window length — live
slots map blocks on demand as their write position grows (ring groups
stop growing once the full ring is resident), retire frees them, and a
growth failure preempts the youngest slot back to the front of the
queue. At the equal-memory defaults (num_blocks=num_window_blocks=None)
scheduling is identical to contiguous; smaller pools admit more
concurrent mixed-length requests per byte at the cost of preemptions.

What preemption discards is the ``preempt`` policy:

  recompute — the victim restarts from scratch (greedy streams unchanged
              by determinism, but every decode step it had paid for is
              redone: counters['recomputed_decode_steps']).
  swap      — the victim's mapped blocks are copied to a host SwapStore
              and its freed; on re-admission fresh blocks are mapped and
              the bytes uploaded, so it RESUMES at its saved position —
              zero recomputed decode steps, bit-identical streams.

``admission='reserved'`` books blocks_for(prompt + max_new) at admit
instead of blocks_for(prompt) — growth can then never fail, so admitted
(QoS) traffic is never preempted, at the cost of admitted concurrency.

Observability (repro.obs): the scheduler registers itself as the
``serve`` provider of the metrics registry (all ``stats()`` keys,
pre-declared so they never appear lazily), stamps every request's
per-phase timeline (queue-wait, prefill, first token, swapped-out time,
recompute waste — surfaced as ``Completion.queue_wait`` / ``ttft`` /
``decode_s`` / ``itl``), and, when a Tracer is enabled, records
``admit`` / ``prefill`` / ``decode`` / ``preempt`` / ``swap-out`` /
``swap-in`` / ``retire`` events per slot track plus ``decode-tick`` /
``prefill-chunk`` spans on the scheduler track — a serve run exports
straight to Perfetto (obs.trace.Tracer.export_chrome).
"""

from __future__ import annotations

import collections
import dataclasses
import math
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.obs import metrics as obs_metrics
from repro.obs import sampler as obs_sampler
from repro.obs import trace as obs_trace
from repro.runtime import bucketing
from repro.serve import engine
from repro.serve.slots import MIN_CHUNK_ROWS, SlotManager, _attn_view_len


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    num_slots: int = 8          # pool width B (the fused decode batch)
    max_len: int = 256          # cache slots per request (prompt + gen)
    prefill_chunk: int = 32     # C: full-chunk prefill quantum
    max_new_tokens: int = 32    # default generation budget
    temperature: float = 0.0    # default sampling temperature (0 = greedy)
    top_k: int = 0              # default top-k filter (0 = disabled)
    top_p: float = 1.0          # default nucleus mass (1.0 = disabled)
    # k > 0: speculative decoding — draft k tokens per greedy slot per
    # tick, verify them in ONE fused chunk call, accept the agreeing
    # prefix and roll back the rest. Needs an attention-only pattern
    # (SSM chunk scans are irreversible) and k + 1 <= the smallest
    # attention view length (the rollback scatter needs distinct ring
    # rows). Greedy streams stay bit-identical to speculate=0.
    speculate: int = 0
    eos_token: Optional[int] = None
    cache_requests: bool = True
    request_cache_size: int = 1024
    seed: int = 0
    # 'continuous': admit whenever a slot is free (per-step interleaving).
    # 'static': admit a full batch only when the pool is EMPTY — the
    # pad-to-slowest baseline fig_serve compares against.
    admit: str = "continuous"
    # 'contiguous': every slot reserves max_len cache rows.
    # 'paged': attention KV lives in block pools (serve.paging) —
    # admission gates on free BLOCKS, slots grow block-by-block as they
    # decode, and a growth failure preempts the youngest slot.
    allocator: str = "contiguous"
    block_size: int = 16        # paged: cache positions per block
    # paged: physical blocks in the global-KV pool. None = equal memory
    # with the contiguous layout (num_slots * ceil(max_len / block_size))
    # — with that default no request can ever fail to grow, so scheduling
    # is identical to contiguous; smaller pools trade preemptions for
    # memory.
    num_blocks: Optional[int] = None
    # paged: also page sliding-window rings through ring-mode page-table
    # groups (one per distinct window length) instead of reserving a
    # dense window-row slab per slot. Blocks map lazily while a request
    # ramps up to `window` written positions; Pareto-short requests never
    # pay for the full ring. Off = the PR-3/4 dense-ring layout.
    paged_window_attn: bool = True
    # paged: physical blocks per window-ring pool. None = equal memory
    # with the dense rings (num_slots * ceil(min(window, max_len) /
    # block_size)).
    num_window_blocks: Optional[int] = None
    # preempt='swap': byte budget for the host SwapStore. None =
    # unbounded; when an eviction's bytes would exceed it, that victim
    # falls back to recompute-preemption (stats()['swap_rejected']).
    swap_bytes_budget: Optional[int] = None
    # paged: what preempt-on-OOB discards. 'recompute' restarts the
    # victim from scratch; 'swap' parks its block bytes in a host
    # SwapStore and resumes it at the saved position on re-admission.
    preempt: str = "recompute"
    # paged: 'optimistic' books blocks for the prompt only (growth may
    # hit OOB -> preempt); 'reserved' books blocks_for(prompt + max_new)
    # at admission, so admitted traffic can never be preempted (QoS).
    admission: str = "optimistic"
    # paged: share block-aligned prompt prefixes across requests through
    # a refcounted PrefixIndex (serve.paging) — an admitted prompt whose
    # leading chunks are indexed maps those blocks read-shared and starts
    # prefill past them; copy-on-write keeps sharers isolated. Greedy
    # streams stay bit-identical to unshared (the shared region is
    # chunk-aligned, so the remaining prefill chunks at the same
    # offsets an unshared run would).
    prefix_sharing: bool = False
    # prefix_sharing: LRU entry bound on the prefix index (each entry
    # holds one block per page-table group alive).
    prefix_index_capacity: int = 512
    # Shard the slot pool over a 1-D device mesh: num_slots splits evenly
    # into mesh_shards shards, each owning its OWN block pools / page
    # tables / swap store / prefix index (num_blocks etc. are then PER
    # SHARD — equal per-device memory), and every tick runs ONE fused
    # program spanning all shards (engine.jit_sharded_*_step; pass a
    # Mesh via Scheduler(mesh=...) to shard_map it over devices).
    # Requires allocator='paged'. None = the unsharded pool;
    # mesh_shards=1 runs the sharded control path over the SAME compiled
    # programs, bit-identical to None.
    mesh_shards: Optional[int] = None
    # sharded: which shard an admitted request lands on.
    # 'least_blocks' (default) picks the shard with the most free
    # blocks; 'round_robin' cycles. Scheduler.placement_fn overrides
    # with a callable (sched, slot_state) -> shard.
    placement: str = "least_blocks"
    # sharded: work-stealing rebalance — a queue head blocked on a full
    # shard migrates to an idle shard that can admit it now instead of
    # head-of-line blocking (swapped-out heads move their host SwapEntry
    # between shard stores, keeping all prefill progress).
    steal: bool = True


@dataclasses.dataclass
class _Slot:
    """Host-side per-slot request state (the validity mask's payload)."""
    rid: int
    prompt: np.ndarray          # int32 (L,)
    max_new_tokens: int
    policy: engine.SamplingPolicy
    mode: str = "generate"      # 'generate' | 'score' (prompt logprobs)
    ctx: int = 0                # tokens consumed into the slot's cache
    chunk_tokens: int = 0       # of which via chunk steps (not decode)
    out: List[int] = dataclasses.field(default_factory=list)
    logprobs: List[float] = dataclasses.field(default_factory=list)
    accepted: int = 0           # speculative drafts accepted (this request)
    drafted: int = 0            # speculative drafts proposed (this request)
    admit_seq: int = -1         # admission order: preemption evicts max
    shard: int = 0              # home shard (0 on unsharded pools)

    @property
    def temperature(self) -> float:
        return self.policy.temperature


@dataclasses.dataclass
class _Timeline:
    """Per-request phase stamps (perf_counter), kept while the request
    is in flight and folded into its Completion at finish."""
    submit_t: float
    admit_t: Optional[float] = None     # first slot claim (None = cached)
    first_token_t: Optional[float] = None
    swap_out_t: Optional[float] = None  # open swap interval, if any
    swapped_s: float = 0.0              # total time parked in the SwapStore
    recomputed_steps: int = 0           # decode ticks redone after preempt
    preemptions: int = 0


@dataclasses.dataclass
class Completion:
    rid: int
    tokens: np.ndarray          # int32 (g,)
    reason: str                 # 'eos' | 'length' | 'score' | 'cached'
    prompt_len: int
    submit_t: float             # time.perf_counter() stamp at submit
    finish_t: float             # time.perf_counter() stamp at finish
    # per-phase stamps (defaults match cache-served completions, which
    # never touch the pool)
    admit_t: Optional[float] = None     # first slot claim
    first_token_t: Optional[float] = None
    swapped_s: float = 0.0              # time parked in the SwapStore
    recomputed_steps: int = 0           # decode ticks redone after preempt
    preemptions: int = 0
    # score() requests: log p(prompt[i] | prompt[:i]) for i = 1..L-1,
    # fp32 (L-1,); None for generate requests
    logprobs: Optional[np.ndarray] = None
    # speculative-decoding effort for this request (0 when speculate=0
    # or served from cache): drafts accepted / proposed
    accepted: int = 0
    drafted: int = 0

    @property
    def latency(self) -> float:
        # perf_counter deltas are monotonic: a wall-clock (NTP) step can
        # never make a latency negative and skew fig_serve's p50/p95
        return self.finish_t - self.submit_t

    @property
    def queue_wait(self) -> float:
        """Submit -> first admission. 0 for cache-served requests."""
        return self.admit_t - self.submit_t if self.admit_t is not None \
            else 0.0

    @property
    def ttft(self) -> float:
        """Submit -> first generated token (== latency when the request
        was served from cache or produced its one token at finish)."""
        return self.first_token_t - self.submit_t \
            if self.first_token_t is not None else self.latency

    @property
    def prefill_s(self) -> float:
        """Admission -> first token: prompt consumption time."""
        if self.admit_t is None or self.first_token_t is None:
            return 0.0
        return self.first_token_t - self.admit_t

    @property
    def decode_s(self) -> float:
        """First token -> finish: pure generation time."""
        return self.finish_t - self.first_token_t \
            if self.first_token_t is not None else 0.0

    @property
    def itl(self) -> float:
        """Mean inter-token latency over the decode phase."""
        return self.decode_s / max(len(self.tokens) - 1, 1)


class RequestCache:
    """LRU memo: (prompt, params) -> completed tokens (greedy only).

    Zipfian traffic repeats a few hot prompts; serving them from the memo
    costs zero decode steps (ROADMAP 'runtime caching' item). Sampled
    (temperature > 0) requests bypass the cache — they are not
    deterministic functions of the key. The request *mode* (score vs
    generate) and the sampling-policy fingerprint are part of the key: a
    ``score()`` and a ``generate()`` of the same prompt return different
    payloads and must never alias in the memo.
    """

    def __init__(self, maxsize: int = 1024):
        self.maxsize = maxsize
        self._d: "collections.OrderedDict[Tuple, Tuple[np.ndarray, str, Optional[np.ndarray]]]" \
            = collections.OrderedDict()
        self.hits = 0
        self.misses = 0

    @staticmethod
    def key(prompt: np.ndarray, max_new_tokens: int,
            eos_token: Optional[int], mode: str = "generate",
            policy: Tuple = ()) -> Tuple:
        # dtype + shape are part of the key: raw bytes alone collide for
        # e.g. int64([1]) vs int32([1, 0]) (same little-endian bytes) or
        # a (4,) vs (2, 2) view of the same buffer. mode + policy
        # fingerprint (SamplingPolicy.fingerprint()) distinguish
        # score/generate and sampling configurations of one prompt.
        p = np.ascontiguousarray(prompt)
        return (p.tobytes(), p.dtype.str, p.shape,
                max_new_tokens, eos_token, mode, tuple(policy))

    def get(self, key: Tuple) \
            -> Optional[Tuple[np.ndarray, str, Optional[np.ndarray]]]:
        got = self._d.get(key)
        if got is None:
            self.misses += 1
            return None
        self._d.move_to_end(key)
        self.hits += 1
        return got

    def put(self, key: Tuple, tokens: np.ndarray, reason: str,
            logprobs: Optional[np.ndarray] = None):
        # defensive copy, frozen: the caller (and the original
        # requester's Completion) may hold the array we were handed —
        # memoizing it by reference would let `completion.tokens[0] = x`
        # corrupt every future hit. get() consumers copy on the way out.
        tokens = np.asarray(tokens, np.int32).copy()
        tokens.setflags(write=False)
        if logprobs is not None:
            logprobs = np.asarray(logprobs, np.float32).copy()
            logprobs.setflags(write=False)
        self._d[key] = (tokens, reason, logprobs)
        self._d.move_to_end(key)
        while len(self._d) > self.maxsize:
            self._d.popitem(last=False)

    @property
    def hit_rate(self) -> float:
        n = self.hits + self.misses
        return self.hits / n if n else 0.0


#: scheduler-owned counters, pre-declared at zero so stats() keys are
#: stable from construction (obs.schema.SCHEDULER_STATS pins them)
_COUNTER_KEYS = (
    "submitted", "admitted", "completed", "steps", "decode_steps",
    "chunk_steps", "generated_tokens", "prefill_tokens",
    "live_decode_slots", "preempted", "swapped_in", "swapped_out",
    "recomputed_decode_steps", "prefix_shared_tokens",
    # sharded pools: queue heads migrated off a full shard (0 otherwise)
    "steals",
    # speculative decoding (all 0 when speculate=0; 'real' drafts only —
    # teacher-forced ramp positions are excluded from the denominator)
    "spec.drafted_tokens", "spec.accepted_tokens", "spec.rejected_tokens",
    "spec.rollbacks",
)


def _log_softmax_np(lg: np.ndarray) -> np.ndarray:
    """Row-wise log-softmax over the last axis, fp32 (host-side prompt
    scoring from surfaced chunk/decode logits)."""
    lg = np.asarray(lg, np.float32)
    m = lg.max(axis=-1, keepdims=True)
    e = lg - m
    return (e - np.log(np.exp(e).sum(axis=-1, keepdims=True))).astype(
        np.float32)


class _ShardObs:
    """Registry ``serve.shard`` provider (sharded pools only): per-shard
    occupancy (``shard<i>.live_slots`` / ``free_slots`` / block + swap
    levels from the pool) plus the scheduler's placement/steal view —
    ``shard<i>.placed`` / ``steals`` / ``queued`` and the pool-wide
    ``steals`` total. The scheduler holds the strong reference (the
    registry keeps providers weakly)."""

    def __init__(self, sched: "Scheduler"):
        self._sched = sched

    def metrics(self) -> dict:
        sched = self._sched
        out = dict(sched.slots.shard_metrics())
        for s in range(sched.slots.num_shards):
            out[f"shard{s}.placed"] = sched._shard_placed[s]
            out[f"shard{s}.steals"] = sched._shard_steals[s]
            out[f"shard{s}.queued"] = len(sched._queues[s])
        out["num_shards"] = sched.slots.num_shards
        out["steals"] = int(sched.counters["steals"])
        return out


class Scheduler:
    """submit(prompts) / step() / drain() continuous-batching engine."""

    def __init__(self, cfg: ModelConfig, params,
                 sched: SchedulerConfig = SchedulerConfig(),
                 tracer: Optional[obs_trace.Tracer] = None,
                 draft_fn=None, mesh=None):
        self.cfg = cfg
        self.params = params
        self.sched = sched
        # pluggable draft source for speculate=k: draft_fn(seq, need) ->
        # >= need proposed next tokens given the committed sequence
        # (prompt + generated so far). None = built-in prompt-lookup
        # self-draft. A draft model slots in here; draft quality only
        # affects speed, never correctness (verify rejects disagreement).
        self._draft_fn = draft_fn
        for field, allowed in (("allocator", ("contiguous", "paged")),
                               ("preempt", ("recompute", "swap")),
                               ("admission", ("optimistic", "reserved"))):
            if getattr(sched, field) not in allowed:
                raise ValueError(f"SchedulerConfig.{field}="
                                 f"{getattr(sched, field)!r} not in {allowed}")
        if sched.prefix_sharing and sched.allocator != "paged":
            raise ValueError("prefix_sharing requires allocator='paged' "
                             "(blocks are the sharing granule)")
        if sched.placement not in ("least_blocks", "round_robin"):
            raise ValueError(f"SchedulerConfig.placement="
                             f"{sched.placement!r} not in "
                             "('least_blocks', 'round_robin')")
        if sched.mesh_shards is not None and sched.allocator != "paged":
            raise ValueError("mesh_shards requires allocator='paged' "
                             "(shards own per-shard block pools)")
        if mesh is not None and sched.mesh_shards is None:
            raise ValueError("Scheduler(mesh=...) needs "
                             "SchedulerConfig.mesh_shards set")
        if sched.speculate < 0:
            raise ValueError(f"speculate must be >= 0: {sched.speculate}")
        if sched.speculate:
            bad = [(s.mixer, s.mlp) for s in cfg.pattern
                   if s.mixer != "attn" or s.mlp == "rwkv_ffn"]
            if bad:
                raise ValueError(
                    "speculate requires an attention-only pattern with "
                    f"stateless MLPs (got {bad}): SSM/rwkv_ffn chunk "
                    "scans cannot roll back rejected drafts")
            min_view = min(_attn_view_len(s, sched.max_len)
                           for s in cfg.pattern)
            if sched.speculate + 1 > min_view:
                raise ValueError(
                    f"speculate={sched.speculate}: verify span "
                    f"{sched.speculate + 1} exceeds the smallest "
                    f"attention view length {min_view} (the rollback "
                    "scatter needs distinct ring rows)")
        # validates temperature/top_k/top_p ranges (ValueError on bad)
        engine.SamplingPolicy(sched.temperature, sched.top_k, sched.top_p)
        # shared prefixes must end on a chunk boundary AND a block
        # boundary: the sharer skips whole chunk steps and maps whole
        # blocks, so only lcm-aligned prefixes keep the remaining
        # prefill chunking (and so the greedy stream) bit-identical to
        # an unshared run.
        prefix_align = math.lcm(sched.prefill_chunk, sched.block_size)
        self.slots = SlotManager(cfg, sched.num_slots, sched.max_len,
                                 paged=sched.allocator == "paged",
                                 block_size=sched.block_size,
                                 num_blocks=sched.num_blocks,
                                 paged_window=sched.paged_window_attn,
                                 num_window_blocks=sched.num_window_blocks,
                                 swap_bytes_budget=sched.swap_bytes_budget,
                                 prefix_sharing=sched.prefix_sharing,
                                 prefix_align=prefix_align,
                                 prefix_capacity=sched.prefix_index_capacity,
                                 mesh_shards=sched.mesh_shards,
                                 mesh=mesh)
        # one FCFS queue per shard (exactly one on unsharded pools, so
        # every single-queue invariant — arrival order, head-of-line
        # admission — is the pre-sharding behavior verbatim)
        self._queues: List["collections.deque[_Slot]"] = [
            collections.deque() for _ in range(self.slots.num_shards)]
        self._rr_next = 0               # round_robin placement cursor
        # pluggable placement: fn(scheduler, _Slot) -> shard index;
        # overrides SchedulerConfig.placement when set
        self.placement_fn = None
        self._shard_placed = [0] * self.slots.num_shards
        self._shard_steals = [0] * self.slots.num_shards
        self._by_slot: Dict[int, _Slot] = {}
        self._inflight: Dict[Tuple, List[int]] = {}
        self._fresh: List[int] = []     # finished, not yet handed out
        self._tl: Dict[int, _Timeline] = {}
        self.results: Dict[int, Completion] = {}
        self.request_cache = RequestCache(sched.request_cache_size)
        self._key = jax.random.PRNGKey(sched.seed)
        self._next_rid = 0
        self._next_seq = 0          # admission sequence (preempt youngest)
        self.counters = collections.Counter(dict.fromkeys(_COUNTER_KEYS, 0))
        # per-request latency histograms (lifetime count/sum, windowed
        # p50/p95) — the sampled series SLO rules like ``ttft_p95 < X``
        # monitor; fresh per scheduler so benchmarks don't cross-pollute
        self._lat = {name: obs_metrics.Histogram()
                     for name in ("queue_wait_ms", "ttft_ms", "itl_ms",
                                  "spec.accept_len")}
        # closed-loop actuator knobs (obs.control.BackpressureController):
        # admit_cap caps admissions per tick while an overload alert
        # fires (None = uncapped FCFS), preempt_override flips the
        # preemption policy without touching the frozen config. Both only
        # ever change timing/admission — greedy token streams are
        # bit-identical with or without them (tests/test_obs_loop.py).
        self.admit_cap: Optional[int] = None
        self.preempt_override: Optional[str] = None
        self._tracer = tracer
        # slot -> (phase name, t0, rid): the open per-slot phase span,
        # closed at first-token / preempt / retire (tracer enabled only)
        self._open_phase: Dict[int, Tuple[str, float, int]] = {}
        obs_metrics.REGISTRY.register_provider("serve", self)
        self._shard_obs = None
        if self.slots.sharded:
            self._shard_obs = _ShardObs(self)
            obs_metrics.REGISTRY.register_provider("serve.shard",
                                                   self._shard_obs)

    @property
    def tracer(self) -> obs_trace.Tracer:
        return self._tracer if self._tracer is not None \
            else obs_trace.get_tracer()

    @property
    def preempt_policy(self) -> str:
        """The policy preempt-on-OOB actually uses this tick: the
        controller's override when backpressure is engaged, else the
        configured one."""
        return self.preempt_override or self.sched.preempt

    def _phase_begin(self, slot: int, name: str, rid: int):
        if self.tracer.enabled:
            self._open_phase[slot] = (name, time.perf_counter(), rid)

    def _phase_end(self, slot: int):
        open_ = self._open_phase.pop(slot, None)
        if open_ is not None:
            name, t0, rid = open_
            self.tracer.complete(name, f"slot{slot}", t0,
                                 time.perf_counter(), rid=rid)

    # -- submission ----------------------------------------------------------

    def submit(self, prompts: Sequence, max_new_tokens: Optional[int] = None,
               temperature: Optional[float] = None,
               top_k: Optional[int] = None,
               top_p: Optional[float] = None) -> List[int]:
        """Enqueue prompts (FCFS); returns request ids. Cached greedy
        repeats complete immediately without touching the pool.
        temperature/top_k/top_p default to the SchedulerConfig values and
        form the batch's SamplingPolicy (validated here, ValueError)."""
        mnt = self.sched.max_new_tokens if max_new_tokens is None \
            else max_new_tokens
        policy = engine.SamplingPolicy(
            self.sched.temperature if temperature is None else temperature,
            self.sched.top_k if top_k is None else top_k,
            self.sched.top_p if top_p is None else top_p)
        rids = []
        # user-input feasibility checks raise ValueError (not assert:
        # they must hold under `python -O` too — the pool's progress
        # guarantee depends on them). The WHOLE batch is validated
        # before anything is enqueued: a mid-batch failure must not
        # leave earlier prompts admitted as orphans whose rids the
        # caller never received (they would complete into `results`
        # with nobody to pop them).
        if mnt < 1:
            raise ValueError("max_new_tokens must be >= 1")
        batch = []
        for p in prompts:
            p = np.asarray(p, np.int32).reshape(-1)
            if not 1 <= len(p) <= self.sched.max_len - mnt:
                raise ValueError(
                    f"prompt length {len(p)} + max_new {mnt} exceeds "
                    f"max_len {self.sched.max_len}")
            if self.slots.paged:
                # progress guarantee for preempt-on-OOB: with every other
                # slot evicted the oldest request must fit the whole pool
                # — in EVERY page-table group (global KV and each
                # window-ring group; ring demand clamps at the full ring)
                why = self.slots.fits_pool(len(p) + mnt)
                if why is not None:
                    raise ValueError(why)
            batch.append(p)
        for p in batch:
            rid = self._next_rid
            self._next_rid += 1
            self._tl[rid] = _Timeline(submit_t=time.perf_counter())
            self.counters["submitted"] += 1
            self.tracer.instant("submit", "scheduler", rid=rid)
            if self.sched.cache_requests and policy.greedy:
                key = RequestCache.key(p, mnt, self.sched.eos_token,
                                       policy=policy.fingerprint())
                if key in self._inflight:
                    # coalesce: an identical request is already queued or
                    # decoding — ride its completion (memo-layer hit: a
                    # zipfian burst of one hot prompt decodes ONCE)
                    self._inflight[key].append(rid)
                    self.request_cache.hits += 1
                    rids.append(rid)
                    continue
                got = self.request_cache.get(key)
                if got is not None:
                    toks, _, _ = got
                    self._finish(rid, len(p), toks.copy(), "cached")
                    rids.append(rid)
                    continue
                self._inflight[key] = []
            self._enqueue(_Slot(rid=rid, prompt=p, max_new_tokens=mnt,
                                policy=policy))
            rids.append(rid)
        return rids

    def score(self, prompts: Sequence) -> List[int]:
        """Enqueue prompts for per-token logprob scoring; returns request
        ids. Each completion carries ``logprobs`` — fp32 (L-1,) with
        ``logprobs[i-1] = log p(prompt[i] | prompt[:i])`` — and no
        generated tokens (reason 'score'). Scoring rides the same chunk
        path as prefill (the per-chunk-logits seam), teacher-forcing the
        prompt and reading every position's logits; deterministic, so
        results memoize in the RequestCache under a score-mode key that
        can never alias a generate() of the same prompt."""
        batch = []
        for p in prompts:
            p = np.asarray(p, np.int32).reshape(-1)
            if not 2 <= len(p) <= self.sched.max_len:
                raise ValueError(
                    f"score prompt length {len(p)} must be in "
                    f"[2, max_len={self.sched.max_len}]")
            if self.slots.paged:
                why = self.slots.fits_pool(len(p))
                if why is not None:
                    raise ValueError(why)
            batch.append(p)
        policy = engine.SamplingPolicy()        # scoring is greedy-only
        rids = []
        for p in batch:
            rid = self._next_rid
            self._next_rid += 1
            self._tl[rid] = _Timeline(submit_t=time.perf_counter())
            self.counters["submitted"] += 1
            self.tracer.instant("submit", "scheduler", rid=rid, mode="score")
            if self.sched.cache_requests:
                key = RequestCache.key(p, 0, self.sched.eos_token,
                                       mode="score",
                                       policy=policy.fingerprint())
                if key in self._inflight:
                    self._inflight[key].append(rid)
                    self.request_cache.hits += 1
                    rids.append(rid)
                    continue
                got = self.request_cache.get(key)
                if got is not None:
                    toks, _, lps = got
                    self._finish(rid, len(p), toks.copy(), "cached",
                                 logprobs=None if lps is None
                                 else lps.copy())
                    rids.append(rid)
                    continue
                self._inflight[key] = []
            self._enqueue(_Slot(rid=rid, prompt=p, max_new_tokens=0,
                                policy=policy, mode="score"))
            rids.append(rid)
        return rids

    def _place(self, st: _Slot) -> int:
        """Pick the home shard for a new request (0 on unsharded pools).
        'least_blocks' takes the shard with the most free blocks, ties
        broken by shorter queue then lower index; 'round_robin' cycles.
        ``placement_fn`` (callable (scheduler, _Slot) -> shard) overrides
        both."""
        n = self.slots.num_shards
        if n == 1:
            return 0
        if self.placement_fn is not None:
            shard = int(self.placement_fn(self, st))
            if not 0 <= shard < n:
                raise ValueError(f"placement_fn returned shard {shard} "
                                 f"(pool has {n})")
            return shard
        if self.sched.placement == "round_robin":
            shard = self._rr_next
            self._rr_next = (self._rr_next + 1) % n
            return shard
        return min(range(n),
                   key=lambda s: (-self.slots.shard_free_blocks(s),
                                  len(self._queues[s]), s))

    def _enqueue(self, st: _Slot):
        st.shard = self._place(st)
        self._shard_placed[st.shard] += 1
        self._queues[st.shard].append(st)

    # -- the scheduling loop -------------------------------------------------

    def step(self) -> List[Completion]:
        """One tick: admit, chunk-prefill, one fused decode, retire.
        Returns every completion not yet handed out — including requests
        finished at submit time by the request cache."""
        with self.tracer.span("admit", "scheduler"):
            self._admit()
        self._prefill_chunks()
        self._decode_once()
        self.counters["steps"] += 1
        out = [self.results[rid] for rid in self._fresh]
        self._fresh.clear()
        # tick the installed sampler (if any) AFTER the tick's work, so
        # a sample sees the levels this step produced; one global load +
        # None check when live sampling is off
        obs_sampler.tick("serve.step")
        return out

    def drain(self) -> List[Completion]:
        """Run until queue and pool are empty; returns the completions
        NOT yet handed out (by an earlier step() or drain()), rid order —
        a completion is delivered exactly once across step/drain calls.

        ``results`` still archives every completion until the caller
        removes entries — a long-lived scheduler (KernelService front
        door) should ``results.pop(rid)`` once a completion is consumed,
        or ``results`` grows without bound."""
        fresh: List[int] = []
        while any(self._queues) or self._by_slot:
            fresh.extend(c.rid for c in self.step())
        fresh.extend(self._fresh)   # cache hits finished at submit time
        self._fresh.clear()
        return [self.results[rid] for rid in sorted(fresh)]

    @property
    def pending(self) -> int:
        return sum(len(q) for q in self._queues)

    @property
    def live(self) -> int:
        return len(self._by_slot)

    def metrics(self) -> dict:
        """Scheduler-owned metrics (registry 'serve' provider): every
        counter (pre-declared), queue/pool levels, cache rates, the
        latency histograms (flattened ``<name>.<field>``) and the live
        overload signal + actuator knobs the SLO/control loop reads.
        ``stats()`` = this + the slot pool's keys."""
        decode_steps = self.counters["decode_steps"]
        head_wait = 0.0
        heads = [q[0] for q in self._queues if q]
        if heads:
            # oldest queue head across shards (one queue when unsharded)
            head_wait = time.perf_counter() \
                - min(self._tl[st.rid].submit_t for st in heads)
        out = {**{k: int(v) for k, v in self.counters.items()},
               "pending": self.pending,
               "live": len(self._by_slot),
               "coalesced_waiting": sum(
                   len(v) for v in self._inflight.values()),
               "cache_hits": self.request_cache.hits,
               "cache_misses": self.request_cache.misses,
               "cache_hit_rate": round(self.request_cache.hit_rate, 4),
               "mean_occupancy": round(
                   self.counters["live_decode_slots"] / decode_steps, 4)
               if decode_steps else 0.0,
               "queue_head_wait_s": round(head_wait, 6),
               "admit_cap": -1 if self.admit_cap is None
               else int(self.admit_cap),
               "preempt_policy": self.preempt_policy}
        for name, h in self._lat.items():
            for k, v in h.summary().items():
                out[f"{name}.{k}"] = v
        return out

    def stats(self) -> dict:
        return {**self.metrics(), **self.slots.stats()}

    # -- internals -----------------------------------------------------------

    def _admit(self):
        if self.sched.admit == "static" and self._by_slot:
            return      # static batching: wait for the whole batch
        self._steal_rebalance()
        # Per-shard FCFS with head-of-line blocking: if a queue head's
        # blocks aren't free (paged), nothing behind it on that shard
        # jumps the line — preserves arrival order and starves no
        # request. Unsharded pools run exactly one queue, so this IS the
        # pre-sharding single-queue loop.
        admitted_this_tick = 0
        for shard, q in enumerate(self._queues):
            while q:
                # backpressure: while the overload alert fires the
                # controller caps admissions per tick (order is still
                # FCFS — only timing changes, so greedy streams are
                # unchanged)
                if self.admit_cap is not None \
                        and admitted_this_tick >= self.admit_cap:
                    return
                if not self._admit_head(shard, q):
                    break           # head-of-line blocked: next shard
                admitted_this_tick += 1

    def _head_admissible(self, shard: int, st: _Slot) -> bool:
        """Could ``st`` admit right now? Swapped-out requests check the
        shard whose store holds their entry; fresh ones check ``shard``.
        Mirrors the checks ``_admit_head`` performs before claiming."""
        if self.slots.is_swapped(st.rid):
            return self.slots.can_admit_swapped(st.rid)
        need = len(st.prompt) + (
            st.max_new_tokens
            if self.sched.admission == "reserved" else 0)
        span = len(st.prompt) + st.max_new_tokens
        pr = st.prompt if st.mode == "generate" else None
        return self.slots.can_admit(
            need, prompt=pr, span=span,
            shard=shard if self.slots.sharded else None)

    def _steal_rebalance(self):
        """Work-stealing rebalance (sharded pools): a queue head that
        cannot admit on its home shard migrates to an IDLE shard (empty
        queue) that can admit it right now, instead of head-of-line
        blocking behind a full shard. The busiest-free destination wins.
        Swapped-out heads move their host SwapEntry between shard swap
        stores (budget- and block-checked up front; a refusal means no
        steal), so a stolen request never loses prefill progress."""
        n = self.slots.num_shards
        if not self.sched.steal or n < 2:
            return
        for s, q in enumerate(self._queues):
            if not q:
                continue
            st = q[0]
            if self._head_admissible(s, st):
                continue            # admits normally this tick
            swapped = self.slots.is_swapped(st.rid)
            cands = [d for d in range(n)
                     if d != s and not self._queues[d]
                     and (self.slots.can_steal_swapped(st.rid, d)
                          if swapped else self._head_admissible(d, st))]
            if not cands:
                continue
            d = max(cands, key=self.slots.shard_free_blocks)
            if swapped and not self.slots.migrate_swapped(st.rid, d):
                continue
            q.popleft()
            st.shard = d
            self._queues[d].append(st)
            self.counters["steals"] += 1
            self._shard_steals[d] += 1
            self.tracer.instant("steal", "scheduler", rid=st.rid,
                                src_shard=s, dst_shard=d)

    def _admit_head(self, shard: int, q) -> bool:
        """Try to admit ``q``'s head onto ``shard``; True = admitted (and
        popped), False = head-of-line blocked (pool or blocks full)."""
        st = q[0]
        sh = shard if self.slots.sharded else None
        swapped_in = False
        if self.slots.is_swapped(st.rid):
            # resume a swap-preempted request: remap + upload its
            # saved blocks; it continues at st.ctx with st.out intact
            got = self.slots.swap_in(st.rid)
            if got is None:
                return False
            slot, _ = got
            self.counters["swapped_in"] += 1
            swapped_in = True
        else:
            # reserved admission books the whole generation budget up
            # front: growth can never OOB, so QoS traffic is never
            # preempted (submit checked it fits the pool)
            need = len(st.prompt) + (
                st.max_new_tokens
                if self.sched.admission == "reserved" else 0)
            # prefix sharing needs the prompt (to match the index)
            # and the request's full span (ring groups only share
            # when the span fits the ring, so no wrap can ever
            # write through a shared block). Score rows never share:
            # a shared prefix skips the chunk steps whose logits ARE
            # the scored logprobs.
            span = len(st.prompt) + st.max_new_tokens
            pr = st.prompt if st.mode == "generate" else None
            if not self.slots.can_admit(need, prompt=pr, span=span,
                                        shard=sh):
                return False
            slot = self.slots.alloc(st.rid, prompt_len=need,
                                    prompt=pr, span=span, shard=sh)
            start = self.slots.prefill_start(slot)
            if start:
                # the leading `start` positions were admitted mapped
                # to index-held blocks: their KV already exists, so
                # prefill resumes past them (chunk-aligned, so the
                # remaining chunking is identical to an unshared run)
                st.ctx = start
                st.chunk_tokens = start
                self.counters["prefix_shared_tokens"] += start
        q.popleft()
        st.admit_seq = self._next_seq
        self._next_seq += 1
        self._by_slot[slot] = st
        self.counters["admitted"] += 1
        now = time.perf_counter()
        tl = self._tl[st.rid]
        if tl.admit_t is None:
            tl.admit_t = now        # first admission only (queue-wait)
            self._lat["queue_wait_ms"].observe(
                (now - tl.submit_t) * 1e3)
        if swapped_in:
            if tl.swap_out_t is not None:
                tl.swapped_s += now - tl.swap_out_t
                tl.swap_out_t = None
            self.tracer.instant("swap-in", f"slot{slot}", rid=st.rid)
        else:
            self.tracer.instant("admit", f"slot{slot}", rid=st.rid,
                                prompt_len=len(st.prompt))
        self._phase_begin(slot, "prefill" if st.ctx < len(st.prompt)
                          else "decode", st.rid)
        return True

    def _preempt(self, slot: int):
        """Evict a live slot to free its blocks (paged growth failure);
        the request re-queues at the FRONT. Under preempt='recompute' it
        restarts from scratch — every decode step it had consumed is
        redone (counted in 'recomputed_decode_steps'; greedy completions
        are unchanged by determinism, sampled ones may diverge like any
        restart). Under preempt='swap' its block bytes move to the host
        SwapStore and it later RESUMES at st.ctx — no wasted work,
        unless the SwapStore's byte budget rejects the entry, in which
        case this victim degrades to a recompute restart (the store
        counts the rejection; stats()['swap_rejected'])."""
        st = self._by_slot.pop(slot)
        self._phase_end(slot)
        tl = self._tl[st.rid]
        swapped = False
        if self.preempt_policy == "swap":
            # bytes moved AND budget rejections are tracked once, by the
            # backing's SwapStore (surfaced through stats() —
            # 'swap_rejected' has a single owner); counters only count
            # scheduler events
            swapped = self.slots.swap_out(slot) is not None
            if swapped:
                self.counters["swapped_out"] += 1
                tl.swap_out_t = time.perf_counter()
                self.tracer.instant("swap-out", f"slot{slot}", rid=st.rid)
        if not swapped:
            self.slots.release(slot)
            # decode ticks this victim consumed (ctx minus chunk-step
            # tokens) that the restart will pay for again
            wasted = st.ctx - st.chunk_tokens
            self.counters["recomputed_decode_steps"] += wasted
            tl.recomputed_steps += wasted
            tl.first_token_t = None     # the restart re-earns its TTFT
            self.tracer.instant("preempt", f"slot{slot}", rid=st.rid,
                                wasted_steps=wasted)
            st.ctx = 0
            st.chunk_tokens = 0
            st.out = []
            st.logprobs = []    # a score restart re-collects from scratch
        st.admit_seq = -1
        # re-queue at the FRONT of the home shard's queue (the shard the
        # slot lived on — a swapped entry's bytes are parked there)
        st.shard = self.slots.shard_of_slot(slot)
        self._queues[st.shard].appendleft(st)
        self.counters["preempted"] += 1
        tl.preemptions += 1

    def _ensure_or_preempt(self, slot: int, upto_pos: int,
                           write_from: Optional[int] = None) -> bool:
        """Grow ``slot``'s storage to cover ``upto_pos``; on block
        exhaustion evict the youngest live slot and retry. The oldest
        live request is only ever self-evicted (when nothing younger is
        left), and the submit-time feasibility assert guarantees it fits
        an empty pool — so the pool always makes forward progress.
        ``write_from`` bounds the copy-on-write scan (speculative ticks
        write a span, not one position). Returns False iff ``slot``
        itself was preempted. Victims come from the grower's own shard —
        block pools are shard-local, so evicting elsewhere frees
        nothing it can use (every slot is shard 0 on unsharded pools)."""
        shard = self.slots.shard_of_slot(slot)
        while not self.slots.ensure(slot, upto_pos, write_from=write_from):
            victim = max((s for s in self._by_slot
                          if self.slots.shard_of_slot(s) == shard),
                         key=lambda s: self._by_slot[s].admit_seq)
            self._preempt(victim)
            if victim == slot:
                return False
        return True

    def _prefill_chunks(self):
        """Consume every pending full chunk (first L-1 prompt tokens only;
        the final token always rides the decode step so decode is the one
        sampler). Bucketed pow2 gather keeps compiles O(log pool)."""
        ch = self.sched.prefill_chunk
        while True:
            need = [s for s, st in sorted(self._by_slot.items())
                    if len(st.prompt) - 1 - st.ctx >= ch]
            if not need:
                return
            if self.slots.paged:
                # prompts are fully mapped at admission (alloc_reset
                # covers positions [0, prompt_len)), so a chunk write can
                # never need a new block — block growth, and with it
                # preempt-on-OOB, happens only on the decode path. NOTE:
                # ensure() is side-effecting, so it must be CALLED
                # outside the assert (python -O strips assert statements
                # — the mapping itself must not depend on them).
                for s in need:
                    # write_from bounds the copy-on-write scan to the
                    # chunk's actual write span [ctx, ctx+ch-1] — which
                    # by construction starts at/after the slot's shared
                    # prefix, so admission-path writes never trigger CoW
                    ok = self.slots.ensure(s, self._by_slot[s].ctx + ch - 1,
                                           write_from=self._by_slot[s].ctx)
                    assert ok, "prefill chunk outgrew the admission mapping"
            m = len(need)
            if self.slots.sharded:
                # the sharded backing pads PER SHARD (pad-by-repeat of
                # each shard's first entry, common pow2 width) so every
                # shard sees the same chunk program; pass the live set
                # unpadded and take rows back in input order
                idx = list(need)
            else:
                bsz = bucketing.round_up_pow2(m, MIN_CHUNK_ROWS)
                idx = need + [need[0]] * (bsz - m)  # pad-by-repeat
            toks = np.stack([
                self._by_slot[s].prompt[self._by_slot[s].ctx:
                                        self._by_slot[s].ctx + ch]
                for s in idx])
            pos = np.asarray([self._by_slot[s].ctx for s in idx], np.int32)
            # pad rows duplicate row 0 bit-for-bit -> scatter deterministic
            with self.tracer.span("prefill-chunk", "scheduler",
                                  slots=m, chunk=ch):
                logits = self.slots.run_chunk(self.params, idx, toks, pos)
            score_rows = [j for j, s in enumerate(need)
                          if self._by_slot[s].mode == "score"]
            if score_rows:
                # chunk logits ARE the prompt scores: logits[j, i]
                # predicts position ctx+i+1, all of which are prompt
                # positions <= L-1 here (the chunk condition guarantees
                # ctx+ch <= L-1)
                lp = _log_softmax_np(
                    np.asarray(logits[np.asarray(score_rows)], np.float32))
                for row, j in enumerate(score_rows):
                    st = self._by_slot[need[j]]
                    fed = st.prompt[st.ctx + 1:st.ctx + ch + 1]
                    st.logprobs.extend(
                        float(lp[row, i, t]) for i, t in enumerate(fed))
            for s in need:
                self._by_slot[s].ctx += ch
                self._by_slot[s].chunk_tokens += ch
            self.counters["chunk_steps"] += 1
            self.counters["prefill_tokens"] += m * ch
            # a score row whose last needed position (L-2) was just
            # consumed is complete without ever decoding
            for s in need:
                st = self._by_slot.get(s)
                if st is not None and st.mode == "score" \
                        and st.ctx >= len(st.prompt) - 1:
                    self._retire(s, "score")

    def _max_commit(self, st: _Slot) -> int:
        """Last cache position a speculative tick may commit for ``st``:
        generate rows never feed past the position producing their final
        token (L + max_new - 2); score rows never feed past the position
        producing the last prompt logprob (L - 2)."""
        ln = len(st.prompt)
        return ln - 2 if st.mode == "score" else ln + st.max_new_tokens - 2

    def _first_token(self, slot: int, st: _Slot):
        """First-generated-token bookkeeping: TTFT stamp, phase flip,
        prefix publication (shared by the plain and speculative ticks)."""
        tl = self._tl[st.rid]
        if tl.first_token_t is None:
            tl.first_token_t = time.perf_counter()
            self._lat["ttft_ms"].observe(
                (tl.first_token_t - tl.submit_t) * 1e3)
        # the prefill phase ends at the first sampled token
        self._phase_end(slot)
        self._phase_begin(slot, "decode", st.rid)
        # publish the prompt's chunk-consumed prefix blocks to
        # the prefix index now that their KV is fully written
        # (no-op unless prefix_sharing; idempotent per prompt)
        self.slots.register_prefix(
            slot, st.prompt, len(st.prompt) + st.max_new_tokens,
            st.chunk_tokens)

    def _decode_once(self):
        """One fused decode over the FULL pool: per-slot tokens, positions
        and sampling policies; free slots run on masked junk (never
        read). With ``speculate=k`` the tick is a verify-accept chunk
        instead (``_decode_speculative``)."""
        if not self._by_slot:
            return
        if self.sched.speculate:
            self._decode_speculative(self.sched.speculate)
            return
        if self.slots.paged:
            # every live slot writes its cache at position ctx this tick:
            # map the covering blocks, preempting youngest-first on OOB
            for s in sorted(self._by_slot):
                if s in self._by_slot:
                    self._ensure_or_preempt(s, self._by_slot[s].ctx)
            if not self._by_slot:
                return
        b = self.slots.num_slots
        toks = np.zeros((b, 1), np.int32)
        pos = np.zeros((b,), np.int32)
        temps = np.zeros((b,), np.float32)
        top_ks = np.zeros((b,), np.int32)
        top_ps = np.ones((b,), np.float32)
        for s, st in self._by_slot.items():
            toks[s, 0] = (st.prompt[st.ctx] if st.ctx < len(st.prompt)
                          else st.out[-1])
            pos[s] = st.ctx
            temps[s] = st.policy.temperature
            top_ks[s] = st.policy.top_k
            top_ps[s] = st.policy.top_p
        self._key, ks = jax.random.split(self._key)
        with self.tracer.span("decode-tick", "scheduler",
                              live=len(self._by_slot)):
            nxt, logits = self.slots.run_decode(
                self.params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(temps), ks, jnp.asarray(top_ks),
                jnp.asarray(top_ps))
            nxt = np.asarray(nxt)
        self.counters["decode_steps"] += 1
        # admitted-concurrency numerator: mean live slots per decode tick
        # = live_decode_slots / decode_steps (fig_serve's occupancy gate)
        self.counters["live_decode_slots"] += len(self._by_slot)
        score_live = [s for s, st in self._by_slot.items()
                      if st.mode == "score"]
        lp = None
        if score_live:
            # the fed token at ctx predicts position ctx+1 — a prompt
            # position (score rows retire before ctx reaches L-1)
            lp = _log_softmax_np(np.asarray(logits[:, 0], np.float32))

        for s in sorted(self._by_slot):
            st = self._by_slot[s]
            if st.mode == "score":
                st.logprobs.append(float(lp[s, st.prompt[st.ctx + 1]]))
                st.ctx += 1
                if st.ctx >= len(st.prompt) - 1:
                    self._retire(s, "score")
                continue
            st.ctx += 1
            if st.ctx < len(st.prompt):
                continue                            # still teacher-forcing
            tok = int(nxt[s])
            st.out.append(tok)
            self.counters["generated_tokens"] += 1
            if len(st.out) == 1:
                self._first_token(s, st)
            eos = (self.sched.eos_token is not None
                   and tok == self.sched.eos_token)
            if eos or len(st.out) >= st.max_new_tokens:
                self._retire(s, "eos" if eos else "length")

    # -- speculative decoding --------------------------------------------

    @staticmethod
    def _lookup_draft(seq: np.ndarray, need: int) -> List[int]:
        """Prompt-lookup self-draft: find the most recent earlier
        occurrence of the sequence's trailing 2-gram and copy the tokens
        that followed it; repeat the last token when nothing matches.
        Draft quality only affects speed — never correctness (the verify
        step rejects disagreeing drafts)."""
        n = len(seq)
        drafts: List[int] = []
        if n >= 3:
            a, b = int(seq[-2]), int(seq[-1])
            for i in range(n - 3, -1, -1):
                if int(seq[i]) == a and int(seq[i + 1]) == b:
                    j = i + 2
                    while len(drafts) < need and j < n:
                        drafts.append(int(seq[j]))
                        j += 1
                    break
        last = int(seq[-1]) if n else 0
        while len(drafts) < need:
            drafts.append(last)
        return drafts

    def _draft_tokens(self, st: _Slot, k: int) -> List[int]:
        """k draft tokens for positions ctx+1..ctx+k: true prompt tokens
        through the teacher-forced ramp (they MUST be — the accepted span
        is written to the cache), prompt-lookup self-draft past it."""
        ln = len(st.prompt)
        out: List[int] = []
        p = st.ctx + 1
        while len(out) < k and p < ln:
            out.append(int(st.prompt[p]))
            p += 1
        if len(out) < k:
            seq = (st.prompt if not st.out
                   else np.concatenate([st.prompt,
                                        np.asarray(st.out, np.int32)]))
            need = k - len(out)
            if self._draft_fn is not None:
                got = [int(t) for t in self._draft_fn(seq, need)][:need]
                out.extend(got)
                need -= len(got)
                if need:                    # short draft: pad via lookup
                    out.extend(self._lookup_draft(seq, need))
            else:
                out.extend(self._lookup_draft(seq, need))
        return out

    def _decode_speculative(self, k: int):
        """One fused verify-accept tick over the FULL pool: feed k+1
        tokens per slot (true next token + k drafts) through the chunk
        path, accept each row's agreeing draft prefix, emit up to k+1
        tokens. Rejected cache writes were rolled back in-program, so
        host state only ever advances by exactly what was committed —
        greedy streams are bit-identical to speculate=0."""
        if self.slots.paged:
            for s in sorted(self._by_slot):
                if s in self._by_slot:
                    st = self._by_slot[s]
                    # the verify span writes [ctx, ctx+k]; only positions
                    # that may COMMIT need mapped blocks (rolled-back
                    # writes beyond the mapping land in the trash block,
                    # which is never attended)
                    upto = max(min(st.ctx + k, self._max_commit(st)),
                               st.ctx)
                    self._ensure_or_preempt(s, upto, write_from=st.ctx)
            if not self._by_slot:
                return
        b = self.slots.num_slots
        toks = np.zeros((b, k + 1), np.int32)
        pos = np.zeros((b,), np.int32)
        plen = np.ones((b,), np.int32)
        maxp = np.zeros((b,), np.int32)
        score_f = np.zeros((b,), bool)
        active = np.zeros((b,), bool)
        temps = np.zeros((b,), np.float32)
        top_ks = np.zeros((b,), np.int32)
        top_ps = np.ones((b,), np.float32)
        for s, st in self._by_slot.items():
            first = (st.prompt[st.ctx] if st.ctx < len(st.prompt)
                     else st.out[-1])
            toks[s] = [int(first)] + self._draft_tokens(st, k)
            pos[s] = st.ctx
            plen[s] = len(st.prompt)
            maxp[s] = self._max_commit(st)
            score_f[s] = st.mode == "score"
            active[s] = True
            temps[s] = st.policy.temperature
            top_ks[s] = st.policy.top_k
            top_ps[s] = st.policy.top_p
        self._key, ks = jax.random.split(self._key)
        with self.tracer.span("decode-tick", "scheduler",
                              live=len(self._by_slot), speculate=k):
            out_tok, acc_n, lp = self.slots.run_verify(
                self.params, jnp.asarray(toks), jnp.asarray(pos),
                jnp.asarray(plen), jnp.asarray(maxp), jnp.asarray(score_f),
                jnp.asarray(active), jnp.asarray(temps),
                jnp.asarray(top_ks), jnp.asarray(top_ps), ks)
            out_tok = np.asarray(out_tok)
            acc_n = np.asarray(acc_n)
            lp = np.asarray(lp, np.float32)
        self.counters["decode_steps"] += 1
        self.counters["live_decode_slots"] += len(self._by_slot)

        tick_accepts: List[int] = []
        for s in sorted(self._by_slot):
            st = self._by_slot[s]
            n = int(acc_n[s])
            adv = n + 1
            base = st.ctx
            ln = len(st.prompt)
            if st.mode == "score":
                # lp[i] scores the token fed at chunk slot i+1 (position
                # base+i+1) — a prompt token for every i <= n (the accept
                # rule clamps score rows to n <= k-1)
                st.logprobs.extend(float(lp[s, i]) for i in range(adv))
                st.ctx = base + adv
                if st.ctx >= ln - 1:
                    self._retire(s, "score")
                continue
            if st.policy.greedy:
                # spec accounting counts REAL drafts only: ramp positions
                # are teacher-forced prompt tokens, not speculation
                forced = max(0, min(ln - (base + 1), k))
                real_drafted = k - forced
                real_accepted = max(n - forced, 0)
                rejected = real_drafted - real_accepted
                st.drafted += real_drafted
                st.accepted += real_accepted
                self.counters["spec.drafted_tokens"] += real_drafted
                self.counters["spec.accepted_tokens"] += real_accepted
                self.counters["spec.rejected_tokens"] += rejected
                if rejected > 0:
                    self.counters["spec.rollbacks"] += 1
                if real_drafted > 0:
                    self._lat["spec.accept_len"].observe(
                        float(real_accepted))
                    tick_accepts.append(real_accepted)
            retired = False
            for i in range(adv):
                if base + i + 1 < ln:
                    continue                        # still teacher-forcing
                tok = int(out_tok[s, i])
                st.out.append(tok)
                self.counters["generated_tokens"] += 1
                if len(st.out) == 1:
                    self._first_token(s, st)
                eos = (self.sched.eos_token is not None
                       and tok == self.sched.eos_token)
                if eos or len(st.out) >= st.max_new_tokens:
                    # tokens past an EOS were committed to the cache but
                    # the slot retires here — release discards them, so
                    # the stream matches the oracle exactly
                    st.ctx = base + adv
                    self._retire(s, "eos" if eos else "length")
                    retired = True
                    break
            if not retired:
                st.ctx = base + adv
        if tick_accepts and self.tracer.enabled:
            # Perfetto counter track: per-tick accepted draft length
            self.tracer.counter("spec.accept_len", "scheduler",
                                mean=float(np.mean(tick_accepts)),
                                max=float(np.max(tick_accepts)))

    def _retire(self, slot: int, reason: str):
        st = self._by_slot.pop(slot)
        self._phase_end(slot)
        self.tracer.instant("retire", f"slot{slot}", rid=st.rid,
                            reason=reason)
        self.slots.release(slot)
        toks = np.asarray(st.out, np.int32)
        lps = (np.asarray(st.logprobs, np.float32)
               if st.mode == "score" else None)
        if self.sched.cache_requests and st.policy.greedy:
            key = RequestCache.key(st.prompt, st.max_new_tokens,
                                   self.sched.eos_token, mode=st.mode,
                                   policy=st.policy.fingerprint())
            self.request_cache.put(key, toks, reason, lps)
            for rid in self._inflight.pop(key, ()):     # coalesced waiters
                self._finish(rid, len(st.prompt), toks.copy(), "cached",
                             logprobs=None if lps is None else lps.copy())
        self._finish(st.rid, len(st.prompt), toks, reason, logprobs=lps,
                     accepted=st.accepted, drafted=st.drafted)

    def _finish(self, rid: int, prompt_len: int, tokens: np.ndarray,
                reason: str, logprobs: Optional[np.ndarray] = None,
                accepted: int = 0, drafted: int = 0):
        self.counters["completed"] += 1
        self._fresh.append(rid)
        tl = self._tl.pop(rid)
        comp = Completion(
            rid=rid, tokens=tokens, reason=reason, prompt_len=prompt_len,
            submit_t=tl.submit_t, finish_t=time.perf_counter(),
            admit_t=tl.admit_t, first_token_t=tl.first_token_t,
            swapped_s=tl.swapped_s, recomputed_steps=tl.recomputed_steps,
            preemptions=tl.preemptions, logprobs=logprobs,
            accepted=accepted, drafted=drafted)
        self.results[rid] = comp
        # ITL is only meaningful for pool-served requests (cache hits
        # have no decode phase)
        if tl.admit_t is not None and tl.first_token_t is not None:
            self._lat["itl_ms"].observe(comp.itl * 1e3)
