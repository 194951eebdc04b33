"""Continuous-batching scheduler: token identity vs per-request generate
(staggered arrivals, slot reuse), SlotManager pool mechanics, chunked
prefill exactness, per-slot sampling, the memoizing request cache, and
the KernelService 'generate' front door."""

import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.models import transformer as T
from repro.serve import (RequestCache, Scheduler, SchedulerConfig,
                         SlotManager, engine, generate)


@pytest.fixture(scope="module")
def gemma():
    cfg = configs.reduced_config("gemma-2b")
    return cfg, T.init_model(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def rwkv():
    cfg = configs.reduced_config("rwkv6-1.6b")
    return cfg, T.init_model(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def gemma3():
    """Windowed model: p0 is a sliding-window (16) ring, p1 global —
    the paged backing runs TWO page-table groups (ring + global KV)."""
    cfg = configs.reduced_config("gemma3-12b")
    return cfg, T.init_model(jax.random.PRNGKey(0), cfg)


def _prompts(rng, vocab, lens):
    return [rng.integers(0, vocab, l).astype(np.int32) for l in lens]


# --------------------------------------------------------------------------
# token identity: continuous batching == per-request generate (greedy)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("model", ["gemma", "rwkv"])
def test_staggered_arrivals_match_per_request_generate(model, request):
    """Mixed prompt lengths, arrivals mid-stream, N > pool (slot reuse
    after eviction): every emitted stream must equal engine.generate's
    (same chunk policy) under greedy sampling."""
    cfg, params = request.getfixturevalue(model)
    rng = np.random.default_rng(1)
    lens = [3, 11, 20, 33, 9, 5]
    mnts = [4, 7, 3, 6, 9, 5]
    prompts = _prompts(rng, cfg.vocab, lens)
    eos = 7
    sc = SchedulerConfig(num_slots=2, max_len=64, prefill_chunk=8,
                         eos_token=eos)
    sched = Scheduler(cfg, params, sc)

    rid2i = {}
    submitted = 0
    for i in range(3):                        # wave 1
        rid2i[sched.submit([prompts[i]], max_new_tokens=mnts[i])[0]] = i
        submitted += 1
    steps = 0
    done = []
    while sched.pending or sched.live or submitted < len(prompts):
        done += sched.step()                  # each handed out ONCE
        steps += 1
        if steps % 3 == 0 and submitted < len(prompts):   # mid-stream
            rid2i[sched.submit([prompts[submitted]],
                               max_new_tokens=mnts[submitted])[0]] \
                = submitted
            submitted += 1
    done += sched.drain()
    assert len(done) == len(prompts)
    assert len({c.rid for c in done}) == len(prompts)     # no duplicates
    assert sched.counters["completed"] == len(prompts)
    for c in done:
        i = rid2i[c.rid]
        ref, reason = generate(params, cfg, prompts[i], mnts[i],
                               eos_token=eos, prefill_chunk=8)
        assert c.tokens.tolist() == ref.tolist(), \
            f"request {i}: {c.tokens.tolist()} != {ref.tolist()}"
        assert c.reason == reason


@pytest.mark.parametrize("model", ["gemma", "rwkv"])
def test_generate_batch_rows_match_one_row(model, request):
    """generate(batch=n) replicates the request over n cache rows, the
    batch shape of an n-slot Scheduler (the oracle on the TPU, where
    each batch size rounds differently); row 0 is the one-row stream."""
    cfg, params = request.getfixturevalue(model)
    prompt = _prompts(np.random.default_rng(5), cfg.vocab, [21])[0]
    one, r1 = generate(params, cfg, prompt, 6, prefill_chunk=8)
    three, r3 = generate(params, cfg, prompt, 6, prefill_chunk=8, batch=3)
    assert three.tolist() == one.tolist() and r3 == r1


def test_lone_prefill_chunk_runs_two_rows(rwkv, monkeypatch):
    """A chunk step never runs one row alone (MIN_CHUNK_ROWS): a lone
    prefilling slot is padded by repeat, so its rounding is that of the
    multi-row chunk programs."""
    from repro.serve import slots as slots_mod
    cfg, params = rwkv
    widths = []
    run = slots_mod.SlotManager.run_chunk

    def spy(self, params, idx, toks, pos):
        widths.append(len(idx))
        return run(self, params, idx, toks, pos)

    monkeypatch.setattr(slots_mod.SlotManager, "run_chunk", spy)
    sched = Scheduler(cfg, params, SchedulerConfig(
        num_slots=4, max_len=64, prefill_chunk=8))
    prompt = _prompts(np.random.default_rng(6), cfg.vocab, [30])[0]
    rid = sched.submit([prompt], max_new_tokens=3)[0]
    sched.drain()
    assert widths and min(widths) == slots_mod.MIN_CHUNK_ROWS == 2
    ref, _ = generate(params, cfg, prompt, 3, prefill_chunk=8)
    assert sched.results[rid].tokens.tolist() == ref.tolist()


@pytest.mark.parametrize("model,allocator,preempt", [
    ("gemma", "contiguous", "recompute"), ("gemma", "paged", "recompute"),
    ("gemma", "paged", "swap"), ("gemma3", "paged", "swap")])
def test_property_random_arrival_patterns(request, model, allocator,
                                          preempt):
    """Property test: random prompt lengths / budgets / arrival patterns
    keep the scheduler token-identical to per-request generate — under
    BOTH slot allocators (paged runs block alloc/grow/free on every
    trace; a sub-equal-memory pool also exercises preempt-on-OOB, under
    both the recompute and the swap-out preemption policies) and for the
    windowed model, whose sliding-window rings page through a ring-mode
    page-table group next to the global-KV one."""
    hypothesis = pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as st

    cfg, params = request.getfixturevalue(model)
    oracle = {}

    @settings(max_examples=5, deadline=None)
    @given(st.data())
    def prop(data):
        rng = np.random.default_rng(data.draw(st.integers(0, 2**16)))
        n = data.draw(st.integers(2, 5))
        lens = [data.draw(st.integers(1, 24)) for _ in range(n)]
        mnts = [data.draw(st.integers(1, 6)) for _ in range(n)]
        stagger = data.draw(st.integers(1, 4))
        prompts = _prompts(rng, cfg.vocab, lens)
        sc = SchedulerConfig(num_slots=2, max_len=48, prefill_chunk=8,
                             cache_requests=False, allocator=allocator,
                             block_size=8, preempt=preempt,
                             num_blocks=8 if allocator == "paged" else None)
        sched = Scheduler(cfg, params, sc)
        rid2i = {}
        submitted = 0
        steps = 0
        done = []
        while submitted < n or sched.pending or sched.live:
            if submitted < n and steps % stagger == 0:
                rid2i[sched.submit([prompts[submitted]],
                                   max_new_tokens=mnts[submitted])[0]] \
                    = submitted
                submitted += 1
            done += sched.step()
            steps += 1
        for c in done + sched.drain():
            i = rid2i[c.rid]
            key = (prompts[i].tobytes(), mnts[i])
            if key not in oracle:
                # at the pool's batch shape: other shapes may round a
                # near-tied greedy pick the other way (seed 48740, lens
                # [13, 1]: top-2 gap 9e-5)
                oracle[key] = generate(params, cfg, prompts[i], mnts[i],
                                       prefill_chunk=8,
                                       batch=sc.num_slots)[0].tolist()
            assert c.tokens.tolist() == oracle[key]
        if preempt == "swap":
            assert sched.counters["recomputed_decode_steps"] == 0

    prop()


# --------------------------------------------------------------------------
# paged vs contiguous: the allocators must be observationally identical
# --------------------------------------------------------------------------

def _run_trace(cfg, params, prompts, mnts, eos, **sc_kw):
    """Replay one staggered arrival trace; returns ({idx: Completion},
    scheduler). Submissions interleave with steps so slots are reused;
    completions are collected across step() AND drain() (each handed
    out exactly once)."""
    sc = SchedulerConfig(num_slots=3, max_len=48, prefill_chunk=8,
                         eos_token=eos, cache_requests=False, **sc_kw)
    sched = Scheduler(cfg, params, sc)
    rid2i, submitted, steps, done = {}, 0, 0, []
    while submitted < len(prompts) or sched.pending or sched.live:
        if submitted < len(prompts) and steps % 2 == 0:
            rid2i[sched.submit([prompts[submitted]],
                               max_new_tokens=mnts[submitted])[0]] = submitted
            submitted += 1
        done += sched.step()
        steps += 1
    done += sched.drain()
    assert len({c.rid for c in done}) == len(done)  # delivered once each
    return {rid2i[c.rid]: c for c in done}, sched


_TRACE = dict(lens=[3, 17, 9, 24, 5, 12], mnts=[6, 4, 8, 5, 7, 3], eos=5)


@pytest.mark.parametrize("model,block_size,num_blocks,num_window_blocks,"
                         "preempt", [
    # global-attention model (the PR-3/4 arms)
    ("gemma", 8, None, None, "recompute"),
    ("gemma", 8, 6, None, "recompute"),
    ("gemma", 8, 6, None, "swap"),
    # windowed model, window(16) >> block_size(2): the ring group pages
    # 8 blocks per ring; under-provisioned global AND ring pools both
    # hit growth-OOB (ring contention during ramp-up)
    ("gemma3", 2, None, None, "recompute"),
    ("gemma3", 2, 16, 9, "recompute"),
    ("gemma3", 2, 16, 9, "swap"),
    # windowed model, window(16) < block_size(24): the ring is a single
    # partial block; the global pool under-provisions to 3
    ("gemma3", 24, 3, None, "swap"),
])
def test_paged_matches_contiguous_differential(request, model, block_size,
                                               num_blocks,
                                               num_window_blocks, preempt):
    """Same arrival trace (staggered, mixed-length, slot reuse) through
    both allocators: token-identical greedy streams and identical finish
    reasons — for the global-attention model AND the windowed model
    (whose rings page through ring-mode page-table groups, with
    window >> block_size and window < block_size layouts).
    num_blocks=None is the equal-memory pool (scheduling provably
    identical); smaller pools under-provision so growth hits
    preempt-on-OOB — invisible under greedy for BOTH policies: recompute
    restarts the victim from scratch, swap must resume it at its saved
    position (ring blocks ride the block path, not a dense snapshot)
    with ZERO recomputed decode steps (the preserved-work acceptance
    gate)."""
    cfg, params = request.getfixturevalue(model)
    rng = np.random.default_rng(7)
    prompts = _prompts(rng, cfg.vocab, _TRACE["lens"])
    mnts, eos = _TRACE["mnts"], _TRACE["eos"]
    base, ref_sched = _run_trace(cfg, params, prompts, mnts, eos)
    paged, sched = _run_trace(cfg, params, prompts, mnts, eos,
                              allocator="paged", block_size=block_size,
                              num_blocks=num_blocks,
                              num_window_blocks=num_window_blocks,
                              preempt=preempt)
    assert set(base) == set(paged) == set(range(len(prompts)))
    for i in range(len(prompts)):
        assert paged[i].tokens.tolist() == base[i].tokens.tolist(), \
            f"request {i}: paged {paged[i].tokens.tolist()} != " \
            f"contiguous {base[i].tokens.tolist()}"
        assert paged[i].reason == base[i].reason
    if num_blocks is None:
        assert sched.counters["preempted"] == 0   # equal memory: no OOB
    else:
        assert sched.counters["preempted"] >= 1   # the path really ran
    if preempt == "swap":
        # preemption preserved every decode step already paid for
        assert sched.counters["recomputed_decode_steps"] == 0
        assert sched.counters["swapped_out"] >= 1
        assert sched.counters["swapped_in"] == sched.counters["swapped_out"]
        # byte traffic is tracked by the SwapStore (single source of
        # truth), surfaced through stats()
        assert sched.stats()["swap_bytes_in"] == \
            sched.stats()["swap_bytes_out"] > 0
        # no slot-tick of work is ever redone: total live decode work ==
        # the useful work a never-preempted run does (pool TICKS may
        # still differ — a swapped request waits in the queue — but its
        # paid-for steps all survive; fig_serve gates the occupancy win)
        assert sched.counters["generated_tokens"] == \
            ref_sched.counters["generated_tokens"]
        assert sched.stats()["swapped_held"] == 0  # store fully drained
    elif num_blocks is not None:
        assert sched.counters["recomputed_decode_steps"] >= 1
    assert sched.stats()["blocks_used"] == 0      # retire freed everything


def test_reserved_admission_never_preempts(gemma):
    """admission='reserved' books blocks_for(prompt + max_new) up front:
    the under-provisioned pool that forces preemptions in the optimistic
    differential must complete the same trace with ZERO preemptions (and
    identical greedy streams) — the QoS half of the trade-off."""
    cfg, params = gemma
    rng = np.random.default_rng(7)
    prompts = _prompts(rng, cfg.vocab, _TRACE["lens"])
    mnts, eos = _TRACE["mnts"], _TRACE["eos"]
    base, _ = _run_trace(cfg, params, prompts, mnts, eos)
    got, sched = _run_trace(cfg, params, prompts, mnts, eos,
                            allocator="paged", block_size=8, num_blocks=6,
                            admission="reserved")
    for i in range(len(prompts)):
        assert got[i].tokens.tolist() == base[i].tokens.tolist()
        assert got[i].reason == base[i].reason
    assert sched.counters["preempted"] == 0
    assert sched.counters["recomputed_decode_steps"] == 0
    assert sched.stats()["blocks_used"] == 0


def test_swap_budget_rejection_falls_back_to_recompute(gemma3):
    """A SwapStore byte budget of 1 rejects every eviction: the swap
    policy must degrade to recompute per victim — still token-identical,
    with the rejection count owned by the SwapStore alone (regression:
    a scheduler-side shadow counter was once silently overwritten by
    the store's zero in the merged stats())."""
    cfg, params = gemma3
    rng = np.random.default_rng(7)
    prompts = _prompts(rng, cfg.vocab, _TRACE["lens"])
    mnts, eos = _TRACE["mnts"], _TRACE["eos"]
    base, _ = _run_trace(cfg, params, prompts, mnts, eos)
    got, sched = _run_trace(cfg, params, prompts, mnts, eos,
                            allocator="paged", block_size=2, num_blocks=16,
                            num_window_blocks=9, preempt="swap",
                            swap_bytes_budget=1)
    for i in range(len(prompts)):
        assert got[i].tokens.tolist() == base[i].tokens.tolist()
        assert got[i].reason == base[i].reason
    c = sched.counters
    assert c["swapped_out"] == 0
    assert c["preempted"] >= 1 and c["recomputed_decode_steps"] >= 1
    st = sched.stats()
    assert st["swap_rejected"] >= 1                     # the store's count
    assert st["swap_bytes_held"] == 0 and st["swap_bytes_budget"] == 1


# --------------------------------------------------------------------------
# slot manager
# --------------------------------------------------------------------------

def test_slot_manager_alloc_release_reset(rwkv):
    cfg, _ = rwkv
    sm = SlotManager(cfg, num_slots=3, cache_slots=16)
    a = sm.alloc(owner=10)
    b = sm.alloc(owner=11)
    assert {a, b} == {0, 1} and sm.free_count == 1
    assert sm.valid[a] and sm.owner[b] == 11

    # dirty slot a, release, realloc -> rows must be zeroed again
    dirty = jax.tree_util.tree_map(lambda l: l + 1, sm.gather([a]))
    sm.scatter(dirty, [a])
    sm.release(a)
    assert not sm.valid[a] and sm.free_count == 2
    a2 = sm.alloc(owner=12)
    assert a2 == a                      # LIFO free list reuses the slot
    fresh = sm.gather([a2])
    zeros = T.init_caches(cfg, 1, 16, per_slot_pos=True)
    for x, z in zip(jax.tree_util.tree_leaves(fresh),
                    jax.tree_util.tree_leaves(zeros)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(z))


def test_slot_gather_scatter_roundtrip(gemma):
    cfg, _ = gemma
    sm = SlotManager(cfg, num_slots=4, cache_slots=8)
    ref = jax.tree_util.tree_map(np.asarray, sm.caches)
    marked = jax.tree_util.tree_map(lambda l: l + 2, sm.gather([1, 3]))
    sm.scatter(marked, [1, 3])
    got = jax.tree_util.tree_map(np.asarray, sm.caches)
    for g, r in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(ref)):
        np.testing.assert_array_equal(g[:, [0, 2]], r[:, [0, 2]])
        np.testing.assert_array_equal(g[:, [1, 3]], r[:, [1, 3]] + 2)


def test_pool_exhaustion_queues_fcfs(gemma):
    cfg, params = gemma
    sc = SchedulerConfig(num_slots=1, max_len=32, prefill_chunk=8,
                         cache_requests=False)
    sched = Scheduler(cfg, params, sc)
    rng = np.random.default_rng(2)
    rids = sched.submit(_prompts(rng, cfg.vocab, [4, 4, 4]),
                        max_new_tokens=2)
    done = sched.step()
    assert sched.live == 1 and sched.pending == 2       # FCFS backlog
    done += sched.drain()
    assert [c.rid for c in done] == sorted(rids)        # completion order


def test_interleaved_step_drain_delivers_each_completion_once(gemma):
    """Regression: drain() used to return sorted(self.results) — every
    completion already handed out by an earlier step() (or a previous
    drain) came back a second time. Each completion must be delivered
    exactly once across an interleaved step/drain/submit sequence, while
    ``results`` keeps archiving until the caller pops."""
    cfg, params = gemma
    sc = SchedulerConfig(num_slots=2, max_len=32, prefill_chunk=8,
                         cache_requests=False)
    sched = Scheduler(cfg, params, sc)
    rng = np.random.default_rng(11)
    delivered = []
    r1 = sched.submit(_prompts(rng, cfg.vocab, [3, 5]), max_new_tokens=2)
    for _ in range(8):                      # enough steps to finish both
        delivered += sched.step()
    assert sorted(c.rid for c in delivered) == sorted(r1)
    assert sched.drain() == []              # nothing new: no re-delivery
    r2 = sched.submit(_prompts(rng, cfg.vocab, [4]), max_new_tokens=2)
    got = sched.drain()                     # only the new completion
    assert [c.rid for c in got] == r2
    assert sched.drain() == []
    # the archive still holds everything until the caller pops (the
    # KernelService front door pops on delivery)
    assert sorted(sched.results) == sorted(r1 + r2)
    for rid in r1 + r2:
        sched.results.pop(rid)
    assert sched.results == {}


def test_submit_validation_raises_value_error(gemma):
    """User-input feasibility is enforced with ValueError (not assert —
    it must survive `python -O`): zero budget, oversize prompt, and a
    paged request that could never fit the whole block pool."""
    cfg, params = gemma
    sched = Scheduler(cfg, params, SchedulerConfig(
        num_slots=1, max_len=16, prefill_chunk=8))
    with pytest.raises(ValueError, match="max_new_tokens"):
        sched.submit([np.arange(4, dtype=np.int32)], max_new_tokens=0)
    with pytest.raises(ValueError, match="exceeds"):
        sched.submit([np.arange(14, dtype=np.int32)], max_new_tokens=4)
    paged = Scheduler(cfg, params, SchedulerConfig(
        num_slots=1, max_len=64, prefill_chunk=8, allocator="paged",
        block_size=8, num_blocks=2))
    with pytest.raises(ValueError, match="blocks > pool"):
        paged.submit([np.arange(20, dtype=np.int32)], max_new_tokens=8)
    with pytest.raises(ValueError, match="SchedulerConfig.preempt"):
        Scheduler(cfg, params, SchedulerConfig(preempt="restart"))


def test_completion_latency_uses_monotonic_clock(gemma):
    """Completion stamps come from time.perf_counter(): latencies are
    non-negative by construction (a wall-clock NTP step cannot skew
    fig_serve's p50/p95) and ordered submit <= finish."""
    cfg, params = gemma
    sched = Scheduler(cfg, params, SchedulerConfig(
        num_slots=1, max_len=32, prefill_chunk=8, cache_requests=False))
    rng = np.random.default_rng(12)
    t0 = time.perf_counter()
    sched.submit(_prompts(rng, cfg.vocab, [4]), max_new_tokens=2)
    done = sched.drain()
    t1 = time.perf_counter()
    (c,) = done
    assert t0 <= c.submit_t <= c.finish_t <= t1
    assert 0.0 <= c.latency <= t1 - t0


# --------------------------------------------------------------------------
# chunked prefill / per-slot steps
# --------------------------------------------------------------------------

def test_chunked_prefill_matches_full_prefill_logits(gemma):
    """Chunk steps over the full prompt == one-shot prefill (tolerance:
    online-softmax accumulation order differs across chunk boundaries)."""
    cfg, params = gemma
    b, s, ch = 2, 24, 8
    toks = jax.random.randint(jax.random.PRNGKey(5), (b, s), 0, cfg.vocab)
    logits_full, caches_full = jax.jit(
        engine.make_prefill_step(cfg, cache_slots=s))(params,
                                                      {"tokens": toks})
    caches = T.init_caches(cfg, b, s, per_slot_pos=True)
    chunk = jax.jit(engine.make_chunk_step(cfg))
    for c0 in range(0, s, ch):
        logits, caches = chunk(params, caches, toks[:, c0:c0 + ch],
                               jnp.full((b,), c0, jnp.int32))
    np.testing.assert_allclose(np.asarray(logits[:, -1], np.float32),
                               np.asarray(logits_full[:, -1], np.float32),
                               rtol=3e-2, atol=3e-2)


def test_per_slot_positions_match_shared_clock(gemma):
    """A per-row position vector with equal entries must reproduce the
    scalar-clock decode step (same tokens, same caches)."""
    cfg, params = gemma
    b, s = 2, 12
    toks = jax.random.randint(jax.random.PRNGKey(6), (b, s), 0, cfg.vocab)
    prefill = jax.jit(engine.make_prefill_step(cfg, cache_slots=s + 4))
    logits, caches = prefill(params, {"tokens": toks})
    tok = engine.sample_token(logits)

    caches2 = T.init_caches(cfg, b, s + 4, per_slot_pos=True)
    chunk = jax.jit(engine.make_chunk_step(cfg))
    _, caches2 = chunk(params, caches2, toks,
                       jnp.zeros((b,), jnp.int32))
    sdec = jax.jit(engine.make_slot_decode_step(cfg))
    decode = jax.jit(engine.make_decode_step(cfg))
    key = jax.random.PRNGKey(0)
    for i in range(3):
        ref_tok, ref_logits, caches = decode(
            params, caches, {"tokens": tok[:, None]},
            jnp.asarray(s + i, jnp.int32))
        got_tok, got_logits, caches2 = sdec(
            params, caches2, tok[:, None],
            jnp.full((b,), s + i, jnp.int32),
            jnp.zeros((b,), jnp.float32), key)
        np.testing.assert_allclose(
            np.asarray(got_logits[:, 0], np.float32),
            np.asarray(ref_logits[:, 0], np.float32),
            rtol=3e-2, atol=3e-2)
        assert got_tok.tolist() == ref_tok.tolist()
        tok = ref_tok


def test_sample_token_per_slot_temperatures():
    """temps vector: greedy rows exactly argmax, hot rows vary."""
    logits = jnp.tile(jnp.asarray([[[0.0, 3.0, 1.0, 2.9]]]), (2, 1, 1))
    temps = jnp.asarray([0.0, 5.0])
    toks = [engine.sample_token(logits, jax.random.PRNGKey(i), temps)
            for i in range(40)]
    greedy = [int(t[0]) for t in toks]
    hot = [int(t[1]) for t in toks]
    assert set(greedy) == {1}
    assert len(set(hot)) > 1


# --------------------------------------------------------------------------
# shared-prefix admission (copy-on-write paged pool)
# --------------------------------------------------------------------------

def _shared_prefix_prompts(cfg, prefix_len, suffix_lens, seed=21):
    """Prompts sharing one system-prompt prefix + unique suffixes."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab, prefix_len).astype(np.int32)
    return [np.concatenate([prefix,
                            rng.integers(0, cfg.vocab, l).astype(np.int32)])
            for l in suffix_lens]


@pytest.mark.parametrize("model,block_size,num_blocks,num_window_blocks,"
                         "preempt,prefix_len,suffix_lens,mnts", [
    # global-attention model, equal-memory pool: sharing engages with no
    # preemption in sight (the common fast path)
    ("gemma", 8, None, None, "recompute", 24, [3, 6, 1, 5, 2],
     [4, 6, 3, 5, 4]),
    # under-provisioned pools: sharing + preempt-recompute and
    # preempt-swap interleave (a swapped-out sharer must resume against
    # blocks it no longer co-owns; evicted index entries must not free
    # blocks still mapped)
    ("gemma", 8, 8, None, "recompute", 24, [3, 6, 1, 5, 2],
     [4, 6, 3, 5, 4]),
    ("gemma", 8, 8, None, "swap", 24, [3, 6, 1, 5, 2], [4, 6, 3, 5, 4]),
    # windowed model: the ring group only shares when the whole request
    # span fits its view (no wrap during a sharer's lifetime), so spans
    # are kept <= window(16); the global-KV group shares alongside
    ("gemma3", 2, None, None, "recompute", 8, [2, 4, 1, 3], [4, 3, 5, 4]),
    ("gemma3", 2, 20, 12, "swap", 8, [2, 4, 1, 3], [4, 3, 5, 4]),
])
def test_shared_prefix_streams_bit_identical(request, model, block_size,
                                             num_blocks, num_window_blocks,
                                             preempt, prefix_len,
                                             suffix_lens, mnts):
    """prefix_sharing=True must be observationally invisible: the same
    staggered trace of prompts sharing a system-prompt prefix produces
    bit-identical greedy streams and finish reasons with sharing on and
    off — while actually sharing (prefix_shared_tokens > 0), including
    through preemption (recompute AND swap) and the windowed model's
    ring + global page-table groups."""
    cfg, params = request.getfixturevalue(model)
    prompts = _shared_prefix_prompts(cfg, prefix_len, suffix_lens)
    eos = _TRACE["eos"]
    kw = dict(allocator="paged", block_size=block_size,
              num_blocks=num_blocks, num_window_blocks=num_window_blocks,
              preempt=preempt)
    base, _ = _run_trace(cfg, params, prompts, mnts, eos, **kw)
    got, sched = _run_trace(cfg, params, prompts, mnts, eos,
                            prefix_sharing=True, **kw)
    assert set(base) == set(got) == set(range(len(prompts)))
    for i in range(len(prompts)):
        assert got[i].tokens.tolist() == base[i].tokens.tolist(), \
            f"request {i}: shared {got[i].tokens.tolist()} != " \
            f"unshared {base[i].tokens.tolist()}"
        assert got[i].reason == base[i].reason
    # sharing really engaged: later arrivals were admitted with their
    # prefix chunks already written
    assert sched.counters["prefix_shared_tokens"] > 0
    st = sched.stats()
    assert st["prefix_hit_chunks"] > 0 and st["prefix_published"] > 0
    if preempt == "swap":
        assert sched.counters["recomputed_decode_steps"] == 0
    # index entries pin their blocks; dropping the index frees them all
    assert st["blocks_used"] > 0            # the index holds blocks
    sched.slots.flush_prefix()
    assert sched.stats()["blocks_used"] == 0
    assert sched.stats()["shared_blocks"] == 0


def test_prefix_sharing_requires_paged_allocator(gemma):
    cfg, params = gemma
    with pytest.raises(ValueError, match="prefix_sharing requires"):
        Scheduler(cfg, params, SchedulerConfig(prefix_sharing=True))


def test_prefix_sharing_counters_zero_when_off(gemma):
    """The sharing keys are pre-declared (schema regression): a plain
    paged run reports them all as exact zeros."""
    cfg, params = gemma
    rng = np.random.default_rng(5)
    sched = Scheduler(cfg, params, SchedulerConfig(
        num_slots=2, max_len=32, prefill_chunk=8, cache_requests=False,
        allocator="paged", block_size=8))
    sched.submit(_prompts(rng, cfg.vocab, [6, 6]), max_new_tokens=2)
    sched.drain()
    st = sched.stats()
    assert sched.counters["prefix_shared_tokens"] == 0
    for k in ("shared_blocks", "cow_copies", "prefix_shared_chunks",
              "prefix_entries", "prefix_lookups", "prefix_hit_chunks",
              "prefix_published", "prefix_evicted"):
        assert st[k] == 0, k


# --------------------------------------------------------------------------
# submit atomicity (batch validation)
# --------------------------------------------------------------------------

def test_submit_batch_is_atomic(gemma):
    """Regression: submit() used to enqueue prompts one-by-one and raise
    on the first invalid member — the valid prefix of the batch stayed
    enqueued as orphans (rids the caller never received, burning pool
    space and polluting ``results``). The whole batch must validate
    before ANY request is accepted."""
    cfg, params = gemma
    sched = Scheduler(cfg, params, SchedulerConfig(
        num_slots=1, max_len=16, prefill_chunk=8, cache_requests=False))
    good = np.arange(4, dtype=np.int32)
    bad = np.arange(14, dtype=np.int32)         # 14 + 4 > max_len
    with pytest.raises(ValueError, match="exceeds"):
        sched.submit([good, bad], max_new_tokens=4)
    # nothing leaked: no pending orphan, no phantom completion
    assert sched.pending == 0 and sched.live == 0
    assert sched.counters["submitted"] == 0
    assert sched.drain() == [] and sched.results == {}
    # the paged feasibility check participates in the same all-or-nothing
    paged = Scheduler(cfg, params, SchedulerConfig(
        num_slots=1, max_len=64, prefill_chunk=8, cache_requests=False,
        allocator="paged", block_size=8, num_blocks=2))
    with pytest.raises(ValueError, match="blocks > pool"):
        paged.submit([good, np.arange(20, dtype=np.int32)],
                     max_new_tokens=8)
    assert paged.pending == 0 and paged.counters["submitted"] == 0
    # the good prompt on its own still goes through afterwards
    rids = sched.submit([good], max_new_tokens=4)
    done = sched.drain()
    assert [c.rid for c in done] == rids


# --------------------------------------------------------------------------
# request cache (zipfian traffic)
# --------------------------------------------------------------------------

def test_request_cache_key_includes_dtype_and_shape():
    """Regression: raw prompt bytes collide across dtypes/shapes — e.g.
    int64([1]) and int32([1, 0]) share little-endian bytes, as do (4,)
    and (2, 2) views of one buffer. The key must separate them."""
    a = np.asarray([1, 0], np.int32)
    b = np.asarray([1], np.int64)
    assert a.tobytes() == b.tobytes()           # the collision being fixed
    assert RequestCache.key(a, 4, None) != RequestCache.key(b, 4, None)
    c = np.asarray([[1, 0], [2, 0]], np.int32)
    d = np.asarray([1, 0, 2, 0], np.int32)
    assert c.tobytes() == d.tobytes()
    assert RequestCache.key(c, 4, None) != RequestCache.key(d, 4, None)
    # equal arrays still key equal (the cache still caches)
    assert RequestCache.key(a, 4, None) == RequestCache.key(a.copy(), 4, None)


def test_request_cache_hits_and_eviction():
    rc = RequestCache(maxsize=2)
    k1 = RequestCache.key(np.asarray([1, 2], np.int32), 4, None)
    k2 = RequestCache.key(np.asarray([1, 2], np.int32), 5, None)  # differs
    assert k1 != k2 and rc.get(k1) is None
    rc.put(k1, np.asarray([9], np.int32), "length")
    got = rc.get(k1)
    assert got is not None and got[0].tolist() == [9]
    rc.put(k2, np.asarray([8], np.int32), "length")
    rc.put(RequestCache.key(np.asarray([3], np.int32), 4, None),
           np.asarray([7], np.int32), "length")
    assert rc.get(k1) is None           # LRU evicted (maxsize=2)
    assert rc.hit_rate == pytest.approx(1 / 3)


def test_scheduler_zipf_repeats_served_from_cache(rwkv):
    cfg, params = rwkv
    sc = SchedulerConfig(num_slots=2, max_len=32, prefill_chunk=8)
    sched = Scheduler(cfg, params, sc)
    rng = np.random.default_rng(3)
    hot = _prompts(rng, cfg.vocab, [6])[0]
    r1 = sched.submit([hot], max_new_tokens=3)
    sched.drain()
    r2 = sched.submit([hot, hot], max_new_tokens=3)     # repeats: no decode
    steps_before = sched.counters["decode_steps"]
    sched.drain()
    assert sched.counters["decode_steps"] == steps_before
    for r in r2:
        assert sched.results[r].reason == "cached"
        assert sched.results[r].tokens.tolist() == \
            sched.results[r1[0]].tokens.tolist()
    assert sched.request_cache.hit_rate > 0
    # sampled requests must bypass the memo (not deterministic)
    r3 = sched.submit([hot], max_new_tokens=3, temperature=0.9)
    sched.drain()
    assert sched.results[r3[0]].reason != "cached"


def test_request_cache_put_copies_and_freezes():
    """Regression (unit): put() used to store the caller's array — a
    later in-place edit through EITHER handle silently rewrote what
    every future hit would see. The memo must own a frozen copy."""
    rc = RequestCache(maxsize=2)
    k = RequestCache.key(np.asarray([1], np.int32), 4, None)
    src = np.asarray([5, 6], np.int32)
    rc.put(k, src, "length")
    src[:] = 0                              # scribble after put
    got, reason, _ = rc.get(k)
    assert got.tolist() == [5, 6] and reason == "length"
    assert not got.flags.writeable          # hits can't poison it either


def test_request_cache_survives_completion_mutation(gemma):
    """Regression (end-to-end): _retire memoized the SAME tokens array
    it handed the original requester, so a caller mutating its
    completion in place rewrote the cache — every later duplicate
    request got the scribbled tokens with reason='cached'."""
    cfg, params = gemma
    sched = Scheduler(cfg, params, SchedulerConfig(
        num_slots=1, max_len=32, prefill_chunk=8))
    rng = np.random.default_rng(9)
    p = _prompts(rng, cfg.vocab, [6])[0]
    (r1,) = sched.submit([p], max_new_tokens=3)
    sched.drain()
    first = sched.results[r1]
    want = first.tokens.tolist()
    first.tokens[:] = -1                    # caller scribbles on its copy
    (r2,) = sched.submit([p], max_new_tokens=3)
    sched.drain()
    served = sched.results[r2]
    assert served.reason == "cached"
    assert served.tokens.tolist() == want   # memo unaffected
    # hits get their own copy too: scribbling on one cached completion
    # leaves the next hit pristine
    served.tokens[:] = -2
    (r3,) = sched.submit([p], max_new_tokens=3)
    sched.drain()
    assert sched.results[r3].tokens.tolist() == want


# --------------------------------------------------------------------------
# KernelService front door
# --------------------------------------------------------------------------

def test_kernel_service_generate_adapter(rwkv):
    from repro.runtime import KernelService, Request

    cfg, params = rwkv
    sched = Scheduler(cfg, params, SchedulerConfig(
        num_slots=2, max_len=32, prefill_chunk=8))
    svc = KernelService(lm=sched)
    assert "generate" in svc.kernels
    rng = np.random.default_rng(4)
    prompts = _prompts(rng, cfg.vocab, [5, 9, 13])
    got = svc.submit([Request("generate", {"prompt": p,
                                           "max_new_tokens": 4})
                      for p in prompts])
    for p, g in zip(prompts, got):
        ref, _ = generate(params, cfg, p, 4, prefill_chunk=8)
        assert g["tokens"].tolist() == ref.tolist()

    # pool-occupancy stats surface through the service front door
    st = svc.stats()
    assert "generate" in st["kernels"]
    assert st["lm"]["num_slots"] == 2 and "allocator" in st["lm"]

    svc_no_lm = KernelService()
    with pytest.raises(ValueError, match="generate kernel needs"):
        svc_no_lm.submit([Request("generate", {"prompt": prompts[0]})])
    assert "lm" not in svc_no_lm.stats()
