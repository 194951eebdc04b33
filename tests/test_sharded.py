"""Sharded serving slot pool: mesh_shards=1 bit-identity to the
unsharded scheduler, n-shard token identity to the unsharded oracle
(through forced swap, CoW prefix sharing, windowed rings and
speculate=k), mesh-aware placement + work-stealing rebalance, the
serve.shard observability surface, jit-cache keying across mesh sizes,
and the shard-pool invariants (property-tested when hypothesis is
installed, seeded-random always). The real shard_map lanes run on a
forced 8-device mesh in a subprocess (XLA device count is fixed at jax
import, so the in-process tests cover the delegate and vmap paths)."""

import dataclasses
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

import jax

from repro import configs
from repro.launch import mesh as mesh_lib
from repro.models import transformer as T
from repro.obs import schema
from repro.serve import Scheduler, SchedulerConfig, engine

SRC = str(Path(__file__).resolve().parents[1] / "src")
ENV = {**os.environ, "PYTHONPATH": SRC,
       "XLA_FLAGS": "--xla_force_host_platform_device_count=8"}


@pytest.fixture(scope="module")
def gemma():
    cfg = configs.reduced_config("gemma-2b")
    return cfg, T.init_model(jax.random.PRNGKey(0), cfg)


@pytest.fixture(scope="module")
def gemma3():
    """Windowed model: sliding-window rings + global KV — two
    page-table groups per shard."""
    cfg = configs.reduced_config("gemma3-12b")
    return cfg, T.init_model(jax.random.PRNGKey(0), cfg)


_LENS = [3, 17, 9, 24, 5, 12]
_MNTS = [6, 4, 8, 5, 7, 3]


def _prompts(cfg, lens, seed=1, prefix=0):
    rng = np.random.default_rng(seed)
    out = [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]
    if prefix:
        shared = rng.integers(0, cfg.vocab, prefix).astype(np.int32)
        out = [np.concatenate([shared, p]) for p in out]
    return out


def _run(cfg, params, sc, prompts, mnts, mesh=None):
    """Staggered submit/step then drain; returns ([(tokens, reason)],
    scheduler) in submission order."""
    s = Scheduler(cfg, params, sc, mesh=mesh)
    rids = []
    for p, m in zip(prompts, mnts):
        rids += s.submit([p], max_new_tokens=m)
        s.step()
    s.drain()
    return [(list(map(int, s.results[r].tokens)), s.results[r].reason)
            for r in rids], s


_BASE = SchedulerConfig(num_slots=4, max_len=64, prefill_chunk=8,
                        allocator="paged", block_size=8, num_blocks=24,
                        eos_token=5, cache_requests=False)


# --------------------------------------------------------------------------
# mesh_shards=1: bit-identical control flow AND streams vs unsharded
# --------------------------------------------------------------------------

def test_mesh1_bit_identical_to_unsharded(gemma):
    """The n=1 sharded pool runs the SAME compiled programs (the
    delegate path), the same admission order, the same slot choices —
    token streams, finish reasons and the scheduler's control-flow
    counters must all be identical to the unsharded scheduler."""
    cfg, params = gemma
    prompts = _prompts(cfg, _LENS)
    a, sa = _run(cfg, params, _BASE, prompts, _MNTS)
    b, sb = _run(cfg, params,
                 dataclasses.replace(_BASE, mesh_shards=1), prompts, _MNTS)
    assert a == b
    for k in ("admitted", "preempted", "chunk_steps", "decode_steps",
              "prefill_tokens", "generated_tokens"):
        assert sa.counters[k] == sb.counters[k], k


def test_mesh1_bit_identical_with_sampling(gemma):
    """Sampled (temperature>0) streams consume the PRNG identically on
    the n=1 path (keys reshape through the delegate unchanged)."""
    cfg, params = gemma
    sc = dataclasses.replace(_BASE, temperature=0.8, top_k=8, seed=3)
    prompts = _prompts(cfg, _LENS[:4])
    a, _ = _run(cfg, params, sc, prompts, _MNTS[:4])
    b, _ = _run(cfg, params, dataclasses.replace(sc, mesh_shards=1),
                prompts, _MNTS[:4])
    assert a == b


# --------------------------------------------------------------------------
# n-shard pools (vmap path): greedy token identity vs the unsharded
# oracle — equal TOTAL resources, per-shard splits
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n", [2, 4])
def test_sharded_tokens_match_unsharded_oracle(gemma, n):
    cfg, params = gemma
    prompts = _prompts(cfg, _LENS)
    a, _ = _run(cfg, params, _BASE, prompts, _MNTS)
    sc = dataclasses.replace(_BASE, mesh_shards=n, num_blocks=24 // n)
    b, sb = _run(cfg, params, sc, prompts, _MNTS)
    assert a == b
    assert sb.slots.num_shards == n


def test_sharded_forced_swap_matches_oracle(gemma):
    """Per-shard pools small enough that decode growth preempts; under
    preempt='swap' the resumed streams must still match the oracle
    (swap is shard-local: blocks and bytes never cross shards except
    via explicit steal migration)."""
    cfg, params = gemma
    sc = dataclasses.replace(_BASE, num_blocks=10, preempt="swap")
    prompts = _prompts(cfg, _LENS)
    mnts = [20, 16, 20, 12, 18, 14]     # long tails force decode growth
    a, sa = _run(cfg, params, sc, prompts, mnts)
    b, sb = _run(cfg, params,
                 dataclasses.replace(sc, mesh_shards=2, num_blocks=5),
                 prompts, mnts)
    assert sa.counters["swapped_out"] > 0      # both arms actually swap
    assert sb.counters["swapped_out"] > 0
    assert [t for t, _ in a] == [t for t, _ in b]


def test_sharded_prefix_sharing_matches_oracle(gemma):
    """CoW prefix sharing stays shard-local: sharers hit the index when
    placed on the shard holding the prefix (pinned here — least-blocks
    placement deliberately spreads load instead), and streams still
    match the unshared oracle bit-for-bit."""
    cfg, params = gemma
    # shared 16-token prefix = 2 chunks = 2 blocks (align lcm(8,8)=8)
    prompts = _prompts(cfg, [5, 7, 9, 6], prefix=16)
    mnts = [4, 4, 4, 4]
    a, _ = _run(cfg, params, _BASE, prompts, mnts)
    sc = dataclasses.replace(_BASE, prefix_sharing=True, mesh_shards=2,
                             num_blocks=12)
    s = Scheduler(cfg, params, sc)
    s.placement_fn = lambda sched, st: 0    # co-locate with the prefix
    rids = []
    for p, m in zip(prompts, mnts):
        rids += s.submit([p], max_new_tokens=m)
        s.step()
    s.drain()
    b = [(list(map(int, s.results[r].tokens)), s.results[r].reason)
         for r in rids]
    assert s.counters["prefix_shared_tokens"] > 0
    assert s.stats()["shared_blocks"] >= 0      # aggregated across shards
    assert a == b


def test_sharded_windowed_rings_match_oracle(gemma3):
    """Two page-table groups per shard (ring + global KV): the sharded
    pool must reproduce the windowed oracle streams."""
    cfg, params = gemma3
    sc = dataclasses.replace(_BASE, block_size=4, num_blocks=48)
    prompts = _prompts(cfg, _LENS)
    a, _ = _run(cfg, params, sc, prompts, _MNTS)
    b, _ = _run(cfg, params,
                dataclasses.replace(sc, mesh_shards=2, num_blocks=24),
                prompts, _MNTS)
    assert a == b


def test_sharded_speculative_matches_oracle(gemma):
    """speculate=k verify-accept ticks run per shard on shard-local
    rows; greedy streams must equal both the unsharded speculative run
    and (by its own test) the non-speculative oracle."""
    cfg, params = gemma
    sc = dataclasses.replace(_BASE, speculate=2)
    prompts = _prompts(cfg, _LENS[:4])
    a, _ = _run(cfg, params, sc, prompts, _MNTS[:4])
    b, _ = _run(cfg, params,
                dataclasses.replace(sc, mesh_shards=2, num_blocks=12),
                prompts, _MNTS[:4])
    assert a == b


def test_sharded_score_rows_match_oracle(gemma):
    """Prompt scoring rides the chunk path: per-token logprobs from a
    sharded pool must equal the unsharded ones bitwise (chunk logits
    come back in input order regardless of shard assignment)."""
    cfg, params = gemma
    prompts = _prompts(cfg, [19, 25, 10])

    def score(sc):
        s = Scheduler(cfg, params, sc)
        rids = s.score(prompts)
        s.drain()
        return [np.asarray(s.results[r].logprobs) for r in rids]

    a = score(_BASE)
    b = score(dataclasses.replace(_BASE, mesh_shards=2, num_blocks=12))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# --------------------------------------------------------------------------
# placement + work-stealing
# --------------------------------------------------------------------------

def test_placement_round_robin_and_pluggable(gemma):
    cfg, params = gemma
    sc = dataclasses.replace(_BASE, mesh_shards=2, num_blocks=12,
                             placement="round_robin")
    s = Scheduler(cfg, params, sc)
    prompts = _prompts(cfg, [4, 4, 4, 4])
    for p in prompts:
        s.submit([p], max_new_tokens=2)
    assert [len(q) for q in s._queues] == [2, 2]
    s.drain()
    # pluggable: pin everything to shard 1
    s2 = Scheduler(cfg, params, sc)
    s2.placement_fn = lambda sched, st: 1
    for p in prompts:
        s2.submit([p], max_new_tokens=2)
    assert [len(q) for q in s2._queues] == [0, 4]
    s2.drain()
    assert s2._shard_placed == [0, 4]


def test_steal_rebalance_beats_head_of_line(gemma):
    """A head queued behind a full shard migrates to the idle shard and
    admits immediately instead of waiting for the full shard to drain."""
    cfg, params = gemma
    sc = dataclasses.replace(_BASE, num_slots=2, mesh_shards=2,
                             num_blocks=12, max_new_tokens=24)
    s = Scheduler(cfg, params, sc)
    s.placement_fn = lambda sched, st: 0        # skewed arrivals
    prompts = _prompts(cfg, [8, 8])
    s.submit([prompts[0]], max_new_tokens=24)
    s.step()                                    # occupies shard 0's slot
    s.submit([prompts[1]], max_new_tokens=24)
    s.step()
    # shard 0 is full (1 slot) -> the second head was stolen to shard 1
    assert s.counters["steals"] == 1
    assert s.live == 2                          # both decoding at once
    s.drain()
    # no-steal control: the same skew head-of-line blocks
    s3 = Scheduler(cfg, params, dataclasses.replace(sc, steal=False))
    s3.placement_fn = lambda sched, st: 0
    s3.submit([prompts[0]], max_new_tokens=24)
    s3.step()
    s3.submit([prompts[1]], max_new_tokens=24)
    s3.step()
    assert s3.counters["steals"] == 0 and s3.live == 1
    s3.drain()


def test_steal_swapped_preserves_prefill_progress(gemma):
    """A swap-preempted request stolen to another shard moves its host
    SwapEntry (budget-checked) and resumes at its saved position: the
    final stream matches the oracle and the migration counters fire."""
    cfg, params = gemma
    prompts = _prompts(cfg, [20, 20, 8])
    mnts = [12, 12, 6]
    oracle, _ = _run(cfg, params,
                     dataclasses.replace(_BASE, num_slots=2, num_blocks=8,
                                         preempt="swap"),
                     prompts, mnts)
    sc = dataclasses.replace(_BASE, num_slots=4, mesh_shards=2,
                             num_blocks=4, preempt="swap")
    s = Scheduler(cfg, params, sc, )
    s.placement_fn = lambda sched, st: 0        # all arrive on shard 0
    rids = []
    for p, m in zip(prompts, mnts):
        rids += s.submit([p], max_new_tokens=m)
        s.step()
    s.drain()
    got = [(list(map(int, s.results[r].tokens)), s.results[r].reason)
           for r in rids]
    # the skewed run forced swap preemption and cross-shard migration
    st = s.stats()
    if s.counters["steals"] and st["swap_migrated_in"]:
        assert st["swap_migrated_in"] == st["swap_migrated_out"]
    assert [t for t, _ in got] == [t for t, _ in oracle]


# --------------------------------------------------------------------------
# observability: serve.shard gauges + stats schema
# --------------------------------------------------------------------------

def test_shard_metrics_schema(gemma):
    cfg, params = gemma
    sc = dataclasses.replace(_BASE, mesh_shards=2, num_blocks=12)
    _, s = _run(cfg, params, sc, _prompts(cfg, _LENS[:3]), _MNTS[:3])
    assert schema.validate_shard_metrics(
        s._shard_obs.metrics(), 2) == []
    assert schema.validate_stats(s.stats(), schema.SCHEDULER_STATS) == []
    assert schema.validate_stats(s.stats(), schema.PAGED_STATS) == []
    placed = sum(s._shard_obs.metrics()[f"shard{i}.placed"]
                 for i in range(2))
    assert placed == 3


# --------------------------------------------------------------------------
# mesh constructors + jit-cache keys (satellites 1 + 2)
# --------------------------------------------------------------------------

def test_make_worker_mesh_oversubscription_message():
    n = len(jax.devices()) + 1
    with pytest.raises(ValueError) as ei:
        mesh_lib.make_worker_mesh(n)
    msg = str(ei.value)
    assert f"requested {n} workers" in msg
    assert f"--xla_force_host_platform_device_count={n}" in msg
    with pytest.raises(ValueError):
        mesh_lib.make_worker_mesh(0)


def test_sharded_step_cache_keys_fold_shard_count(gemma):
    """Installing two shard counts back-to-back must give two distinct
    compiled programs (the cache key folds num_shards + mesh), and
    re-requesting the first must return the SAME object (cache hit)."""
    cfg, _ = gemma
    f1 = engine.jit_sharded_decode_step(cfg, 1, 8)
    f2 = engine.jit_sharded_decode_step(cfg, 2, 8)
    assert f1 is not f2
    assert engine.jit_sharded_decode_step(cfg, 1, 8) is f1
    mesh = mesh_lib.make_worker_mesh(1, axis="slots")
    f3 = engine.jit_sharded_decode_step(cfg, 1, 8, mesh=mesh, axis="slots")
    assert f3 is not f1


def test_dispatch_jit_cache_folds_mesh():
    from repro.runtime.dispatch import _jit_batched

    def fn(x):
        return x * 2

    mesh = mesh_lib.make_worker_mesh(1)
    a = _jit_batched(fn, (0,), None, None)
    b = _jit_batched(fn, (0,), mesh, mesh.axis_names[0])
    assert a is not b
    x = np.arange(4, dtype=np.float32)
    np.testing.assert_array_equal(np.asarray(a(x)), np.asarray(b(x)))


# --------------------------------------------------------------------------
# shard-pool invariants (hypothesis when available, seeded always)
# --------------------------------------------------------------------------

def _check_shard_invariants(s):
    """(1) every live request maps to exactly ONE shard (slot shard ==
    recorded home; queued requests sit in their home queue; swapped
    entries parked on exactly one store). (2) per-shard block
    accounting: free + mapped(+index-held) == total in every group —
    blocks never cross shards."""
    sm = s.slots
    b = sm.backing
    owners = {}
    for slot, st in s._by_slot.items():
        assert sm.shard_of_slot(slot) == st.shard
        owners[st.rid] = owners.get(st.rid, 0) + 1
    for i, q in enumerate(s._queues):
        for st in q:
            assert st.shard == i
            owners[st.rid] = owners.get(st.rid, 0) + 1
            if sm.is_swapped(st.rid):
                held = [j for j, sh in enumerate(b.shards)
                        if st.rid in sh.swaps]
                assert held == [i]      # parked exactly on the home shard
    assert all(v == 1 for v in owners.values()), owners
    for i, sh in enumerate(b.shards):
        holds = sh.prefix_holds()
        for vl, g in sh.groups.items():
            g.pt.check_invariants(holds[vl])
            free = g.pool.num_blocks - g.pool.used_count
            assert free == sum(1 for a in g.pool.allocated if not a)
        assert sm.shard_free_blocks(i) == sum(
            g.pool.num_blocks - g.pool.used_count
            for g in sh.groups.values())


def _random_serving_trace(gemma, seed, n_shards, preempt, steal=True):
    cfg, params = gemma
    rng = np.random.default_rng(seed)
    # >= 5 blocks of 8 per shard: the longest drawn request (27 prompt +
    # 9 new = 36 positions) must fit an empty shard, or submit rightly
    # refuses it (fits_pool) before any invariant is exercised
    sc = dataclasses.replace(
        _BASE, num_slots=4, mesh_shards=n_shards,
        num_blocks=int(rng.integers(5, 9)), preempt=preempt,
        placement=str(rng.choice(["least_blocks", "round_robin"])),
        steal=steal, prefix_sharing=bool(rng.integers(0, 2)))
    s = Scheduler(cfg, params, sc)
    for _ in range(int(rng.integers(4, 10))):
        k = int(rng.integers(1, 3))
        lens = rng.integers(2, 28, size=k)
        prompts = [rng.integers(0, cfg.vocab, l).astype(np.int32)
                   for l in lens]
        s.submit(prompts, max_new_tokens=int(rng.integers(1, 10)))
        for _ in range(int(rng.integers(0, 3))):
            s.step()
            _check_shard_invariants(s)
    s.drain()
    _check_shard_invariants(s)
    assert s.pending == 0 and s.live == 0
    # pool fully drained back: every block free on every shard
    for i in range(s.slots.num_shards):
        free = s.slots.shard_free_blocks(i)
        total = sum(g.pool.num_blocks
                    for g in s.slots.backing.shards[i].groups.values())
        held = sum(int(h.sum()) for h in
                   s.slots.backing.shards[i].prefix_holds().values())
        assert free + held == total


@pytest.mark.parametrize("preempt", ["recompute", "swap"])
def test_property_shard_invariants_seeded(gemma, preempt):
    for seed in (0, 1):
        _random_serving_trace(gemma, seed, 2, preempt)


def test_property_shard_invariants_hypothesis(gemma):
    pytest.importorskip("hypothesis")
    from hypothesis import given, settings, strategies as hst

    @settings(max_examples=10, deadline=None)
    @given(hst.integers(0, 2**16), hst.sampled_from([2, 4]),
           hst.sampled_from(["recompute", "swap"]))
    def prop(seed, n, preempt):
        _random_serving_trace(gemma, seed, n, preempt)

    prop()


# --------------------------------------------------------------------------
# real shard_map lanes: forced 8-device mesh in a subprocess (two mesh
# sizes back-to-back also regression-tests the jit-cache keying on live
# meshes)
# --------------------------------------------------------------------------

_SHARD_MAP_CODE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses
    import numpy as np
    import jax
    from repro import configs
    from repro.launch import mesh as mesh_lib
    from repro.models import transformer as T
    from repro.serve import Scheduler, SchedulerConfig

    cfg = configs.reduced_config("gemma-2b")
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(1)
    lens, mnts = [3, 17, 9, 12], [5, 4, 6, 3]
    prompts = [rng.integers(0, cfg.vocab, l).astype(np.int32)
               for l in lens]

    base = SchedulerConfig(num_slots=4, max_len=64, prefill_chunk=8,
                           allocator="paged", block_size=8,
                           num_blocks=16, eos_token=5,
                           cache_requests=False)

    def run(sc, mesh=None):
        s = Scheduler(cfg, params, sc, mesh=mesh)
        rids = []
        for p, m in zip(prompts, mnts):
            rids += s.submit([p], max_new_tokens=m)
            s.step()
        s.drain()
        return [list(map(int, s.results[r].tokens)) for r in rids]

    oracle = run(base)
    for n in (2, 4):            # two mesh sizes back-to-back
        mesh = mesh_lib.make_worker_mesh(n, axis="slots")
        got = run(dataclasses.replace(base, mesh_shards=n,
                                      num_blocks=16 // n), mesh=mesh)
        assert got == oracle, (n, got, oracle)
    print("SHARD_MAP_OK", len(jax.devices()))
""")


def test_shard_map_differential_forced_8_devices():
    res = subprocess.run([sys.executable, "-c", _SHARD_MAP_CODE],
                         capture_output=True, text=True, env=ENV,
                         timeout=1200)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "SHARD_MAP_OK 8" in res.stdout
