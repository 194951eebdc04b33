"""repro.runtime: bucketing round-trips, dispatcher equivalence (vmap and
shard_map), mixed-kernel KernelService.submit bit-identical to direct
kernel calls, the batched mapper vs per-read ReadMapper, the pipelined
executor, and the autotune cache."""

import json
from functools import partial

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.apps.read_mapper import MapperConfig, ReadMapper
from repro.core import align as align_lib
from repro.core import chain as chain_lib
from repro.core import dtw as dtw_lib
from repro.core import sort as rsort
from repro.core.scan1d import affine_scan
from repro.data import genomics
from repro.runtime import (Autotuner, BucketSpec, KernelService, Request,
                           ServiceConfig, bucketing, pad_stack, run_pipelined,
                           unpad, valid_mask)
from repro.runtime.autotune import seed_from_fig9
from repro.runtime.dispatch import Dispatcher, make_worker_mesh

CFG = ServiceConfig(seq_bucket=32, sw_tile=8, dtw_tile=8, anchor_bucket=64,
                    sort_bucket=64, scan_bucket=16)


# --------------------------------------------------------------------------
# bucketing
# --------------------------------------------------------------------------

def test_bucket_specs():
    lin = BucketSpec(64)
    assert [lin.padded(n) for n in (1, 64, 65, 130)] == [64, 64, 128, 192]
    p2 = BucketSpec(64, mode="pow2")
    assert [p2.padded(n) for n in (1, 64, 65, 130)] == [64, 64, 128, 256]


def test_pad_mask_unpad_roundtrip(rng):
    arrs = [rng.normal(size=n).astype(np.float32) for n in (3, 17, 32, 1)]
    lengths = bucketing.lengths_of(arrs)
    stacked = pad_stack(arrs, 32, fill=-1.0)
    assert stacked.shape == (4, 32)
    mask = valid_mask(lengths, 32)
    assert np.all(stacked[~mask] == -1.0)       # padding is all sentinel
    back = unpad(stacked, lengths)
    for a, b in zip(arrs, back):
        np.testing.assert_array_equal(a, b)     # pad -> unpad is identity


def test_group_by_bucket():
    groups = bucketing.group_by_bucket([3, 70, 64, 130, 5], BucketSpec(64))
    assert groups == {64: [0, 2, 4], 128: [1], 192: [3]}


# --------------------------------------------------------------------------
# dispatcher
# --------------------------------------------------------------------------

def _affine(x, y):
    return x * 2.0 + y, jnp.sum(x)


@pytest.mark.parametrize("use_mesh", [False, True])
def test_dispatcher_matches_direct_loop(use_mesh):
    d = Dispatcher(mesh=make_worker_mesh() if use_mesh else None)
    x = np.arange(15, dtype=np.float32).reshape(5, 3)
    y = np.float32(1.0)
    out, s = d.run(_affine, (x, y), in_axes=(0, None))
    direct = [jax.jit(_affine)(x[i], y) for i in range(5)]
    np.testing.assert_array_equal(np.asarray(out),
                                  np.stack([np.asarray(o) for o, _ in direct]))
    np.testing.assert_array_equal(np.asarray(s),
                                  np.stack([np.asarray(v) for _, v in direct]))


def test_dispatcher_odd_batch_through_mesh():
    # on this 1-device container the worker count is 1, so any batch size
    # divides; the test still pins the shard_map path's shape contract
    # (production meshes only change num_workers, not the semantics).
    d = Dispatcher(mesh=make_worker_mesh())
    x = np.arange(7, dtype=np.float32)[:, None]
    out, _ = d.run(_affine, (x, np.float32(0.0)), in_axes=(0, None))
    assert np.asarray(out).shape == (7, 1)


# --------------------------------------------------------------------------
# pipeline
# --------------------------------------------------------------------------

def test_run_pipelined_preserves_order_and_results():
    fn = jax.jit(lambda x: x * x)
    items = [np.float32(i) for i in range(9)]
    got = list(run_pipelined(items, fn, depth=3))
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray([i * i for i in range(9)],
                                             np.float32))


def test_run_pipelined_propagates_producer_errors():
    def items():
        yield 1.0
        raise RuntimeError("producer boom")
    with pytest.raises(RuntimeError, match="producer boom"):
        list(run_pipelined(items(), lambda x: x))


# --------------------------------------------------------------------------
# KernelService == direct kernel calls (bit-identical, shape sweep)
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def svc():
    return KernelService(CFG)


def test_service_chain_matches_direct(svc, rng):
    direct_fn = jax.jit(partial(chain_lib.chain_anchors, T=CFG.chain_T,
                                mode=CFG.chain_mode, block=CFG.chain_block))
    reqs, want = [], []
    for n in (5, 17, 63, 130):
        r = np.sort(rng.integers(0, 5000, n)).astype(np.int32)
        q = np.sort(rng.integers(0, 400, n)).astype(np.int32)
        reqs.append(Request("chain", {"q": q, "r": r}))
        want.append(direct_fn(jnp.asarray(q), jnp.asarray(r)))
    for got, (f, pred) in zip(svc.submit(reqs), want):
        np.testing.assert_array_equal(got["f"], np.asarray(f))
        np.testing.assert_array_equal(got["pred"], np.asarray(pred))


def test_service_sw_matches_direct(svc, rng):
    reqs, want = [], []
    for la, lb in ((7, 12), (31, 17), (40, 64), (100, 80)):
        a = rng.integers(0, 4, la).astype(np.int32)
        b = rng.integers(0, 4, lb).astype(np.int32)
        reqs.append(Request("sw", {"a": a, "b": b}))
        mat, score = align_lib.sw_tiled(
            jnp.asarray(a), jnp.asarray(b), CFG.sw_params,
            tile_r=CFG.sw_tile, tile_c=CFG.sw_tile)
        ei, ej = align_lib.sw_end_position(mat)
        want.append((float(score), (int(ei), int(ej))))
    for got, (score, end) in zip(svc.submit(reqs), want):
        assert float(got["score"]) == score
        assert tuple(int(x) for x in got["end"]) == end


def test_service_dtw_matches_direct(svc, rng):
    reqs, want = [], []
    for ls, lr in ((5, 9), (16, 16), (33, 40)):
        s = rng.normal(size=ls).astype(np.float32)
        r = rng.normal(size=lr).astype(np.float32)
        reqs.append(Request("dtw", {"s": s, "r": r}))
        want.append(float(dtw_lib.dtw_tiled(
            jnp.asarray(s), jnp.asarray(r),
            tile_r=CFG.dtw_tile, tile_c=CFG.dtw_tile)[1]))
    for got, dist in zip(svc.submit(reqs), want):
        assert float(got["distance"]) == dist


def test_service_sort_matches_direct(svc, rng):
    reqs, want = [], []
    for n in (3, 50, 130):
        keys = rng.integers(0, 2**32, n, dtype=np.uint32)
        reqs.append(Request("sort", {"keys": keys}))
        want.append(rsort.radix_sort(jnp.asarray(keys),
                                     num_chunks=CFG.sort_chunks,
                                     min_parallel=0))
    for got, (sk, sv) in zip(svc.submit(reqs), want):
        np.testing.assert_array_equal(got["keys"], np.asarray(sk))
        np.testing.assert_array_equal(got["vals"], np.asarray(sv))


def test_service_scan1d_matches_direct(svc, rng):
    direct_fn = jax.jit(affine_scan)
    reqs, want = [], []
    for t in (4, 20, 33):
        a = rng.normal(size=t).astype(np.float32)
        b = rng.normal(size=t).astype(np.float32)
        x0 = np.float32(rng.normal())
        reqs.append(Request("scan1d", {"a": a, "b": b, "x0": x0}))
        want.append(np.asarray(direct_fn(jnp.asarray(a), jnp.asarray(b),
                                         jnp.asarray(x0))))
    for got, xs in zip(svc.submit(reqs), want):
        np.testing.assert_array_equal(got["xs"], xs)


def test_service_mixed_submit_preserves_order(svc, rng):
    reqs = [
        Request("dtw", {"s": rng.normal(size=6).astype(np.float32),
                        "r": rng.normal(size=8).astype(np.float32)}),
        Request("sort", {"keys": rng.integers(0, 99, 7, dtype=np.uint32)}),
        Request("scan1d", {"a": np.ones(5, np.float32),
                           "b": np.zeros(5, np.float32),
                           "x0": np.float32(3.0)}),
        Request("dtw", {"s": rng.normal(size=12).astype(np.float32),
                        "r": rng.normal(size=5).astype(np.float32)}),
    ]
    out = svc.submit(reqs)
    assert "distance" in out[0] and "distance" in out[3]
    assert "keys" in out[1] and "xs" in out[2]
    np.testing.assert_array_equal(out[2]["xs"], np.full(5, 3.0, np.float32))
    with pytest.raises(KeyError):
        svc.submit([Request("nope", {})])


def test_service_seed_needs_reference(svc):
    with pytest.raises(ValueError, match="reference"):
        svc.submit([Request("seed", {"read": np.zeros(64, np.int8)})])


def test_service_dedups_identical_payloads_without_aliasing(svc, rng):
    """A bulk submit repeating one payload pays for ONE dispatch — and
    the duplicates must come back as fresh arrays, not views of the
    original's buffer (the RequestCache aliasing bug, one layer down:
    one caller's in-place edit must never corrupt a sibling's result)."""
    keys = rng.integers(0, 2**32, 17, dtype=np.uint32)
    other = rng.integers(0, 2**32, 9, dtype=np.uint32)
    before = svc.deduped_requests
    out = svc.submit([Request("sort", {"keys": keys}),
                      Request("sort", {"keys": other}),
                      Request("sort", {"keys": keys.copy()}),
                      Request("sort", {"keys": keys.copy()})])
    assert svc.deduped_requests == before + 2     # 3 identical -> 1 dispatch
    assert svc.metrics()["deduped_requests"] == svc.deduped_requests
    want = np.sort(keys)
    for i in (0, 2, 3):
        np.testing.assert_array_equal(out[i]["keys"], want)
    # duplicates own their buffers: scribbling on one leaves the rest
    out[2]["keys"][:] = 0
    np.testing.assert_array_equal(out[0]["keys"], want)
    np.testing.assert_array_equal(out[3]["keys"], want)
    # same little-endian bytes under different dtypes/shapes is NOT a
    # duplicate (the key carries bytes+dtype+shape, like RequestCache.key)
    from repro.runtime.service import _payload_key
    a32 = np.asarray([1, 0], np.uint32)
    b64 = np.asarray([1], np.uint64)
    assert a32.tobytes() == b64.tobytes()
    assert _payload_key({"keys": a32}) != _payload_key({"keys": b64})
    assert _payload_key({"keys": a32}) == _payload_key({"keys": a32.copy()})


# --------------------------------------------------------------------------
# adversarial shapes: the bucketing edge cases submit() must not bend on
# --------------------------------------------------------------------------

def test_service_empty_submit(svc):
    assert svc.submit([]) == []


def test_service_singleton_batch_matches_direct(svc, rng):
    """A lone request (batch of one, nothing to amortize padding against)
    must still be bit-identical to the direct jitted kernel."""
    s = rng.normal(size=CFG.seq_bucket).astype(np.float32)
    r = rng.normal(size=5).astype(np.float32)
    got = svc.submit([Request("dtw", {"s": s, "r": r})])[0]
    want = float(dtw_lib.dtw_tiled(jnp.asarray(s), jnp.asarray(r),
                                   tile_r=CFG.dtw_tile,
                                   tile_c=CFG.dtw_tile)[1])
    assert float(got["distance"]) == want


def test_service_exact_bucket_boundary_lengths(svc, rng):
    """Lengths at bucket-1 / bucket / bucket+1: the off-by-one edges of
    BucketSpec.padded (bucket is padding-free, bucket+1 spills into the
    next bucket) stay bit-identical to direct calls."""
    lens = (CFG.sort_bucket - 1, CFG.sort_bucket, CFG.sort_bucket + 1, 1)
    reqs, want = [], []
    for n in lens:
        keys = rng.integers(0, 2**32, n, dtype=np.uint32)
        reqs.append(Request("sort", {"keys": keys}))
        want.append(rsort.radix_sort(jnp.asarray(keys),
                                     num_chunks=CFG.sort_chunks,
                                     min_parallel=0))
    for got, (sk, sv) in zip(svc.submit(reqs), want):
        np.testing.assert_array_equal(got["keys"], np.asarray(sk))
        np.testing.assert_array_equal(got["vals"], np.asarray(sv))


def test_service_one_bucket_vs_distinct_buckets(svc, rng):
    """All requests sharing ONE bucket vs every request in its own
    bucket: grouping must be invisible in the results (each compared to
    the direct jitted kernel)."""
    direct_fn = jax.jit(affine_scan)
    same_bucket = (9, 11, 14)        # all pad to scan_bucket=16
    distinct = (5, 20, 40)           # pad to 16, 32, 48
    spec = BucketSpec(CFG.scan_bucket)
    assert len(bucketing.group_by_bucket(list(same_bucket), spec)) == 1
    assert len(bucketing.group_by_bucket(list(distinct), spec)) == 3
    for lens in (same_bucket, distinct):
        reqs, want = [], []
        for t in lens:
            a = rng.normal(size=t).astype(np.float32)
            b = rng.normal(size=t).astype(np.float32)
            x0 = np.float32(rng.normal())
            reqs.append(Request("scan1d", {"a": a, "b": b, "x0": x0}))
            want.append(np.asarray(direct_fn(jnp.asarray(a),
                                             jnp.asarray(b),
                                             jnp.asarray(x0))))
        for got, xs in zip(svc.submit(reqs), want):
            np.testing.assert_array_equal(got["xs"], xs)


# --------------------------------------------------------------------------
# end-to-end mapper: batched service == per-read ReadMapper (bit-identical)
# --------------------------------------------------------------------------

@pytest.mark.slow
def test_service_mapper_matches_readmapper():
    ref = genomics.make_reference(12_000, seed=0)
    prof = genomics.ReadProfile("TEST", 400, 80, 0.93)
    reads = [r for r, _ in genomics.sample_reads(ref, prof, 3, seed=1)]
    junk = np.random.default_rng(9).integers(0, 4, 300).astype(np.int8)
    reads += [junk, np.zeros(10, np.int8)]   # gating paths: unmapped, short

    mcfg = MapperConfig(mode="squire")
    direct = ReadMapper(ref, mcfg).map_reads(reads)
    svc = KernelService(ServiceConfig(mapper=mcfg), reference=ref)
    got = svc.submit([Request("map", {"read": r}) for r in reads])
    seeds = svc.submit([Request("seed", {"read": reads[0]})])

    for a, b in zip(direct, got):
        assert a.pos == b.pos
        assert a.sw_score == b.sw_score          # bit-identical, not close
        assert a.chain_score == b.chain_score
        assert a.n_anchors == b.n_anchors
        assert a.align_cells == b.align_cells
    assert len(seeds[0]["q"]) == direct[0].n_anchors


@pytest.mark.parametrize("kernel", ["chain", "sw", "dtw", "sort"])
def test_service_kernel_stages_match_jnp(kernel, kernel_stages, rng):
    """The TPU branch (Pallas chain / SW / DTW tiles / rank-kernel sort,
    here under the interpreter) returns what the jnp stages return."""
    svc = KernelService(CFG)
    reqs, want = [], []
    for n in (5, 40, 70):
        if kernel == "chain":
            q = np.sort(rng.integers(0, 400, n)).astype(np.int32)
            r = np.sort(rng.integers(0, 5000, n)).astype(np.int32)
            reqs.append(Request("chain", {"q": q, "r": r}))
            want.append(jax.jit(partial(
                chain_lib.chain_anchors, T=CFG.chain_T, mode="sequential"))(
                    jnp.asarray(q), jnp.asarray(r)))
        elif kernel == "sw":
            a = rng.integers(0, 4, n).astype(np.int32)
            b = rng.integers(0, 4, n + 9).astype(np.int32)
            reqs.append(Request("sw", {"a": a, "b": b}))
            want.append(float(np.asarray(align_lib.sw_ref(
                jnp.asarray(a), jnp.asarray(b), CFG.sw_params)).max()))
        elif kernel == "dtw":
            s = rng.normal(size=n).astype(np.float32)
            r = rng.normal(size=n + 3).astype(np.float32)
            reqs.append(Request("dtw", {"s": s, "r": r}))
            want.append(float(dtw_lib.dtw_tiled(
                jnp.asarray(s), jnp.asarray(r), tile_r=CFG.dtw_tile,
                tile_c=CFG.dtw_tile)[1]))
        else:
            keys = rng.integers(0, 2**8, n, dtype=np.uint32)   # many ties
            reqs.append(Request("sort", {"keys": keys}))
            order = np.argsort(keys, kind="stable")
            want.append((keys[order], order.astype(np.int32)))
    for got, w in zip(svc.submit(reqs), want):
        if kernel == "chain":
            np.testing.assert_array_equal(got["f"], np.asarray(w[0]))
            np.testing.assert_array_equal(got["pred"], np.asarray(w[1]))
        elif kernel == "sw":
            assert float(got["score"]) == w
        elif kernel == "dtw":
            assert float(got["distance"]) == w
        else:
            np.testing.assert_array_equal(got["keys"], w[0])
            np.testing.assert_array_equal(got["vals"], w[1])


# --------------------------------------------------------------------------
# autotune
# --------------------------------------------------------------------------

def test_autotune_cache_roundtrip(tmp_path):
    path = str(tmp_path / "cache.json")
    t = Autotuner(path)
    assert t.get("dtw.tile") is None
    t.put("dtw.tile", 32, us=12.5)
    assert Autotuner(path).get("dtw.tile") == 32   # fresh instance reloads
    data = json.loads((tmp_path / "cache.json").read_text())
    assert data["dtw.tile"]["value"] == 32


def test_autotune_tune_picks_fastest(tmp_path):
    t = Autotuner(str(tmp_path / "cache.json"))
    calls = []

    def make_thunk(cand):
        def thunk():
            calls.append(cand)
            if cand == "slow":
                sum(range(200_000))
            return jnp.zeros(())
        return thunk

    best = t.tune("toy.knob", {"slow": "slow", "fast": "fast"}, make_thunk)
    assert best == "fast"
    calls.clear()
    assert t.tune("toy.knob", {"slow": "slow"}, make_thunk) == "fast"
    assert calls == []                              # cached: not re-measured


def test_autotune_tune_skips_failing_candidates(tmp_path):
    """One bad candidate (e.g. a block size incompatible with the bucket
    shape) must not abort the sweep: it is skipped, recorded in the
    cache entry, and tune raises only when EVERY candidate fails."""
    path = str(tmp_path / "cache.json")
    t = Autotuner(path)

    def make_thunk(cand):
        def thunk():
            if cand == "bad":
                raise ValueError("block size incompatible with bucket")
            if cand == "slow":
                sum(range(200_000))
            return jnp.zeros(())
        return thunk

    best = t.tune("toy.knob", {"bad": "bad", "slow": "slow",
                               "fast": "fast"}, make_thunk)
    assert best == "fast"
    entry = json.loads((tmp_path / "cache.json").read_text())["toy.knob"]
    assert "bad" in entry["failed"]                 # failure is recorded
    assert "incompatible" in entry["failed"]["bad"]
    with pytest.raises(RuntimeError, match="every candidate failed"):
        t.tune("doomed.knob", {"bad": "bad"}, make_thunk)
    assert t.get("doomed.knob") is None             # nothing persisted


def test_autotune_save_tmp_is_per_pid_and_merges(tmp_path, monkeypatch):
    """save() renames a per-pid tmp file AND merges the on-disk entries
    first: two processes that loaded the cache before either wrote must
    not lose each other's keys to a whole-file last-rename-wins race."""
    import os

    path = str(tmp_path / "cache.json")
    seen = []
    real_replace = os.replace

    def spy(src, dst):
        seen.append(src)
        return real_replace(src, dst)

    monkeypatch.setattr(os, "replace", spy)
    a = Autotuner(path)
    b = Autotuner(path)            # "process B": loaded before A wrote
    a.put("a.knob", 1)
    assert seen and seen[0] == f"{path}.{os.getpid()}.tmp"
    b.put("b.knob", 2)             # B's save must not discard A's entry
    fresh = Autotuner(path)
    assert fresh.get("a.knob") == 1 and fresh.get("b.knob") == 2


def test_autotune_seed_from_fig9(tmp_path):
    path = str(tmp_path / "cache.json")
    rows = ["fig9.dtw.tile16,90.0,vmem_bytes=1",
            "fig9.dtw.tile32,40.0,vmem_bytes=2",
            "fig9.dtw.tile64,70.0,vmem_bytes=3",
            "fig9.ssm.chunk64,10.0,vmem_bytes=4",
            "not_a_fig9_row,1.0,x"]
    best = seed_from_fig9(rows, path=path)
    assert best == {"dtw.tile": 32, "ssm.chunk": 64}
    tuned = CFG.tuned(Autotuner(path))
    assert tuned.dtw_tile == 32 and tuned.sw_tile == 32
    assert tuned.scan_bucket == 64


def test_autotune_bucketed_keys(tmp_path):
    """Per-bucket knobs resolve ahead of per-kernel, then default."""
    path = str(tmp_path / "cache.json")
    t = Autotuner(path)
    t.put("chain.block", 16)
    t.put("chain.block@b256", 8)
    assert t.get_bucketed("chain.block", 256, 32) == 8    # bucketed wins
    assert t.get_bucketed("chain.block", 1024, 32) == 16  # kernel fallback
    assert t.get_bucketed("sort.chunks", 256, 4) == 4     # default


def test_autotune_seed_from_fig9_bucketed(tmp_path):
    """fig9's chain-block / sort-chunk sweeps carry @b<bucket> suffixes
    and land on per-bucket keys (fastest per bucket wins)."""
    path = str(tmp_path / "cache.json")
    rows = ["fig9.chain.block8@b256,50.0,depth=1",
            "fig9.chain.block16@b256,20.0,depth=2",
            "fig9.chain.block16@b1024,90.0,depth=3",
            "fig9.chain.block32@b1024,30.0,depth=4",
            "fig9.sort.chunks2@b256,15.0,depth=5",
            "fig9.dtw.tile32,40.0,vmem_bytes=2"]
    best = seed_from_fig9(rows, path=path)
    assert best == {"chain.block@b256": 16, "chain.block@b1024": 32,
                    "sort.chunks@b256": 2, "dtw.tile": 32}
    t = Autotuner(path)
    assert t.get_bucketed("chain.block", 256, 64) == 16
    assert t.get_bucketed("chain.block", 1024, 64) == 32
    assert t.get_bucketed("chain.block", 512, 64) == 64   # unswept bucket


def test_service_uses_bucketed_chain_block(tmp_path):
    """ChainAdapter consults the per-bucket tuned block in blocked mode
    (the schedule that consumes it); results stay bit-identical to the
    default knob — block size is perf-only."""
    import dataclasses
    path = str(tmp_path / "cache.json")
    t = Autotuner(path)
    t.put("chain.block@b64", 8)
    cfg = dataclasses.replace(CFG, chain_mode="blocked", chain_block=16)
    rng = np.random.default_rng(0)
    q = np.sort(rng.integers(0, 400, 40)).astype(np.int32)
    r = np.sort(rng.integers(0, 5000, 40)).astype(np.int32)
    req = [Request("chain", {"q": q, "r": r})]
    # per-bucket tuned block vs the ServiceConfig knob (no tuner)
    tuned = KernelService(cfg, tuner=t).submit(req)[0]
    default = KernelService(cfg).submit(req)[0]
    np.testing.assert_array_equal(tuned["f"], default["f"])
    np.testing.assert_array_equal(tuned["pred"], default["pred"])


def test_service_reads_no_autotune_file_by_default(monkeypatch, rng):
    """Without an explicit tuner the service compiles from its
    ServiceConfig knobs alone: no Autotuner (so no file outside the
    checkout) is ever built."""
    import repro.runtime.autotune as autotune_mod

    def refuse(self, path=None):
        raise AssertionError("the default service opened an autotune cache")

    monkeypatch.setattr(autotune_mod.Autotuner, "__init__", refuse)
    import dataclasses
    svc = KernelService(dataclasses.replace(CFG, chain_mode="blocked"))
    assert svc.tuner is None
    keys = rng.integers(0, 2**32, 40, dtype=np.uint32)
    q = np.sort(rng.integers(0, 400, 40)).astype(np.int32)
    r = np.sort(rng.integers(0, 5000, 40)).astype(np.int32)
    got = svc.submit([Request("sort", {"keys": keys}),
                      Request("chain", {"q": q, "r": r})])
    np.testing.assert_array_equal(got[0]["keys"], np.sort(keys))
