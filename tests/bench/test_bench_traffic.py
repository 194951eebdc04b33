"""Traffic draws and the arithmetic of the end-to-end metrics."""

import importlib.util
import math
from pathlib import Path
from statistics import NormalDist

import numpy as np
import pytest

from bench import gen
from bench.record import Run, Window

ROOT = Path(__file__).resolve().parents[2]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), ROOT / "bench/metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_draws_are_deterministic_per_seed_and_a_permutation_across_seeds():
    a = gen.lognormal_ints(200, 384, 0.8, 32, 2048, 2**33 + 5, "p")
    b = gen.lognormal_ints(200, 384, 0.8, 32, 2048, 2**33 + 5, "p")
    c = gen.lognormal_ints(200, 384, 0.8, 32, 2048, 7, "p")
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert sorted(a) == sorted(c)          # the same sizes, another order
    t1 = gen.random_tokens([5, 9], 100, 3, "t")
    t2 = gen.random_tokens([5, 9], 100, 3, "t")
    assert all(np.array_equal(x, y) for x, y in zip(t1, t2))


def test_lognormal_follows_its_median_and_clips():
    x = gen.lognormal_ints(1001, 384, 0.8, 32, 2048, 1, "p")
    assert x.min() >= 32 and x.max() <= 2048
    assert np.median(x) == 384
    # the 84th percentile of a lognormal is median * e^sigma
    assert np.quantile(x, NormalDist().cdf(1.0)) == pytest.approx(
        384 * math.exp(0.8), rel=0.01)
    assert (x == 2048).sum() == (np.asarray(
        [384 * math.exp(0.8 * NormalDist().inv_cdf(q))
         for q in gen.quantiles(1001)]) >= 2048).sum()


def test_quartile_batches_take_one_length_per_quartile():
    lo, hi = 200, 1771 + 3 * 600
    a = gen.quartile_cycle(1771, 600, lo, hi, 2, 2**33 + 1, "l")
    b = gen.quartile_cycle(1771, 600, lo, hi, 2, 2**33 + 1, "l")
    c = gen.quartile_cycle(1771, 600, lo, hi, 2, 9, "l")
    assert a.shape == (2, 4) and np.array_equal(a, b)
    assert sorted(a.ravel()) == sorted(c.ravel())   # same lengths
    nd = NormalDist(1771, 600)
    for row in a:                                   # one per quartile
        assert [int(4 * nd.cdf(n)) for n in row] == [0, 1, 2, 3]
    # the stratified quantiles of each quartile, unrounded to any grid
    assert sorted(a[:, 0]) == [round(nd.inv_cdf(1 / 16)),
                               round(nd.inv_cdf(3 / 16))]
    pairings = {tuple(map(tuple, gen.quartile_cycle(
        1771, 600, lo, hi, 2, s, "l"))) for s in range(40)}
    assert len(pairings) > 1                        # the seed pairs them


def test_every_wave_of_documents_holds_the_same_sizes():
    from bench.traffic.closed_loop_lm import document_lengths

    mix = {"in_flight": 16, "waves": 3,
           "prompt": {"median": 4096, "sigma": 0.4, "lo": 2048, "hi": 8192},
           "output": {"lo": 32, "hi": 128}}
    a, outs = document_lengths(mix, 5)
    b, _ = document_lengths(mix, 2**32 + 9)
    assert len(a) == 48
    waves = [sorted(a[i:i + 16]) for i in range(0, 48, 16)]
    assert waves[0] == waves[1] == waves[2] == sorted(b[:16])
    assert a[:16] != b[:16]                 # in another order
    assert all(32 <= x <= 128 for x in outs)


def test_uniform_ints_cover_the_range_evenly():
    x = gen.uniform_ints(97, 32, 128, 2, "o")
    assert sorted(x) == list(range(32, 129))


def lm_run(seconds, counters):
    w = Window(t0=0.0, t1=seconds, items=[], attempted=0, failed=0,
               counters=counters, steps=[])
    return Run(config={}, window=w, peaks={})


def test_rates_are_all_work_over_the_whole_window():
    items = [{"bases": 1000}] * 6
    w = Window(t0=5.0, t1=17.0, items=items, attempted=6, failed=0,
               counters={})
    run = Run(config={}, window=w, peaks={})
    assert reader("map_bases_per_s").read(run) == 6000 / 12.0
    run = lm_run(8.0, {"prefill_tokens": 4096, "live_decode_slots": 904})
    assert reader("tokens_per_s").read(run) == 5000 / 8.0


def test_a_stall_lowers_the_rate():
    """The same work with a 3 s stall in the window: both rates fall."""
    base = {"prefill_tokens": 40000, "live_decode_slots": 10000}
    tokens = reader("tokens_per_s")
    assert tokens.read(lm_run(13.0, base)) < tokens.read(lm_run(10.0, base))
    items = [{"bases": 1500}] * 8
    bases = reader("map_bases_per_s")
    steady, stalled = (
        bases.read(Run(config={}, peaks={}, window=Window(
            t0=0.0, t1=t1, items=items, attempted=8, failed=0,
            counters={}))) for t1 in (20.0, 23.0))
    assert stalled < steady


def test_unfinished_requests_count_as_missing():
    """A request never finished is failed: 10 of 100 unfinished after
    the drain make 10 failures, and the check skips them."""
    from types import SimpleNamespace

    from bench.lmload import LMLoad

    load = LMLoad()
    load.system = SimpleNamespace(prefill_chunk=32)
    load.records = {i: {"prompt": None, "max_new": 4} for i in range(100)}
    for i in range(90):
        load.records[i]["tokens"] = np.zeros(4, np.int32)
    w = load.window(0.0, 1.0, {}, {}, [], {})
    assert w.attempted == 100 and w.failed == 10
    assert sum(bool(it.get("missing")) for it in w.items) == 10
    assert w.info["prefill_chunk"] == 32


def test_mapper_reads_and_the_probes_that_warm_every_window_bucket(tiny):
    """Reads are exactly their drawn length. A length whose alignment
    window can fall into two of the program's padding buckets gets a
    probe read into each: its window is the read's length plus its shift
    plus the slack on both sides, as the program computes it."""
    from bench.systems.mapper import System, sequence_read, shifted_read
    from bench.traffic.read_batches import PROBE_ACCURACY

    config, _ = tiny("map-ont")
    system = System(config, 2**31 + 5)
    g = gen.rng(1, "t")
    read, start = sequence_read(system.genome, 437, 0.85, [.5, .25, .25], g)
    assert len(read) == 437 and 0 <= start < len(system.genome)
    bucket, pad = system.mapper_cfg.read_bucket, config["sw_window_pad"]
    n = next(n for n in range(300, 900) if len(system.window_buckets(n)) > 1)
    reach = system.window_buckets(n)
    assert [b for _, b in reach] == [-(-(n + 2 * pad + s) // bucket) * bucket
                                     for s, _ in reach]
    assert len({b for _, b in reach}) == len(reach)
    for shift, padded in reach:
        read = shifted_read(system.genome, n, shift, PROBE_ACCURACY, g)
        [res] = system.map([read])
        assert res.align_cells == n * (n + shift + 2 * pad)
        assert -(-(n + shift + 2 * pad) // bucket) * bucket == padded
