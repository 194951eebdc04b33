"""The mapper's reference aligns all reads together on the device
(``Reference.map_all``); the per-read anti-diagonal sweep (``map``,
``sw``) is the oracle it must equal, score for score, in the exact
configuration and under the bfloat16 control."""

import itertools
import types

import numpy as np
import pytest

from bench import gen, run
from bench.references import mapper as ref_mapper
from bench.systems.mapper import make_genome

DTYPES = ["float32", "bfloat16"]


@pytest.fixture()
def cell(tiny):
    config, mix = tiny("map-ont")
    genome = make_genome(config["genome_bases"], 2**31 + 21)
    return config, mix, genome


@pytest.fixture()
def refs(cell):
    config, _, genome = cell
    return {dt: ref_mapper.reference_for(genome, config, dt) for dt in DTYPES}


def cycle_reads(cell, seed):
    """Reads of every length of the cell's cycle, as its traffic makes
    them, at the tests' genome size."""
    from bench.check_cost import cell_reads

    config, mix, genome = cell
    return cell_reads(config, mix, genome, 8, seed)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("seed", [2**31 + 31, 2**33 + 7, 5])
def test_map_all_equals_map_read_by_read(cell, refs, seed, dtype):
    ref = refs[dtype]
    reads = cycle_reads(cell, seed)
    got = ref.map_all(reads)
    want = [ref.map(r) for r in reads]
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert (g.pos, g.n_anchors, g.chain_score, g.sw_score) == \
            (w.pos, w.n_anchors, w.chain_score, w.sw_score)
    assert sum(w.pos >= 0 for w in want) >= len(want) // 2


def _edge_pairs(case, genome, ref, g):
    base = genome[1000:1400]
    if case == "identical":
        return [(base, base.copy()), (base[:37], base[:37].copy())]
    if case == "all_mismatch":
        # reads of bases 0 and 1 against windows of bases 2 and 3
        return [(base[:300] % 2, base[50:250] % 2 + 2),
                (np.zeros(50, np.int8), np.ones(80, np.int8))]
    if case == "read_longer_than_window":
        return [(base, base[100:160]), (base[:513], base[:20])]
    if case == "window_at_genome_end":
        read = genome[-300:].copy()
        flip = g.random(300) < 0.03
        read[flip] = (read[flip] + 1) % 4
        mapping, window = ref.locate(read)
        assert window is not None and window[1] == len(genome)
        return [(read, genome[window[0]:window[1]])]
    if case == "padded_into_one_shape":
        # lengths spread over one grid cell, and more pairs than a chunk
        lens = g.integers(1, ref_mapper.SW_GRID, size=(11, 2))
        return [(genome[s:s + n], genome[s + 3:s + 3 + m])
                for s, (n, m) in zip(g.integers(0, 100_000, 11), lens)]
    raise ValueError(case)


@pytest.mark.parametrize("dtype", DTYPES)
@pytest.mark.parametrize("case", [
    "identical", "all_mismatch", "read_longer_than_window",
    "window_at_genome_end", "padded_into_one_shape"])
def test_batched_sw_equals_the_per_read_sweep(cell, refs, case, dtype,
                                              monkeypatch):
    _, _, genome = cell
    ref = refs[dtype]
    monkeypatch.setattr(ref_mapper, "SW_MAX_BATCH", 8)
    pairs = _edge_pairs(case, genome, ref, gen.rng(3, case))
    want = [ref.sw(a, b) for a, b in pairs]
    assert ref.sw_all(pairs) == want
    if case == "identical":
        assert want[0] == 2 * len(pairs[0][0]) or dtype != "float32"
    if case == "all_mismatch":
        assert want == [0.0, 0.0]


def test_one_altered_score_among_many_reads_fails_the_run(tiny,
                                                         monkeypatch):
    """Batching hides no single read's error: one read's SW score, altered
    where the service produces it, is the run's ``sw_score_gap``. The
    traffic's clock advances one second a reading, so the window holds
    three batches however fast the machine runs them."""
    from bench.traffic import read_batches
    from repro.runtime import service

    submit = service.KernelService.submit
    state = {"window": False, "altered": 0}

    def altered(self, requests):
        out = submit(self, requests)
        if state["window"] and not state["altered"]:
            out[1].sw_score += 6.0
            state["altered"] += 1
        return out

    window = read_batches.Driver.run

    def in_window(self):
        state["window"] = True
        return window(self)

    compare = ref_mapper.compare
    compared = []

    def counted(got, want, truths, tol):
        compared.append((len(got), len(want), len(truths)))
        return compare(got, want, truths, tol)

    monkeypatch.setattr(service.KernelService, "submit", altered)
    monkeypatch.setattr(read_batches.Driver, "run", in_window)
    monkeypatch.setattr(ref_mapper, "compare", counted)
    clock = itertools.count()
    monkeypatch.setattr(read_batches, "time", types.SimpleNamespace(
        perf_counter=lambda: float(next(clock))))
    config, mix = tiny("map-ont")
    out = run.execute("map-ont", 2**31 + 41, 5.0, False, config=config,
                      mix=mix)
    assert state["altered"] == 1 and out["attempted"] == 12
    # every read the window completed is compared
    assert compared == [(out["attempted"],) * 3]
    assert not out["correct"]
    checks = out["checks"]
    assert checks["sw_score_gap"]["value"] == 6.0
    assert checks["pos_mismatches"]["value"] == 0
    assert checks["anchor_mismatches"]["value"] == 0
    assert out["check_s"] > 0


def test_check_cost_maps_a_sample_alike_on_both_paths(cell):
    from bench import check_cost

    config, mix, _ = cell
    out = check_cost.measure("map-ont", 24, 2**31 + 51, 4, config=config,
                             mix=mix)
    assert out["reads"] == 24 and 0 < out["aligned"] <= 24
    assert out["sample"] >= 2
    assert out["sample_sw_gap"] == 0.0
    assert out["sample_sw_gap_bfloat16"] == 0.0
    assert out["check_s"] >= out["sw_s"] > 0
