"""End-to-end read mapper (paper §VI-C): baseline == squire exactness,
mapping accuracy on planted reads, and profile behaviour (Fig. 8's
accuracy->align-work relation)."""

import numpy as np
import pytest

from repro.apps.read_mapper import MapperConfig, ReadMapper, mapping_accuracy
from repro.data import genomics


@pytest.fixture(scope="module")
def ref():
    return genomics.make_reference(12_000, seed=0)


@pytest.fixture(scope="module")
def reads(ref):
    prof = genomics.ReadProfile("TEST", 400, 80, 0.93)
    return genomics.sample_reads(ref, prof, 3, seed=1)


@pytest.mark.slow
def test_mapper_finds_planted_reads(ref, reads):
    mapper = ReadMapper(ref, MapperConfig(mode="squire"))
    res = mapper.map_reads([r for r, _ in reads])
    acc = mapping_accuracy(res, [t for _, t in reads])
    assert acc == 1.0, [(r.pos, t) for r, (_, t) in zip(res, reads)]


@pytest.mark.slow
def test_baseline_and_squire_identical(ref, reads):
    """The paper's transformation is exact: both pipelines must agree on
    position and score for every read."""
    rb = ReadMapper(ref, MapperConfig(mode="baseline")).map_reads(
        [r for r, _ in reads])
    rs = ReadMapper(ref, MapperConfig(mode="squire")).map_reads(
        [r for r, _ in reads])
    for a, b in zip(rb, rs):
        assert a.pos == b.pos
        assert a.n_anchors == b.n_anchors
        np.testing.assert_allclose(a.sw_score, b.sw_score, atol=1e-3)
        np.testing.assert_allclose(a.chain_score, b.chain_score, atol=1e-3)


@pytest.mark.slow
def test_high_accuracy_reads_anchor_denser(ref):
    """PBHF-style (99.99%) reads produce more anchors per base than
    ONT-style (85%) reads — the Fig. 8 workload-shift mechanism."""
    mapper = ReadMapper(ref, MapperConfig(mode="squire"))
    hi = genomics.sample_reads(
        ref, genomics.ReadProfile("HI", 400, 1, 0.9999), 2, seed=3)
    lo = genomics.sample_reads(
        ref, genomics.ReadProfile("LO", 400, 1, 0.85), 2, seed=3)
    d_hi = np.mean([mapper.map_read(r).n_anchors / len(r) for r, _ in hi])
    d_lo = np.mean([mapper.map_read(r).n_anchors / len(r) for r, _ in lo])
    assert d_hi > 2 * d_lo


def test_unmappable_read_returns_unmapped(ref):
    rng = np.random.default_rng(9)
    junk = rng.integers(0, 4, 300).astype(np.int8)  # random, not from ref
    mapper = ReadMapper(ref, MapperConfig(mode="squire"))
    res = mapper.map_read(junk)
    assert res.pos == -1 or res.chain_score < 60


def test_short_read_rejected(ref):
    mapper = ReadMapper(ref, MapperConfig())
    res = mapper.map_read(np.zeros(10, np.int8))
    assert res.pos == -1


def test_kernel_stages_match_jnp_stages(ref, reads, kernel_stages):
    """The TPU branch of the squire mapper (Pallas chain, SW tile and
    rank-kernel sort; here under the interpreter), per read and through
    the batched service, maps every read exactly as the jnp stages do."""
    from repro.kernels import mode
    from repro.runtime import KernelService, Request, ServiceConfig

    rs = [r for r, _ in reads]
    cfg = MapperConfig(mode="squire")
    got = ReadMapper(ref, cfg).map_reads(rs)
    batched = KernelService(ServiceConfig(mapper=cfg), reference=ref).submit(
        [Request("map", {"read": r}) for r in rs])
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(mode, "use_kernels", lambda: False)
        import jax
        jax.clear_caches()
        want = ReadMapper(ref, cfg).map_reads(rs)
        jax.clear_caches()
    for a, b, c in zip(got, batched, want):
        assert a.pos == b.pos == c.pos >= 0
        assert a.sw_score == b.sw_score == c.sw_score
        assert a.chain_score == b.chain_score == c.chain_score
        assert a.n_anchors == b.n_anchors == c.n_anchors
