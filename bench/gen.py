"""Traffic draws from a seed: the same seed gives the same inputs.

Every draw is stratified: n values at the fixed quantiles (i + 0.5) / n
of the stated distribution, put in an order drawn from the seed. So every
seed sends the same set of sizes, in another order, and a run's
work does not swing with the draw.
"""

from __future__ import annotations

from statistics import NormalDist

import numpy as np


def rng(seed: int, stream: str) -> np.random.Generator:
    """Independent generator for one named use of ``seed`` (any size)."""
    return np.random.default_rng([int(seed) % 2**63,
                                  *map(ord, stream)])


def key32(seed: int, stream: str) -> int:
    """A 31-bit integer for ``jax.random.PRNGKey`` from any seed."""
    return int(rng(seed, stream).integers(0, 2**31 - 1))


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def shuffled(values: np.ndarray, seed: int, stream: str) -> np.ndarray:
    return values[rng(seed, stream).permutation(len(values))]


def lognormal_ints(n: int, median: float, sigma: float, lo: int, hi: int,
                   seed: int, stream: str) -> np.ndarray:
    """n lengths from lognormal(median, sigma), clipped to [lo, hi]."""
    z = np.asarray([NormalDist().inv_cdf(q) for q in quantiles(n)])
    vals = np.clip(np.rint(median * np.exp(sigma * z)), lo, hi)
    return shuffled(vals.astype(np.int64), seed, stream)


def uniform_ints(n: int, lo: int, hi: int, seed: int, stream: str
                 ) -> np.ndarray:
    """n integers uniform over [lo, hi]."""
    vals = np.floor(lo + quantiles(n) * (hi - lo + 1)).astype(np.int64)
    return shuffled(vals, seed, stream)


def quartile_cycle(mean: float, sd: float, lo: float, hi: float,
                   per_quartile: int, seed: int, stream: str) -> np.ndarray:
    """(per_quartile, 4) read lengths: row b is batch b of a cycle, one
    length from each quartile of normal(mean, sd) clipped to [lo, hi].
    Quartile q takes ``per_quartile`` stratified quantiles inside it,
    (q + (j + 0.5) / per_quartile) / 4, each in its own row, in an order
    drawn from the seed: every seed sends the same lengths, paired into
    batches in another way."""
    nd = NormalDist(mean, sd)
    cols = []
    for q in range(4):
        lens = [min(max(round(nd.inv_cdf((q + (j + 0.5) / per_quartile)
                                         / 4)), lo), hi)
                for j in range(per_quartile)]
        cols.append(shuffled(np.asarray(lens, np.int64), seed,
                             f"{stream}{q}"))
    return np.stack(cols, axis=1)


def random_tokens(lengths, vocab: int, seed: int, stream: str):
    g = rng(seed, stream)
    return [g.integers(0, vocab, int(n)).astype(np.int32) for n in lengths]
