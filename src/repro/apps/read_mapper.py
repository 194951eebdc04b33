"""End-to-end read mapper (paper §VI-C): seed -> chain -> align.

The paper combines SEED, CHAIN and SW into a minimap2-skeleton read mapper
and uses it as the test-bench for end-to-end acceleration (Fig. 8). This
module is that application on the JAX substrate:

  1. **seed** — window minimizers over the read, vectorized hash-index
     probe against the reference, chunk-parallel radix sort by reference
     position (core.seeding / core.sort).
  2. **chain** — banded max-plus DP over the sorted anchors with the
     paper's loop fission + T=64 band truncation (core.chain), backtracked
     on the host to the best chain.
  3. **align** — Smith-Waterman of the read against the reference window
     the chain selected, on the tiled wavefront engine (core.align).

Shape discipline and execution both come from ``repro.runtime``: stage
inputs are padded to shape buckets (``runtime.bucketing``, sentinel-masked)
and dispatched through a ``runtime.dispatch.Dispatcher`` whose compile
cache holds one program per bucket. The stage payload builders and stage
functions are module-level so the batched ``runtime.service.KernelService``
path runs the *same* computations over whole request batches — per-read
and batched mapping are bit-identical.

``mode`` selects the execution strategy per stage, mirroring the paper's
baseline-vs-Squire comparison (Fig. 8):
  * ``baseline`` — single-chunk sort, sequential chain scan, sequential SW
    (the 1-worker / host-core-only configuration).
  * ``squire``   — chunk-parallel sort, fission/blocked chain, tiled
    wavefront SW (the accelerated configuration).
Both modes are exact: results agree anchor-for-anchor and score-for-score.

The platform picks the squire stages' implementation (kernels.mode): on a
TPU chain and SW run the Pallas kernels (and the seed sort the rank
kernel, inside core.sort); on the CPU they run the jnp stages. Both give
the same anchors, chains and scores.
"""

from __future__ import annotations

import dataclasses
import functools
from typing import List, Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import align as align_lib
from repro.core import chain as chain_lib
from repro.core import seeding
from repro.core.chain import ChainParams
from repro.kernels import mode as kmode
from repro.runtime import bucketing
from repro.runtime.dispatch import Dispatcher


@dataclasses.dataclass(frozen=True)
class MapperConfig:
    k: int = 15                 # minimizer k-mer size
    w: int = 10                 # minimizer window
    max_occ: int = 8            # max hits per minimizer
    band_T: int = 64            # chain band (the paper's T=64)
    min_chain_score: float = 40.0
    sw_window_pad: int = 64     # reference slack around the chain span
    sw_params: align_lib.SWParams = align_lib.SWParams()
    num_workers: int = 8        # sort chunks / chain blocks knob
    mode: str = "squire"        # squire | baseline
    read_bucket: int = 256      # reads padded to multiples of this
    anchor_bucket: int = 512    # anchor arrays padded to multiples of this
    sw_tile: int = 64           # wavefront tile (squire mode)


@dataclasses.dataclass
class MapResult:
    pos: int                    # mapped reference position (-1 = unmapped)
    sw_score: float
    chain_score: float
    n_anchors: int
    align_cells: int            # SW matrix cells (the align-stage work)


# --------------------------------------------------------------------------
# stage payload builders (runtime.bucketing; shared with runtime.service)
# --------------------------------------------------------------------------

def seed_payload(read: np.ndarray, cfg: MapperConfig
                 ) -> Tuple[np.ndarray, np.int32]:
    """Read padded to its read bucket + its true length."""
    nb = bucketing.round_up(len(read), cfg.read_bucket)
    padded = bucketing.pad_to(np.asarray(read, np.int32), nb, 0)
    return padded, np.int32(len(read))


def chain_payload(q: np.ndarray, r: np.ndarray, cfg: MapperConfig
                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Anchors padded to their anchor bucket with sentinel positions."""
    nv = len(q)
    nb = bucketing.round_up(max(nv, 1), cfg.anchor_bucket)
    qp = bucketing.pad_to(np.asarray(q, np.int32), nb, 0)
    rp = bucketing.pad_to(np.asarray(r, np.int32), nb, 2**30)  # far sentinel
    vp = bucketing.pad_to(np.ones(nv, bool), nb, False)
    return qp, rp, vp


def align_payload(read: np.ndarray, window: np.ndarray, cfg: MapperConfig
                  ) -> Tuple[np.ndarray, np.ndarray]:
    """Read/window padded to buckets with mutually-mismatching sentinels."""
    na = bucketing.round_up(len(read), cfg.read_bucket)
    nb = bucketing.round_up(len(window), cfg.read_bucket)
    a = bucketing.pad_to(np.asarray(read, np.int32), na, 254)
    b = bucketing.pad_to(np.asarray(window, np.int32), nb, 255)
    return a, b


def chain_window(qv: np.ndarray, rv: np.ndarray, members: List[int],
                 read_len: int, ref_len: int, cfg: MapperConfig
                 ) -> Tuple[int, int]:
    """Best chain's span -> reference window for the align stage."""
    lo_anchor, hi_anchor = members[0], members[-1]
    ref_lo = max(0, int(rv[lo_anchor]) - int(qv[lo_anchor])
                 - cfg.sw_window_pad)
    ref_hi = min(ref_len,
                 int(rv[hi_anchor]) + (read_len - int(qv[hi_anchor]))
                 + cfg.sw_window_pad)
    return ref_lo, ref_hi


# --------------------------------------------------------------------------
# per-bucket stage functions (plain; the Dispatcher jits + caches them, and
# the service vmaps the same objects — one compile cache either way)
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _seed_fn(k: int, w: int, max_occ: int, n_chunks: int):
    def run(idx_h, idx_p, read, valid_len):
        return seeding.seed(seeding.Index(idx_h, idx_p), read, k, w,
                            max_occ=max_occ, num_sort_chunks=n_chunks,
                            valid_len=valid_len)
    return run


@functools.lru_cache(maxsize=None)
def _chain_fn(T: int, mode: str, block: int):
    def run(q, r, valid):
        return chain_lib.chain_anchors(q, r, T=T, mode=mode, block=block,
                                       anchor_valid=valid)
    return run


@functools.lru_cache(maxsize=None)
def _chain_fn_pallas(T: int):
    from repro.kernels import ops

    def run(q, r, valid):
        params = ChainParams()
        n = q.shape[0]
        w = jnp.where(valid, float(params.kmer), chain_lib.NEG)
        scores = chain_lib.chain_scores(q, r, T, params, anchor_valid=valid)
        f, off = ops.chain_scan(scores, w)
        pred = jnp.where(off > 0, jnp.arange(n) - off, -1)
        return f, pred
    return run


def chain_stage(mode: str, T: int):
    """The chain stage fn for ``mode`` on this platform: the Pallas chain
    kernel for squire on a TPU, else the jnp schedule (blocked for
    squire, sequential for baseline)."""
    if mode == "squire" and kmode.use_kernels():
        return _chain_fn_pallas(T)
    return _chain_fn(T, "blocked" if mode == "squire" else "sequential", 16)


def sw_stage(mode: str, tile: int, params: align_lib.SWParams):
    """The align stage fn for ``mode`` on this platform (see _sw_fn)."""
    kernels = mode == "squire" and kmode.use_kernels()
    return _sw_fn(mode, tile, kernels, params)


@functools.lru_cache(maxsize=None)
def _sw_fn(mode: str, tile: int, kernels: bool,
           params: align_lib.SWParams):
    """-> (fn(a, b) -> (mat, score), whole_jit: bool).

    ``whole_jit=False`` marks eager wavefront schedules (only the tile is
    jitted — tracing the whole matrix would unroll thousands of tiles);
    the Dispatcher passes such fns through un-jitted. ``kernels`` (the
    platform's choice, part of the cache key) takes the Pallas tile.
    """
    if kernels:
        from repro.kernels import ops
        fn = ops.make_sw_tile_fn(params.match, params.mismatch, params.gap)

        def run(a, b):
            return align_lib.sw_tiled(a, b, params, tile_r=tile,
                                      tile_c=tile, tile_fn=fn)
        return run, False
    if mode == "squire":
        tile_fn = jax.jit(functools.partial(align_lib._sw_tile_fn, params))

        def run(a, b):
            return align_lib.sw_tiled(a, b, params, tile_r=tile,
                                      tile_c=tile, tile_fn=tile_fn)
        return run, False

    def run_base(a, b):
        mat = align_lib.sw_ref(a, b, params)
        return mat, jnp.max(mat)
    return run_base, True


class ReadMapper:
    def __init__(self, reference: np.ndarray, cfg: MapperConfig,
                 runtime: Optional[Dispatcher] = None):
        self.cfg = cfg
        self.reference = np.asarray(reference, np.int8)
        self.index = seeding.build_index(self.reference, cfg.k, cfg.w)
        self.runtime = runtime or Dispatcher()

    # -- stages --------------------------------------------------------------

    def _seed(self, read: np.ndarray):
        cfg = self.cfg
        n_chunks = cfg.num_workers if cfg.mode == "squire" else 1
        padded, true_len = seed_payload(read, cfg)
        fn = _seed_fn(cfg.k, cfg.w, cfg.max_occ, n_chunks)
        q, r, valid = self.runtime.run_one(
            fn, (self.index.hashes, self.index.positions,
                 jnp.asarray(padded), jnp.asarray(true_len)))
        return np.asarray(q), np.asarray(r), np.asarray(valid)

    def _chain(self, q: np.ndarray, r: np.ndarray):
        cfg = self.cfg
        nv = len(q)
        qp, rp, vp = chain_payload(q, r, cfg)
        f, pred = self.runtime.run_one(
            chain_stage(cfg.mode, cfg.band_T),
            (jnp.asarray(qp), jnp.asarray(rp), jnp.asarray(vp)))
        return np.asarray(f)[:nv], np.asarray(pred)[:nv]

    def _align(self, read: np.ndarray, ref_lo: int, ref_hi: int
               ) -> Tuple[float, int, int]:
        cfg = self.cfg
        window = self.reference[ref_lo:ref_hi].astype(np.int32)
        a, b = align_payload(read, window, cfg)
        fn, whole_jit = sw_stage(cfg.mode, cfg.sw_tile, cfg.sw_params)
        mat, score = self.runtime.run_one(
            fn, (jnp.asarray(a), jnp.asarray(b)), jit=whole_jit)
        end_i, end_j = align_lib.sw_end_position(mat)
        return float(score), int(end_j), len(read) * len(window)

    # -- end to end ------------------------------------------------------------

    def map_read(self, read: np.ndarray) -> MapResult:
        cfg = self.cfg
        read = np.asarray(read)
        if len(read) < cfg.k + cfg.w:
            return MapResult(-1, 0.0, 0.0, 0, 0)

        q, r, valid = self._seed(read)
        nv = int(valid.sum())
        if nv < 2:
            return MapResult(-1, 0.0, 0.0, nv, 0)
        qv, rv = q[valid], r[valid]

        f, pred = self._chain(qv, rv)
        chains = chain_lib.backtrack(f, pred,
                                     min_score=cfg.min_chain_score)
        if not chains:
            return MapResult(-1, 0.0, 0.0, nv, 0)
        score, members = chains[0]

        ref_lo, ref_hi = chain_window(qv, rv, members, len(read),
                                      len(self.reference), cfg)
        if ref_hi - ref_lo < cfg.k:
            return MapResult(-1, 0.0, score, nv, 0)

        sw_score, end_j, cells = self._align(read, ref_lo, ref_hi)
        return MapResult(pos=ref_lo, sw_score=sw_score, chain_score=score,
                         n_anchors=nv, align_cells=cells)

    def map_reads(self, reads: List[np.ndarray]) -> List[MapResult]:
        return [self.map_read(rd) for rd in reads]


def mapping_accuracy(results: List[MapResult], truths: List[int],
                     tol: int = 200) -> float:
    """Fraction of reads mapped within ``tol`` bases of their true start."""
    ok = sum(1 for res, t in zip(results, truths)
             if res.pos >= 0 and abs(res.pos - t) <= tol)
    return ok / max(len(results), 1)
