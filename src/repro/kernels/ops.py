"""jit'd public wrappers around the Pallas kernels.

These adapt shapes (lane padding, block alignment), convert kernel-native
layouts back to caller layouts, and plug the tile kernel into the
core.wavefront scheduler so `dtw_tiled(..., tile_fn=ops.dtw_tile_fn)` runs
the Pallas path end to end.

The kernel mode (compiled on a TPU, interpreted on the CPU) comes from the
platform (kernels.mode); no wrapper takes it as an argument.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.chain_scan import chain_scan_pallas
from repro.kernels.dtw_wavefront import dp_tile_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas

NEG = jnp.float32(-1e18)


# --------------------------------------------------------------------------
# ssm_scan
# --------------------------------------------------------------------------

def ssm_scan(r, w, k, v, u=None, chunk: int = 64):
    """Chunked WKV scan with automatic T-padding. Shapes (B, T, d*)."""
    b, t, dk = r.shape
    if u is None:
        u = jnp.zeros((dk,), jnp.float32)
    pad = (-t) % chunk
    if pad:
        padk = jnp.zeros((b, pad, dk), r.dtype)
        padv = jnp.zeros((b, pad, v.shape[-1]), v.dtype)
        r = jnp.concatenate([r, padk], axis=1)
        w = jnp.concatenate([w, jnp.ones((b, pad, dk), w.dtype)], axis=1)
        k = jnp.concatenate([k, padk], axis=1)
        v = jnp.concatenate([v, padv], axis=1)
    y = ssm_scan_pallas(r, w, k, v, u, chunk=chunk)
    return y[:, :t]


# --------------------------------------------------------------------------
# chain
# --------------------------------------------------------------------------

def chain_scan(scores, w, block: int = 1024, lanes: int = 128):
    """Banded chain recurrence with band padded to `lanes` and N to block."""
    n, t = scores.shape
    if t < lanes:
        scores = jnp.concatenate(
            [scores, jnp.full((n, lanes - t), NEG)], axis=1)
    padn = (-n) % block
    if padn:
        scores = jnp.concatenate(
            [scores, jnp.full((padn, scores.shape[1]), NEG)], axis=0)
        w = jnp.concatenate([w, jnp.full((padn,), NEG)], axis=0)
    f, off = chain_scan_pallas(scores, w, block=block)
    off = jnp.minimum(off, t)  # padded lanes can never win, but clamp anyway
    return f[:n], off[:n]


def chain_anchors(q, r, T: int = 64, params=None, block: int = 1024):
    """Drop-in for core.chain.chain_anchors on the Pallas path."""
    from repro.core import chain as cchain
    params = params or cchain.ChainParams()
    n = q.shape[0]
    w = jnp.full((n,), float(params.kmer), jnp.float32)
    scores = cchain.chain_scores(q, r, T, params)   # fission phase (dense)
    f, off = chain_scan(scores, w, block=block)
    pred = jnp.where(off > 0, jnp.arange(n) - off, -1)
    return f, pred


# --------------------------------------------------------------------------
# 2-D DP tiles
# --------------------------------------------------------------------------

def _diag_to_row_major(d, tr: int, tc: int):
    rows = jnp.arange(tr)[:, None]
    cols = jnp.arange(tc)[None, :]
    return d[rows + cols, jnp.broadcast_to(rows, (tr, tc))]


@functools.partial(jax.jit,
                   static_argnames=("kind", "match", "mismatch", "gap"))
def dp_tile(top, left, corner, a, b, *, kind="dtw", **params):
    """Pallas tile with core.wavefront.TileFn signature."""
    tr, tc = a.shape[0], b.shape[0]
    d = dp_tile_pallas(top, left, corner, a, b, kind=kind, **params)
    tile = _diag_to_row_major(d, tr, tc)
    return tile, tile[-1, :], tile[:, -1], tile[-1, -1]


def dtw_tile_fn(top, left, corner, a, b):
    return dp_tile(top, left, corner, a, b, kind="dtw")


def make_sw_tile_fn(match=2.0, mismatch=-4.0, gap=4.0):
    return functools.partial(dp_tile, kind="sw", match=match,
                             mismatch=mismatch, gap=gap)


# --------------------------------------------------------------------------
# radix sort (rank kernel + jnp scatter/merge)
# --------------------------------------------------------------------------

def radix_sort_chunks(keys, vals=None, key_bits: int = 32,
                      block: int = 512):
    """Chunk-parallel LSD radix sort on the Pallas rank kernel.

    keys: (n_chunks, chunk_len) uint32 -> sorted within each chunk; the
    caller merges chunks (core.sort.merge_sorted), mirroring Alg. 1.
    """
    from repro.kernels.radix_rank import radix_rank_pallas

    n_chunks, clen = keys.shape
    if vals is None:
        vals = jnp.broadcast_to(jnp.arange(clen, dtype=jnp.int32)[None],
                                keys.shape)
    blk = min(block, clen)
    for shift in range(0, key_bits, 8):
        ranks, hists = radix_rank_pallas(keys, shift=shift, block=blk)
        starts = jnp.cumsum(hists, axis=1) - hists          # exclusive
        bucket = ((keys >> shift) & 255).astype(jnp.int32)
        pos = jnp.take_along_axis(starts, bucket, axis=1) + ranks
        keys = jnp.zeros_like(keys).at[
            jnp.arange(n_chunks)[:, None], pos].set(keys)
        vals = jnp.zeros_like(vals).at[
            jnp.arange(n_chunks)[:, None], pos].set(vals)
    return keys, vals


def dtw_tiled(s, r, tile_r: int = 128, tile_c: int = 128, **kw):
    """End-to-end Pallas DTW: wavefront scheduler + Pallas tiles."""
    from repro.core import dtw as cdtw
    return cdtw.dtw_tiled(s, r, tile_r, tile_c, tile_fn=dtw_tile_fn, **kw)


def sw_tiled(a, b, params=None, tile_r: int = 128, tile_c: int = 128):
    from repro.core import align as calign
    p = params or calign.SWParams()
    fn = make_sw_tile_fn(p.match, p.mismatch, p.gap)
    return calign.sw_tiled(a, b, p, tile_r, tile_c, tile_fn=fn)
