"""Chunk-parallel radix sort + parallel merge (paper §III-A, Alg. 1).

Squire splits the array across workers, each worker runs a scalar LSD radix
sort on its chunk, and the host merges the sorted chunks with a min-heap.
TPU adaptation:

  * chunk sort  — vmapped over chunks ("workers"); each pass is a *stable
    counting sort* realized with data-parallel primitives: one-hot bucket
    matrix -> per-bucket exclusive prefix sums give every element its rank
    (this replaces the scalar inner loop; the cumsum is the fine-grain
    parallel structure).
  * merge       — the sequential min-heap merge becomes a parallel merge:
    position of a[i] in merge(a,b) is i + searchsorted(b, a[i]); log2(W)
    pairwise rounds replace the heap. Exact and stable.

Supports an optional value array (sort-by-key), which seeding/chaining use
to carry query positions alongside reference positions.

On a TPU the chunk sort runs the Pallas rank kernel
(kernels.ops.radix_sort_chunks); the jnp counting pass below is the CPU
path and the oracle. Both are stable, so the result is the same.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

Array = jnp.ndarray

RADIX_BITS = 8
RADIX = 1 << RADIX_BITS
_LANES = 128       # the rank kernel's key block is lane-aligned


def _counting_pass(keys: Array, vals: Array, shift: int) -> Tuple[Array, Array]:
    """One stable LSD pass on a single chunk (uint32 keys)."""
    n = keys.shape[0]
    bucket = (keys >> shift) & (RADIX - 1)                    # (n,)
    onehot = jax.nn.one_hot(bucket, RADIX, dtype=jnp.int32)   # (n, R)
    within = jnp.cumsum(onehot, axis=0) - onehot              # rank in bucket
    counts = jnp.sum(onehot, axis=0)                          # (R,)
    starts = jnp.cumsum(counts) - counts                      # exclusive scan
    pos = starts[bucket] + jnp.take_along_axis(
        within, bucket[:, None], axis=1)[:, 0]
    out_k = jnp.zeros_like(keys).at[pos].set(keys)
    out_v = jnp.zeros_like(vals).at[pos].set(vals)
    return out_k, out_v


def radix_sort_chunk(keys: Array, vals: Array, key_bits: int = 32
                     ) -> Tuple[Array, Array]:
    """Full LSD radix sort of one chunk (the per-worker kernel)."""
    for shift in range(0, key_bits, RADIX_BITS):
        keys, vals = _counting_pass(keys, vals, shift)
    return keys, vals


def merge_sorted(ak: Array, av: Array, bk: Array, bv: Array
                 ) -> Tuple[Array, Array]:
    """Stable parallel merge of two sorted (key, value) arrays."""
    na, nb = ak.shape[0], bk.shape[0]
    pos_a = jnp.arange(na) + jnp.searchsorted(bk, ak, side="left")
    pos_b = jnp.arange(nb) + jnp.searchsorted(ak, bk, side="right")
    nk = jnp.zeros((na + nb,), ak.dtype)
    nv = jnp.zeros((na + nb,), av.dtype)
    nk = nk.at[pos_a].set(ak).at[pos_b].set(bk)
    nv = nv.at[pos_a].set(av).at[pos_b].set(bv)
    return nk, nv


def radix_sort(keys: Array, vals: Optional[Array] = None,
               num_chunks: int = 8, key_bits: int = 32,
               min_parallel: int = 10_000):
    """Chunk-parallel radix sort (Alg. 1). Exact vs jnp.sort.

    Like the paper (line 2 of Alg. 1), arrays below `min_parallel` skip the
    worker path and sort in one chunk — chunking overhead dominates below
    ~10k elements on Squire, and below one tile here.
    """
    from repro.kernels import mode

    if vals is None:
        vals = jnp.arange(keys.shape[0], dtype=jnp.int32)
    n = keys.shape[0]
    kernels = mode.use_kernels()
    if n < min_parallel:
        num_chunks = 1
    if num_chunks == 1 and not kernels:
        return radix_sort_chunk(keys, vals, key_bits)

    # pad to a multiple of num_chunks (x the kernel's 128-lane key block)
    # with +inf-like keys: stable, so they sort after every real key and
    # the slice below drops exactly them
    pad = (-n) % (num_chunks * (_LANES if kernels else 1))
    maxk = jnp.array(jnp.iinfo(keys.dtype).max, keys.dtype)
    if pad:
        keys = jnp.concatenate([keys, jnp.full((pad,), maxk, keys.dtype)])
        vals = jnp.concatenate([vals, jnp.zeros((pad,), vals.dtype)])
    lc = keys.shape[0] // num_chunks

    kc = keys.reshape(num_chunks, lc)
    vc = vals.reshape(num_chunks, lc)
    if kernels:
        from repro.kernels import ops
        kc, vc = ops.radix_sort_chunks(kc, vc, key_bits=key_bits,
                                       block=math.gcd(lc, 512))
    else:
        kc, vc = jax.vmap(partial(radix_sort_chunk, key_bits=key_bits))(
            kc, vc)

    # log2 rounds of pairwise merges
    chunks = [(kc[i], vc[i]) for i in range(num_chunks)]
    while len(chunks) > 1:
        nxt = []
        for i in range(0, len(chunks) - 1, 2):
            nxt.append(merge_sorted(*chunks[i], *chunks[i + 1]))
        if len(chunks) % 2:
            nxt.append(chunks[-1])
        chunks = nxt
    out_k, out_v = chunks[0]
    return out_k[:n], out_v[:n]


def sort_i32(keys: Array, vals: Optional[Array] = None, **kw):
    """Signed int32 sort: flipping the sign bit maps int32 order onto
    uint32 order (works without x64)."""
    sign = jnp.uint32(0x80000000)
    uk = jax.lax.bitcast_convert_type(keys, jnp.uint32) ^ sign
    ok, ov = radix_sort(uk, vals, **kw)
    return jax.lax.bitcast_convert_type(ok ^ sign, jnp.int32), ov
