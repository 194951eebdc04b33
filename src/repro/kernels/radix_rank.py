"""Pallas TPU kernel: radix counting-sort rank pass (paper Alg. 1 hot-spot).

One LSD radix pass over a worker chunk = three dependency-bound steps:
per-key bucket histogram, exclusive bucket prefix (the serial part), and
stable rank assignment. The paper's workers run this scalar loop per
chunk; the TPU version keeps the (R, C) one-hot block in VMEM and turns
the histogram + stable rank into MXU/VPU work:

  * grid = chunks ("workers") — dependency-free fine-grain parallelism,
  * per chunk: bucket = (keys >> shift) & (R-1); the running per-bucket
    count is a (R, 1) carry across C-sized key blocks (the global-counter
    pattern — order inside a chunk preserves stability),
  * rank[i] = running_count[bucket_i] before i, computed blockwise: the
    causal prefix count of the (R, C) one-hot along the keys is one MXU
    product with a strictly upper-triangular (C, C) ones matrix (0/1
    operands in bf16, fp32 accumulation — exact up to 2^24 keys).

Layout (what Mosaic accepts): keys are (n_chunks, 1, chunk_len) so every
block's last two dims equal the array's; buckets run along sublanes and
keys along lanes, so no transpose is needed. Output is each key's stable
rank within (chunk, bucket) plus the chunk's bucket histogram; ops.py
composes ranks + histograms into scatter positions exactly like
core.sort._counting_pass (the jnp oracle).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from repro.kernels import mode


def _rank_kernel(keys_ref, rank_ref, hist_ref, *, block: int, n_blocks: int,
                 radix: int, shift: int):
    buckets = jax.lax.broadcasted_iota(jnp.int32, (radix, 1), 0)
    src = jax.lax.broadcasted_iota(jnp.int32, (block, block), 0)
    dst = jax.lax.broadcasted_iota(jnp.int32, (block, block), 1)
    before = (src < dst).astype(jnp.bfloat16)     # key c' precedes key c

    def body(i, count):
        off = pl.multiple_of(i * block, block)
        keys = keys_ref[0, :, pl.ds(off, block)]                  # (1, C)
        bucket = jax.lax.shift_right_logical(keys, shift) & (radix - 1)
        onehot = (bucket == buckets).astype(jnp.int32)            # (R, C)
        # stable rank: keys earlier in the block with the same bucket
        within = jax.lax.dot_general(
            onehot.astype(jnp.bfloat16), before, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32).astype(jnp.int32)
        rank = jnp.sum((within + count) * onehot, axis=0, keepdims=True)
        rank_ref[0, :, pl.ds(off, block)] = rank
        return count + jnp.sum(onehot, axis=1, keepdims=True)     # (R, 1)

    hist_ref[0] = jax.lax.fori_loop(0, n_blocks, body,
                                    jnp.zeros((radix, 1), jnp.int32))


@functools.partial(jax.jit, static_argnames=("radix", "shift", "block"))
def radix_rank_pallas(keys, *, radix: int = 256, shift: int = 0,
                      block: int = 512):
    """keys: (n_chunks, chunk_len) uint32. Returns (ranks, hists):
    ranks (n_chunks, chunk_len) int32 — stable rank within (chunk, bucket);
    hists (n_chunks, radix) int32 — per-chunk bucket histogram.
    chunk_len must be a multiple of `block` (and `block` of 128 on chip).
    """
    n_chunks, clen = keys.shape
    if clen % block:
        raise ValueError(f"chunk_len={clen} not a multiple of {block}")
    kern = functools.partial(_rank_kernel, block=block,
                             n_blocks=clen // block, radix=radix,
                             shift=shift)
    row = pl.BlockSpec((1, 1, clen), lambda c: (c, 0, 0))
    ranks, hists = pl.pallas_call(
        kern,
        grid=(n_chunks,),
        in_specs=[row],
        out_specs=[row, pl.BlockSpec((1, radix, 1), lambda c: (c, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((n_chunks, 1, clen), jnp.int32),
                   jax.ShapeDtypeStruct((n_chunks, radix, 1), jnp.int32)],
        interpret=mode.interpret(),
    )(jax.lax.bitcast_convert_type(keys.astype(jnp.uint32),
                                   jnp.int32)[:, None, :])
    return ranks[:, 0, :], hists[:, :, 0]
