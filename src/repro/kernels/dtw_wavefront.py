"""Pallas TPU kernel: 2-D DP wavefront tile (DTW min-plus / SW max-plus).

One program computes a (tr x tc) DP tile given its top row, left column and
corner boundary values — the exact unit Squire's workers compute between
local-counter handoffs (Alg. 4 / Fig. 5). Inside the tile, cells are swept
in anti-diagonal order with the whole diagonal updated as one vector op
(the fine-grain parallelism; one lane per tile row).

Output is *diagonal-major*: D[k, i] = M[i, k - i]. ops.py converts back to
row-major and extracts boundaries (a production kernel would emit tiles
directly; the conversion is outside the dependency-critical path).

Dependency bookkeeping (i = row lane, j = k - i):
    up   M[i-1, j  ] = top[j]       if i == 0 else  D_{k-1}[i-1]
    left M[i,   j-1] = left[i]      if j == 0 else  D_{k-1}[i]
    diag M[i-1, j-1] = corner       if i == 0 and j == 0
                     = top[j-1]     if i == 0
                     = left[i-1]    if j == 0
                     = D_{k-2}[i-1] otherwise

Layout (what Mosaic accepts): rows live on the lanes of (1, L) vectors,
L = max(tr, tc) rounded up to 128 (padding lanes sit above the real rows
and never feed them). "x[i-1]" is a one-lane roll. The per-column inputs
top[k - i] and b[k - i] are shift registers: each diagonal rolls them one
lane up and takes the entering element top[k] / b[k] from lane 0 of a
source row that rolls one lane down. So the kernel never reverses or
slices a vector at a dynamic offset, and every operand is a (1, L) VMEM
row (batchable under vmap: the service's batched tiles). Diagonals are
produced 8 at a time into one (8, L) tile and stored at an aligned row
offset. VMEM: the (K, L) output block plus a few vregs; tr = tc = 128 ->
~130 KB fp32.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mode

BIG = 1e18  # python float: compile-time immediate inside the kernel
LANES = 128


def _dp_tile_kernel(corner_ref, top_ref, b_ref, left_ref, a_ref, d_ref, *,
                    kind: str, tc: int, match: float, mismatch: float,
                    gap: float):
    width = a_ref.shape[1]
    ii = jax.lax.broadcasted_iota(jnp.int32, (1, width), 1)
    sub = jax.lax.broadcasted_iota(jnp.int32, (8, width), 0)
    left = left_ref[...]
    a = a_ref[...]
    corner = corner_ref[...]                      # lane 0 holds the corner

    def prev(x):                                  # lane i <- x[i-1]
        return pltpu.roll(x, 1, 1)

    def next_(x):                                 # lane i <- x[i+1]
        return pltpu.roll(x, width - 1, 1)

    left_shift = prev(left)                       # left[i-1]
    pad_val = BIG if kind == "dtw" else 0.0

    def group(g, carry):
        d1, d2, topjm1, bjm1, top_src, b_src = carry
        out = jnp.zeros((8, width), jnp.float32)
        for r in range(8):
            k = g * 8 + r
            # lane 0 of *_src is top[k] / b[k] (junk past tc: masked)
            topj = jnp.where(ii == 0, top_src, prev(topjm1))      # top[k-i]
            bj = jnp.where(ii == 0, b_src, prev(bjm1))            # b[k-i]
            jj = k - ii
            valid = (jj >= 0) & (jj < tc)
            up = jnp.where(ii == 0, topj, prev(d1))
            lf = jnp.where(jj == 0, left, d1)
            dg = jnp.where(ii == 0, topjm1,
                           jnp.where(jj == 0, left_shift, prev(d2)))
            dg = jnp.where((ii == 0) & (jj == 0), corner, dg)

            if kind == "dtw":
                new = jnp.abs(a - bj) + jnp.minimum(dg, jnp.minimum(up, lf))
            elif kind == "sw":
                s = jnp.where(a == bj, jnp.float32(match),
                              jnp.float32(mismatch))
                new = jnp.maximum(dg + s, jnp.maximum(up - gap, lf - gap))
                new = jnp.maximum(new, 0.0)
            else:
                raise ValueError(kind)

            new = jnp.where(valid, new, pad_val)
            out = jnp.where(sub == r, new, out)
            d1, d2, topjm1, bjm1 = new, d1, topj, bj
            top_src, b_src = next_(top_src), next_(b_src)
        d_ref[pl.ds(pl.multiple_of(g * 8, 8), 8), :] = out
        return d1, d2, topjm1, bjm1, top_src, b_src

    zero = jnp.zeros((1, width), jnp.float32)
    jax.lax.fori_loop(0, d_ref.shape[0] // 8, group,
                      (zero, zero, zero, zero, top_ref[...], b_ref[...]))


@functools.partial(jax.jit,
                   static_argnames=("kind", "match", "mismatch", "gap"))
def dp_tile_pallas(top, left, corner, a, b, *, kind: str = "dtw",
                   match: float = 2.0, mismatch: float = -4.0,
                   gap: float = 4.0):
    """Run one wavefront tile. Returns diagonal-major D (tr+tc-1, tr)."""
    tr, tc = a.shape[0], b.shape[0]
    n_diag = tr + tc - 1
    width = -(-max(tr, tc) // LANES) * LANES
    rows = -(-n_diag // 8) * 8

    def row(x):
        x = jnp.reshape(x, (1, -1)).astype(jnp.float32)
        return jnp.pad(x, ((0, 0), (0, width - x.shape[1])))

    kern = functools.partial(_dp_tile_kernel, kind=kind, tc=tc,
                             match=match, mismatch=mismatch, gap=gap)
    d = pl.pallas_call(
        kern,
        out_shape=jax.ShapeDtypeStruct((rows, width), jnp.float32),
        interpret=mode.interpret(),
    )(row(corner), row(top), row(b), row(left), row(a))
    return d[:n_diag, :tr]
