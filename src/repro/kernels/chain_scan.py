"""Pallas TPU kernel: banded max-plus chain recurrence (paper Alg. 3).

Consumes the fission-phase score matrix S (N, T) — produced as dense
VPU/MXU work by core.chain.chain_scores — and runs the serial part

    f(i) = max(w_i, max_t S[i, t] + f(i - t)),   t in [1, T]

with the last-T window of f held in a VMEM ring (the paper keeps it in the
workers' L1/L2; the global-counter ordering is the sequential grid).

Squire mapping:
  * worker         -> the T band lanes: every candidate in the band is
                      evaluated in one vector op (the paper's workers split
                      this same band round-robin).
  * global counter -> the ring scratch carried across sequential grid steps.

Layout (what Mosaic accepts): T is padded to the 128-lane register width
by ops.py; scores and the (N, 1) column of anchor weights w are read 8
rows (one vreg) at a time at aligned offsets; f and off are lane-dense
(block / 128, 128) tiles filled by a masked select per anchor and stored
once per grid step. No operand lives in SMEM, so the kernel batches
under vmap (the service's batched chain stage). The argmax is a lane min
over the lanes that reach the max (leftmost, like jnp.argmax), and the
ring shifts by a lane roll. VMEM per program: the (C, T) scores block, the
lane-padded (C, 1) weights and two (C/128, 128) output tiles; C=1024,
T=128 -> ~1 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mode

NEG = -1e18  # python float: becomes a compile-time immediate in the kernel
LANES = 128


def _chain_kernel(w_ref, scores_ref, f_ref, off_ref, ring_ref, *,
                  block: int):
    t_band = scores_ref.shape[1]

    @pl.when(pl.program_id(0) == 0)
    def _init():
        ring_ref[...] = jnp.full_like(ring_ref, NEG)

    lane = jax.lax.broadcasted_iota(jnp.int32, (1, t_band), 1)
    zero_row = jnp.zeros((1, LANES), jnp.float32)
    shape = f_ref.shape
    anchor = (jax.lax.broadcasted_iota(jnp.int32, shape, 0) * LANES
              + jax.lax.broadcasted_iota(jnp.int32, shape, 1))

    def group(g, carry):
        ring, f_acc, off_acc = carry          # ring slot j = f(i-1-j)
        base = pl.multiple_of(g * 8, 8)
        rows = scores_ref[pl.ds(base, 8), :]  # (8, T)
        ws = w_ref[pl.ds(base, 8), :]         # (8, 1)
        for j in range(8):
            t = base + j
            cand = rows[j:j + 1, :] + ring
            best = jnp.max(cand, axis=1, keepdims=True)           # (1, 1)
            arg = jnp.min(jnp.where(cand == best, lane, t_band),
                          axis=1, keepdims=True)                  # (1, 1)
            wi = ws[j:j + 1, :]
            fi = jnp.maximum(best, wi)
            off = jnp.where(best >= wi, arg + 1, 0)
            hit = anchor == t
            # (1, 1) -> (1, 128) -> tile: Mosaic broadcasts one axis at a
            # time, so widen to a lane row first (a float add: the integer
            # x + 0 would fold away); off is exact in fp32
            f_acc = jnp.where(hit, fi + zero_row, f_acc)
            off_acc = jnp.where(hit, off.astype(jnp.float32) + zero_row,
                                off_acc)
            # shift the window: new f enters slot 0
            ring = jnp.where(lane == 0, fi, pltpu.roll(ring, 1, 1))
        return ring, f_acc, off_acc

    ring, f_acc, off_acc = jax.lax.fori_loop(
        0, block // 8, group,
        (ring_ref[...], jnp.zeros(shape, jnp.float32),
         jnp.zeros(shape, jnp.float32)))
    ring_ref[...] = ring
    f_ref[...] = f_acc
    off_ref[...] = off_acc.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("block",))
def chain_scan_pallas(scores, w, *, block: int = 1024):
    """scores: (N, T) fp32 band scores (NEG where invalid); w: (N,).

    N must be a multiple of ``block`` and ``block`` of 128; on the chip
    ``block // 128`` must also be a multiple of 8 unless it is N // 128.
    Returns (f: (N,) fp32, off: (N,) int32 in [0, T]; 0 = chain start).
    """
    n, t = scores.shape
    if n % block or block % LANES:
        raise ValueError(f"N={n} not a multiple of block={block}, or "
                         f"block not a multiple of {LANES}")
    rows = block // LANES
    f, off = pl.pallas_call(
        functools.partial(_chain_kernel, block=block),
        grid=(n // block,),
        in_specs=[pl.BlockSpec((block, 1), lambda i: (i, 0)),
                  pl.BlockSpec((block, t), lambda i: (i, 0))],
        out_specs=[pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
                   pl.BlockSpec((rows, LANES), lambda i: (i, 0))],
        out_shape=[jax.ShapeDtypeStruct((n // LANES, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((n // LANES, LANES), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, t), jnp.float32)],
        interpret=mode.interpret(),
    )(w.astype(jnp.float32)[:, None], scores.astype(jnp.float32))
    return f.reshape(n), off.reshape(n)
