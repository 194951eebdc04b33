"""Pallas TPU kernel: chunked diagonal-linear recurrence (RWKV6 / Mamba).

The LM-scale instance of the paper's 1-D pattern (DESIGN.md §3.1): per head,

    S_t = diag(w_t) S_{t-1} + k_t^T v_t              (state: dk x dv)
    y_t = r_t (S_{t-1} + diag(u) k_t^T v_t)          (RWKV6 readout)

Squire mapping:
  * worker chunk   -> one grid step owning a C-step time chunk; the chunk's
                      working set (q/k/v/w blocks + the state) lives in VMEM.
  * global counter -> the state scratch carried across sequential grid
                      steps (Pallas TPU grids iterate in order; the scratch
                      is the boundary handoff).
  * loop fission   -> the dk x dv rank-1 update and readout are fully
                      vectorized per step (VPU); only the C-long chunk loop
                      is serial, giving depth C instead of T per (b, h).

VMEM budget per program (fp32): 4 blocks of (C, d) + state (dk, dv)
= 4*C*d + dk*dv floats; with C=64, d=dk=dv=64: ~82 KB — well under 16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mode


def _ssm_kernel(r_ref, w_ref, k_ref, v_ref, u_ref, y_ref, state_ref,
                *, chunk: int):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    def step(t, _):
        rt = r_ref[0, t, :]                      # (dk,)
        wt = w_ref[0, t, :]
        kt = k_ref[0, t, :]
        vt = v_ref[0, t, :]                      # (dv,)
        u = u_ref[...]                           # (dk,)
        s = state_ref[...]                       # (dk, dv)
        kv = kt[:, None] * vt[None, :]
        # readout uses S_{t-1} plus the bonus-weighted current kv (RWKV6)
        yt = jnp.sum(rt[:, None] * (s + u[:, None] * kv), axis=0)
        y_ref[0, pl.ds(t, 1), :] = yt[None, :]
        state_ref[...] = wt[:, None] * s + kv
        return 0

    jax.lax.fori_loop(0, chunk, step, 0, unroll=False)


@functools.partial(jax.jit, static_argnames=("chunk",))
def ssm_scan_pallas(r, w, k, v, u, *, chunk: int = 64):
    """Chunked WKV-style scan.

    Args:
      r, w, k: (B, T, dk)  — receptance / decay (multiplicative, in (0,1])
                             / key. B folds batch*heads.
      v: (B, T, dv) values.
      u: (dk,) bonus for the current token (RWKV6's `u`; zeros for Mamba).
      chunk: time chunk per grid step (the "worker" granularity).

    Returns: y (B, T, dv) in fp32.
    """
    b, t, dk = r.shape
    dv = v.shape[-1]
    if t % chunk:
        raise ValueError(f"T={t} not a multiple of chunk={chunk}")
    nchunks = t // chunk
    f32 = lambda x: x.astype(jnp.float32)

    grid = (b, nchunks)
    blk = lambda d: pl.BlockSpec((1, chunk, d), lambda i, c: (i, c, 0))
    return pl.pallas_call(
        functools.partial(_ssm_kernel, chunk=chunk),
        grid=grid,
        in_specs=[blk(dk), blk(dk), blk(dk), blk(dv),
                  pl.BlockSpec((dk,), lambda i, c: (0,))],
        out_specs=blk(dv),
        out_shape=jax.ShapeDtypeStruct((b, t, dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=mode.interpret(),
    )(f32(r), f32(w), f32(k), f32(v), f32(u))
