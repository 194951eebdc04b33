"""Pallas TPU kernel: fused blockwise (flash) attention with GQA/MQA.

Why this kernel exists (EXPERIMENTS.md §Perf, gemma3/olmoe iterations):
the pure-jnp online-softmax path materializes fp32 (Sq, blk) score/prob
tensors per KV block — measured as the dominant memory-term contributor
on every attention train cell (~28 GB/fusion on gemma3 train_4k). The fix
is fusion, not dtype: scores must live and die in VMEM. That is exactly
what this kernel does — one (q-block × kv-block) tile of scores at a time
in VMEM scratch, with the m/l/acc online-softmax carry, so HBM traffic is
q + k + v + out only.

Squire mapping: the KV-block loop is the 1-D dependency chain (running
max/denominator = the global counter); q-blocks × (batch, head) are the
dependency-free fine-grain parallelism (the grid).

GQA/MQA: the kv BlockSpec index_map folds the query-head -> kv-head
mapping (h // group), so grouped heads read the same KV block without
materializing a broadcast.

Causal masking is by absolute position; `window > 0` adds a sliding
window (gemma3 local layers). Fully-masked KV blocks are skipped via the
loop bound (causal ⇒ kv blocks beyond the q block never load).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import mode

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                  bq: int, bk: int, seq_kv: int, window: int, scale: float):
    qb = pl.program_id(2)
    q = q_ref[0, 0].astype(jnp.float32) * scale        # (bq, hd)

    m_ref[...] = jnp.full_like(m_ref, NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)

    q_pos = qb * bq + jax.lax.iota(jnp.int32, bq)      # absolute q rows

    # causal: kv blocks strictly after this q block are fully masked
    n_kv = jax.lax.min((qb + 1) * bq + bk - 1, seq_kv) // bk

    def body(i, _):
        k_blk = k_ref[0, 0, pl.ds(i * bk, bk), :].astype(jnp.float32)
        v_blk = v_ref[0, 0, pl.ds(i * bk, bk), :].astype(jnp.float32)
        kv_pos = i * bk + jax.lax.iota(jnp.int32, bk)

        s = jax.lax.dot_general(
            q, k_blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)        # (bq, bk)
        ok = kv_pos[None, :] <= q_pos[:, None]
        if window > 0:
            ok &= (q_pos[:, None] - kv_pos[None, :]) < window
        s = jnp.where(ok, s, NEG_INF)

        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        corr = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new[:, None])
        p = jnp.where(ok, p, 0.0)
        l_ref[...] = l_ref[...] * corr + jnp.sum(p, axis=-1)
        acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
            p, v_blk, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return 0

    jax.lax.fori_loop(0, n_kv, body, 0)
    out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)[:, None]
    o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("window", "bq", "bk"))
def flash_attention_pallas(q, k, v, *, window: int = 0, bq: int = 128,
                           bk: int = 128):
    """Fused causal (optionally sliding-window) attention.

    q: (B, H, Sq, hd); k, v: (B, KV, Skv, hd) with H % KV == 0.
    Sq % bq == 0 and Skv % bk == 0 (ops.py pads). Returns (B, H, Sq, hd)
    in q.dtype.
    """
    b, h, sq, hd = q.shape
    kvh, skv = k.shape[1], k.shape[2]
    assert h % kvh == 0, (h, kvh)
    grp = h // kvh
    if sq % bq or skv % bk:
        raise ValueError(f"Sq={sq} % bq={bq} or Skv={skv} % bk={bk} != 0")
    grid = (b, h, sq // bq)
    scale = hd ** -0.5

    kern = functools.partial(_flash_kernel, bq=bq, bk=bk, seq_kv=skv,
                             window=window, scale=scale)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, hd), lambda b_, h_, q_: (b_, h_, q_, 0)),
            pl.BlockSpec((1, 1, skv, hd),
                         lambda b_, h_, q_: (b_, h_ // grp, 0, 0)),
            pl.BlockSpec((1, 1, skv, hd),
                         lambda b_, h_, q_: (b_, h_ // grp, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, hd),
                               lambda b_, h_, q_: (b_, h_, q_, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, hd), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq,), jnp.float32),
                        pltpu.VMEM((bq, hd), jnp.float32)],
        interpret=mode.interpret(),
    )(q, k, v)
