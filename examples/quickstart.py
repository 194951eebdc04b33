"""Quickstart: the Squire dependency-decomposition engine in five minutes.

Runs on one CPU device. Shows the paper's three kernel patterns (1-D chain,
2-D wavefront, chunk-parallel sort) through the public API, each in its
sequential ("one worker") and Squire-parallel form, asserting exactness —
then one LM training step whose recurrent layer is the same engine.

    PYTHONPATH=src python examples/quickstart.py
"""

import numpy as np

import jax
import jax.numpy as jnp

from repro.core import MAXPLUS, affine_scan
from repro.core import chain as chain_lib
from repro.core import dtw as dtw_lib
from repro.core import sort as sort_lib
from repro.data import genomics


def demo_scan1d():
    print("== 1-D recurrence engine (the global counter) ==")
    key = jax.random.PRNGKey(0)
    a = jax.random.normal(key, (1024,))
    b = jax.random.normal(jax.random.PRNGKey(1), (1024,))
    x0 = jnp.zeros(())
    seq = affine_scan(a, b, x0, MAXPLUS, mode="sequential")
    chk = affine_scan(a, b, x0, MAXPLUS, mode="chunked", num_chunks=16)
    par = affine_scan(a, b, x0, MAXPLUS, mode="associative")
    assert np.allclose(seq, chk, atol=1e-4) \
        and np.allclose(seq, par, atol=1e-4)
    print("  sequential == chunked(16 workers) == associative: exact "
          "(up to fp32 reassociation)\n")


def demo_chain():
    print("== Chain kernel (minimap2, paper Alg. 2/3) ==")
    q, r = genomics.anchor_set(2000, seed=0)
    f_seq, p_seq = chain_lib.chain_anchors(jnp.asarray(q), jnp.asarray(r),
                                           T=64, mode="sequential")
    f_blk, p_blk = chain_lib.chain_anchors(jnp.asarray(q), jnp.asarray(r),
                                           T=64, mode="blocked")
    assert np.allclose(f_seq, f_blk, atol=1e-4)
    chains = chain_lib.backtrack(np.asarray(f_seq), np.asarray(p_seq))
    print(f"  2000 anchors -> best chain score "
          f"{float(jnp.max(f_seq)):.1f}, {len(chains)} chains; "
          "sequential == blocked: exact\n")


def demo_dtw():
    print("== DTW (paper Alg. 4) on the tiled wavefront ==")
    key = jax.random.PRNGKey(2)
    s = jax.random.normal(key, (128,))
    r = jax.random.normal(jax.random.PRNGKey(3), (160,))
    ref = dtw_lib.dtw_ref(s, r)
    mat, dist = dtw_lib.dtw_tiled(s, r, tile_r=32, tile_c=32)
    assert np.allclose(mat, ref, atol=1e-4)
    print(f"  DTW distance {float(dist):.2f}; "
          "tiled wavefront == sequential: exact\n")


def demo_sort():
    print("== Chunk-parallel radix sort (paper Alg. 1) ==")
    keys = jax.random.randint(jax.random.PRNGKey(4), (50_000,), 0,
                              2**31 - 1, dtype=jnp.int32).astype(jnp.uint32)
    sk, sv = sort_lib.radix_sort(keys, num_chunks=8)
    assert np.array_equal(np.asarray(sk), np.sort(np.asarray(keys)))
    print("  50k keys, 8 worker chunks + parallel merge == jnp.sort\n")


def demo_lm_step():
    print("== One LM train step (RWKV6: the engine at LM scale) ==")
    from repro import configs
    from repro.optim import AdamWConfig
    from repro.train import init_train_state, make_train_step

    cfg = configs.reduced_config("rwkv6-1.6b")
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    step = jax.jit(make_train_step(cfg, AdamWConfig()))
    batch = {"tokens": jnp.zeros((2, 64), jnp.int32),
             "labels": jnp.zeros((2, 64), jnp.int32)}
    state, metrics = step(state, batch)
    print(f"  loss={float(metrics['loss']):.3f} "
          f"grad_norm={float(metrics['grad_norm']):.3f} — "
          "the WKV layer runs core.linear_attn (chunked Squire scan)\n")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.configure()
    demo_scan1d()
    demo_chain()
    demo_dtw()
    demo_sort()
    demo_lm_step()
    print("quickstart: all demos passed")
