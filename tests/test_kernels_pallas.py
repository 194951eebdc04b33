"""Pallas kernel sweeps: every kernel x shapes x dtypes vs the ref.py
pure-jnp oracle, in interpret mode (the brief's per-kernel contract)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import chain as C
from repro.core import dtw as D
from repro.core import align as A
from repro.kernels import ops, ref
from repro.kernels.chain_scan import chain_scan_pallas
from repro.kernels.dtw_wavefront import dp_tile_pallas
from repro.kernels.ssm_scan import ssm_scan_pallas


# --------------------------------------------------------------------------
# ssm_scan (chunked WKV)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("b,t,dk,dv,chunk", [
    (1, 32, 16, 16, 8),
    (2, 64, 32, 16, 16),
    (3, 96, 64, 64, 32),
    (2, 128, 8, 24, 64),
])
def test_ssm_scan_shapes(b, t, dk, dv, chunk):
    ks = jax.random.split(jax.random.PRNGKey(t), 5)
    r = jax.random.normal(ks[0], (b, t, dk))
    w = jax.nn.sigmoid(jax.random.normal(ks[1], (b, t, dk)) + 2.0)
    k = jax.random.normal(ks[2], (b, t, dk))
    v = jax.random.normal(ks[3], (b, t, dv))
    u = 0.1 * jax.random.normal(ks[4], (dk,))
    got = ssm_scan_pallas(r, w, k, v, u, chunk=chunk)
    want = ref.ssm_scan_ref(r, w, k, v, u)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssm_scan_dtypes(dtype):
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    b, t, d = 2, 64, 32
    r = jax.random.normal(ks[0], (b, t, d), dtype)
    w = jax.nn.sigmoid(jax.random.normal(ks[1], (b, t, d), dtype) + 2)
    k = jax.random.normal(ks[2], (b, t, d), dtype)
    v = jax.random.normal(ks[3], (b, t, d), dtype)
    u = jnp.zeros((d,), dtype)
    got = ops.ssm_scan(r, w, k, v, u, chunk=16)
    want = ref.ssm_scan_ref(r, w, k, v, u)
    tol = 1e-4 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)


def test_ssm_scan_t_padding():
    """ops wrapper pads T to the chunk size; result must be unaffected."""
    ks = jax.random.split(jax.random.PRNGKey(1), 5)
    b, t, d = 1, 50, 16       # t=50 not a multiple of 16
    r = jax.random.normal(ks[0], (b, t, d))
    w = jax.nn.sigmoid(jax.random.normal(ks[1], (b, t, d)))
    k = jax.random.normal(ks[2], (b, t, d))
    v = jax.random.normal(ks[3], (b, t, d))
    got = ops.ssm_scan(r, w, k, v, chunk=16)
    want = ref.ssm_scan_ref(r, w, k, v, jnp.zeros((d,)))
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-4)


# --------------------------------------------------------------------------
# chain_scan
# --------------------------------------------------------------------------

@pytest.mark.parametrize("n,t,block", [
    (256, 128, 256),
    (512, 128, 256),
    (1024, 128, 512),
])
def test_chain_scan_vs_core(n, t, block):
    rng = np.random.default_rng(n)
    scores = rng.normal(size=(n, t)).astype(np.float32)
    scores[rng.random((n, t)) < 0.5] = -1e18
    # ban forward references (j >= i): mask t >= i
    for i in range(min(n, t)):
        scores[i, i:] = -1e18
    w = np.full((n,), 15.0, np.float32)
    f_pal, off_pal = chain_scan_pallas(jnp.asarray(scores), jnp.asarray(w),
                                       block=block)
    f_ref, off_ref = C.chain_sequential(jnp.asarray(scores), jnp.asarray(w))
    np.testing.assert_allclose(np.asarray(f_pal), np.asarray(f_ref),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(off_pal), np.asarray(off_ref))


def test_chain_scan_ops_band_padding():
    """ops.chain_scan pads T<128 bands to 128 lanes; exactness preserved."""
    q, r = np.arange(300) * 10, np.arange(300) * 10
    f_core, p_core = C.chain_anchors(jnp.asarray(q), jnp.asarray(r), T=64,
                                     mode="sequential")
    f_pal, p_pal = ops.chain_anchors(jnp.asarray(q), jnp.asarray(r), T=64)
    np.testing.assert_allclose(np.asarray(f_pal), np.asarray(f_core),
                               rtol=1e-5, atol=1e-4)
    np.testing.assert_array_equal(np.asarray(p_pal), np.asarray(p_core))


# --------------------------------------------------------------------------
# dp tile (DTW / SW)
# --------------------------------------------------------------------------

@pytest.mark.parametrize("tr,tc", [(8, 8), (16, 16), (32, 16), (16, 32)])
def test_dp_tile_dtw_vs_jnp_tile(tr, tc):
    ks = jax.random.split(jax.random.PRNGKey(tr * 100 + tc), 5)
    top = jax.random.normal(ks[0], (tc,))
    left = jax.random.normal(ks[1], (tr,))
    corner = jax.random.normal(ks[2], ())
    a = jax.random.normal(ks[3], (tr,))
    b = jax.random.normal(ks[4], (tc,))
    tile, bot, right, c_out = ops.dp_tile(top, left, corner, a, b,
                                          kind="dtw")
    from repro.core.wavefront import dp_tile_diagonal
    from repro.core.dtw import _cell
    want, wb, wr, wc = dp_tile_diagonal(_cell, top, left, corner, a, b)
    np.testing.assert_allclose(tile, want, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(bot, wb, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(right, wr, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("n,m", [(32, 32), (64, 96)])
def test_dtw_tiled_pallas_end_to_end(n, m):
    ks = jax.random.split(jax.random.PRNGKey(n), 2)
    s = jax.random.normal(ks[0], (n,))
    r = jax.random.normal(ks[1], (m,))
    want = D.dtw_ref(s, r)
    mat, dist = ops.dtw_tiled(s, r, tile_r=32, tile_c=32)
    np.testing.assert_allclose(mat, want, rtol=1e-5, atol=1e-4)


def test_sw_tiled_pallas_end_to_end():
    rng = np.random.default_rng(7)
    a = rng.integers(0, 4, 48).astype(np.int32)
    b = rng.integers(0, 4, 64).astype(np.int32)
    want = A.sw_ref(jnp.asarray(a), jnp.asarray(b))
    mat, best = ops.sw_tiled(jnp.asarray(a), jnp.asarray(b),
                             tile_r=16, tile_c=16)
    np.testing.assert_allclose(mat, want, rtol=1e-5, atol=1e-4)
    np.testing.assert_allclose(best, np.asarray(want).max(), atol=1e-4)


# --------------------------------------------------------------------------
# radix rank kernel
# --------------------------------------------------------------------------

@pytest.mark.parametrize("shift", [0, 8, 16, 24])
def test_radix_rank_vs_oracle(shift):
    from repro.kernels.radix_rank import radix_rank_pallas
    rng = np.random.default_rng(shift)
    keys = rng.integers(0, 2**32, (3, 512), dtype=np.uint32)
    ranks, hists = radix_rank_pallas(jnp.asarray(keys), shift=shift,
                                     block=256)
    for c in range(3):
        bucket = (keys[c] >> shift) & 255
        want = np.zeros(512, np.int32)
        cnt: dict = {}
        for i, bkt in enumerate(bucket):
            want[i] = cnt.get(bkt, 0)
            cnt[bkt] = cnt.get(bkt, 0) + 1
        np.testing.assert_array_equal(np.asarray(ranks)[c], want)
        np.testing.assert_array_equal(np.asarray(hists)[c],
                                      np.bincount(bucket, minlength=256))


def test_radix_sort_chunks_full_pipeline():
    rng = np.random.default_rng(1)
    keys = rng.integers(0, 2**32, (4, 512), dtype=np.uint32)
    sk, sv = ops.radix_sort_chunks(jnp.asarray(keys), block=256)
    sk = np.asarray(sk)
    for c in range(4):
        np.testing.assert_array_equal(sk[c], np.sort(keys[c]))
    # values permuted consistently (stable)
    sv = np.asarray(sv)
    for c in range(4):
        np.testing.assert_array_equal(keys[c][sv[c]], sk[c])


@pytest.mark.parametrize("n,chunks", [(3, 8), (300, 1), (1000, 4)])
def test_radix_sort_kernel_path(n, chunks, kernel_stages):
    """core.sort's TPU branch (chunks padded to the 128-lane key block and
    sorted by the rank kernel) is the stable sort, ties and pads included."""
    from repro.core import sort as R
    rng = np.random.default_rng(n)
    keys = rng.integers(0, 64, n, dtype=np.uint32)
    keys[::7] = 0xFFFFFFFF                  # real keys equal to the pad key
    vals = rng.integers(0, 2**31, n).astype(np.int32)
    sk, sv = R.radix_sort(jnp.asarray(keys), jnp.asarray(vals),
                          num_chunks=chunks, min_parallel=0)
    order = np.argsort(keys, kind="stable")
    np.testing.assert_array_equal(np.asarray(sk), keys[order])
    np.testing.assert_array_equal(np.asarray(sv), vals[order])


# --------------------------------------------------------------------------
# flash attention
# --------------------------------------------------------------------------

def _naive_attn(q, k, v, window=0):
    b, h, sq, hd = q.shape
    grp = h // k.shape[1]
    kf = jnp.repeat(k, grp, axis=1)
    vf = jnp.repeat(v, grp, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                   kf.astype(jnp.float32)) * hd ** -0.5
    qp = jnp.arange(sq)[:, None]
    kp = jnp.arange(k.shape[2])[None, :]
    ok = kp <= qp
    if window:
        ok &= (qp - kp) < window
    p = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, vf.astype(jnp.float32))


@pytest.mark.parametrize("b,h,kvh,sq,hd,win,bq,bk", [
    (2, 4, 4, 128, 64, 0, 64, 64),       # MHA
    (1, 8, 2, 256, 32, 0, 128, 128),     # GQA 4:1
    (1, 4, 1, 256, 64, 0, 64, 128),      # MQA
    (1, 4, 2, 256, 64, 96, 64, 64),      # sliding window (gemma3 local)
])
def test_flash_attention_sweep(b, h, kvh, sq, hd, win, bq, bk):
    from repro.kernels.flash_attention import flash_attention_pallas
    ks = jax.random.split(jax.random.PRNGKey(sq + win), 3)
    q = jax.random.normal(ks[0], (b, h, sq, hd))
    k = jax.random.normal(ks[1], (b, kvh, sq, hd))
    v = jax.random.normal(ks[2], (b, kvh, sq, hd))
    out = flash_attention_pallas(q, k, v, window=win, bq=bq, bk=bk)
    want = _naive_attn(q, k, v, win)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_dtypes(dtype):
    from repro.kernels.flash_attention import flash_attention_pallas
    ks = jax.random.split(jax.random.PRNGKey(9), 3)
    q = jax.random.normal(ks[0], (1, 2, 128, 64), dtype)
    k = jax.random.normal(ks[1], (1, 2, 128, 64), dtype)
    v = jax.random.normal(ks[2], (1, 2, 128, 64), dtype)
    out = flash_attention_pallas(q, k, v)
    assert out.dtype == dtype
    want = _naive_attn(q, k, v)
    tol = 2e-3 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want), rtol=tol, atol=tol)


def test_sw_tile_scoring_params():
    rng = np.random.default_rng(8)
    a = rng.integers(0, 4, 16).astype(np.int32)
    b = rng.integers(0, 4, 16).astype(np.int32)
    p = A.SWParams(match=3.0, mismatch=-2.0, gap=1.5)
    want = A.sw_ref(jnp.asarray(a), jnp.asarray(b), p)
    fn = ops.make_sw_tile_fn(p.match, p.mismatch, p.gap)
    mat, best = A.sw_tiled(jnp.asarray(a), jnp.asarray(b), p,
                           tile_r=8, tile_c=8, tile_fn=fn)
    np.testing.assert_allclose(mat, want, rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("backend,interpret", [("cpu", True), ("tpu", False),
                                               ("gpu", None)])
def test_kernel_mode_from_platform(monkeypatch, backend, interpret):
    """Compiled on a TPU, interpreted on the CPU, and no mode at all
    elsewhere: an unknown backend raises instead of falling back."""
    from repro.kernels import mode
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    if interpret is None:
        with pytest.raises(RuntimeError, match="no Pallas kernel mode"):
            mode.interpret()
        with pytest.raises(RuntimeError):
            mode.use_kernels()
    else:
        assert mode.interpret() is interpret
        assert mode.use_kernels() is not interpret
