"""RWKV6 (Finch) and Mamba blocks built on core.linear_attn.

Both are the LM-scale instances of the paper's 1-D dependency-bound pattern
(DESIGN.md §3.1): training/prefill runs the chunk-parallel path
(`wkv_chunked` / `mamba_chunked` — Squire's worker partitioning), decode
runs the O(1)-state single-step path. The recurrent state *is* the cache:
a 524k context costs the same per token as a 1k context (`long_500k`).

RWKV6 here implements the structural essentials of Finch: static token-
shift mixing vectors plus the headline *data-dependent decay* (a low-rank
MLP modulating w per token/channel), multi-head (dk = dv = 64) WKV with the
current-token bonus `u`, per-head groupnorm, and the squared-ReLU channel
mix. Mamba follows mamba-1: in/gate projections, depthwise causal conv,
selective (dt, B, C) projections, diagonal state update.
"""

from __future__ import annotations

import functools

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import linear_attn as la
from repro.models import layers as L
from repro.sharding import shard_act

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# RWKV6 time mix
# ---------------------------------------------------------------------------

# RWKV-6's ln_x: GroupNorm eps = 1e-5 * head_size_divisor**2 (divisor 8).
# A head whose WKV readout is near rank-1 (the first positions) has a tiny
# variance; the larger eps keeps its normalisation continuous instead of
# amplifying rounding noise into a sign flip.
LN_X_EPS = 64e-5


class RWKVConfig(NamedTuple):
    d_model: int
    head_dim: int = 64
    decay_lora: int = 64
    scan_chunk: int = 64

    @property
    def num_heads(self) -> int:
        return self.d_model // self.head_dim


def init_rwkv_time_mix(key, cfg: RWKVConfig):
    d, h, hd = cfg.d_model, cfg.num_heads, cfg.head_dim
    ks = jax.random.split(key, 8)
    ramp = jnp.arange(d, dtype=jnp.float32) / d
    p = {
        # token-shift mixing coefficients (static lerp weights)
        "mu_r": 0.5 * (1 + ramp), "mu_k": 0.7 * (1 + ramp) / 2,
        "mu_v": 0.7 * (1 + ramp) / 2, "mu_w": 0.6 * (1 + ramp) / 2,
        "mu_g": 0.5 * (1 + ramp),
        "wr": L.he_init(ks[0], (d, d), d),
        "wk": L.he_init(ks[1], (d, d), d),
        "wv": L.he_init(ks[2], (d, d), d),
        "wg": L.he_init(ks[3], (d, d), d),
        "wo": L.he_init(ks[4], (d, d), d),
        # data-dependent decay: w = exp(-exp(w0 + tanh(x A) B))
        "w0": -6.0 + 5.0 * ramp,                         # decay base
        "w_lora_a": L.truncated_normal(ks[5], (d, cfg.decay_lora), 0.02),
        "w_lora_b": jnp.zeros((cfg.decay_lora, d), jnp.float32),
        "u": L.truncated_normal(ks[6], (h, hd), 0.5),    # bonus
        "ln_x": L.init_groupnorm(d),                     # per-head norm
    }
    return p


def _token_shift(x: Array, x_prev: Optional[Array]) -> Array:
    """shifted[t] = x[t-1]; slot -1 comes from the decode state (or zeros)."""
    if x_prev is None:
        x_prev = jnp.zeros_like(x[:, :1])
    else:
        x_prev = x_prev[:, None] if x_prev.ndim == 2 else x_prev
    return jnp.concatenate([x_prev.astype(x.dtype), x[:, :-1]], axis=1)


def rwkv_time_mix(params, cfg: RWKVConfig, x: Array,
                  state: Optional[dict] = None, chunk: Optional[int] = None):
    """x: (B, S, D). state (decode/prefill-continuation) holds
    {"s": (B, H, hd, hd) fp32, "x_prev": (B, D)}. Returns (y, new_state).
    """
    b, s, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    dt = x.dtype
    x_prev = state["x_prev"] if state is not None else None
    xs = _token_shift(x, x_prev)

    def mix(mu):
        return x + (xs - x) * mu.astype(dt)

    r = mix(params["mu_r"]) @ params["wr"].astype(dt)
    k = mix(params["mu_k"]) @ params["wk"].astype(dt)
    v = mix(params["mu_v"]) @ params["wv"].astype(dt)
    g = jax.nn.silu(mix(params["mu_g"]) @ params["wg"].astype(dt))
    # data-dependent decay (the Finch feature)
    xw = mix(params["mu_w"]).astype(jnp.float32)
    dd = jnp.tanh(xw @ params["w_lora_a"]) @ params["w_lora_b"]
    w = jnp.exp(-jnp.exp(params["w0"] + dd))             # (B, S, D) in (0,1)

    # Layout choice (§Perf rwkv6 iterations 3/5, measured both ways):
    #  * fold (b*h) when b % n_devices == 0 — each device owns whole batch
    #    rows; the flat layout lets XLA fuse the chunked scan best
    #    (train_4k: collective 1421 -> 811 ms).
    #  * otherwise keep heads a REAL axis and vmap the scan over them —
    #    the misaligned fold makes GSPMD all-gather full fp32 tensors
    #    (prefill_32k with b=32: 689 GB/device, 30x regression).
    from repro.sharding import current_mesh
    mesh = current_mesh()
    n_dev = 1 if mesh is None or mesh.empty else mesh.devices.size
    use_fold = (b % max(n_dev, 1)) == 0
    s0 = state["s"] if state is not None else None       # (b, h, hd, hd)

    def to_heads(z):
        return z.reshape(b, s, h, hd).transpose(0, 2, 1, 3)

    if use_fold:
        def fold(z):
            return to_heads(z).reshape(b * h, s, hd)

        rf, wf, kf, vf = map(fold, (r, w, k, v))
        shard_fold = lambda z: shard_act(z, "ssm_fold", None, None)
        rf, wf, kf, vf = map(shard_fold, (rf, wf, kf, vf))
        s0f = s0.reshape(b * h, hd, hd) if s0 is not None else None
        yf, s_fin = la.wkv_chunked(rf, wf, kf, vf, None, s0f,
                                   chunk=chunk or cfg.scan_chunk,
                                   out_dtype=dt)
        yf = shard_fold(yf)
        uf = jnp.broadcast_to(params["u"][None], (b, h, hd))             .reshape(b * h, hd)
        bonus = jnp.einsum("btk,bk,btk->bt", rf.astype(jnp.float32),
                           uf, kf.astype(jnp.float32))
        yf = yf + bonus[..., None] * vf.astype(jnp.float32)
        yf = yf.reshape(b, h, s, hd)
        s_fin = s_fin.reshape(b, h, hd, hd)
    else:
        # misaligned fold: leave layout to GSPMD (no constraint) — measured
        # better than both the constrained fold (30x gathers) and a
        # vmap-over-heads form (2x) on prefill_32k / multi-pod trains.
        def fold(z):
            return to_heads(z).reshape(b * h, s, hd)

        rf, wf, kf, vf = map(fold, (r, w, k, v))
        s0f = s0.reshape(b * h, hd, hd) if s0 is not None else None
        yf, s_fin = la.wkv_chunked(rf, wf, kf, vf, None, s0f,
                                   chunk=chunk or cfg.scan_chunk,
                                   out_dtype=dt)
        uf = jnp.broadcast_to(params["u"][None], (b, h, hd)) \
            .reshape(b * h, hd)
        bonus = jnp.einsum("btk,bk,btk->bt", rf.astype(jnp.float32),
                           uf, kf.astype(jnp.float32))
        yf = yf + bonus[..., None] * vf.astype(jnp.float32)
        yf = yf.reshape(b, h, s, hd)
        s_fin = s_fin.reshape(b, h, hd, hd)

    y = yf.transpose(0, 2, 1, 3).reshape(b, s, d)
    y = shard_act(y, "batch", "seq", None)
    y = L.groupnorm(params["ln_x"], y.astype(dt), groups=h,
                    eps=LN_X_EPS)
    y = (y * g) @ params["wo"].astype(dt)
    new_state = {"s": s_fin,
                 "x_prev": x[:, -1].astype(jnp.float32)}
    return y, new_state


def rwkv_time_mix_decode(params, cfg: RWKVConfig, x: Array, state: dict):
    """Single-token decode: x (B, 1, D). O(1) in context length."""
    b, _, d = x.shape
    h, hd = cfg.num_heads, cfg.head_dim
    dt = x.dtype
    xs = state["x_prev"][:, None].astype(dt)

    def mix(mu):
        return x + (xs - x) * mu.astype(dt)

    r = (mix(params["mu_r"]) @ params["wr"].astype(dt))[:, 0]
    k = (mix(params["mu_k"]) @ params["wk"].astype(dt))[:, 0]
    v = (mix(params["mu_v"]) @ params["wv"].astype(dt))[:, 0]
    g = jax.nn.silu(mix(params["mu_g"]) @ params["wg"].astype(dt))[:, 0]
    xw = mix(params["mu_w"]).astype(jnp.float32)[:, 0]
    dd = jnp.tanh(xw @ params["w_lora_a"]) @ params["w_lora_b"]
    w = jnp.exp(-jnp.exp(params["w0"] + dd))             # (B, D)

    fold = lambda z: z.reshape(b * h, hd)
    s0 = state["s"].reshape(b * h, hd, hd)
    yf, s_fin = la.wkv_decode_step(fold(r), fold(w), fold(k), fold(v),
                                   None, s0)
    uf = jnp.broadcast_to(params["u"][None], (b, h, hd)).reshape(b * h, hd)
    bonus = jnp.einsum("bk,bk,bk->b", fold(r).astype(jnp.float32), uf,
                       fold(k).astype(jnp.float32))
    yf = yf + bonus[:, None] * fold(v).astype(jnp.float32)

    y = yf.reshape(b, h * hd)[:, None, :]
    y = L.groupnorm(params["ln_x"], y.astype(dt), groups=h,
                    eps=LN_X_EPS)
    y = (y * g[:, None]) @ params["wo"].astype(dt)
    new_state = {"s": s_fin.reshape(b, h, hd, hd),
                 "x_prev": x[:, -1].astype(jnp.float32)}
    return y, new_state


def init_rwkv_state(batch: int, cfg: RWKVConfig):
    h, hd = cfg.num_heads, cfg.head_dim
    return {"s": jnp.zeros((batch, h, hd, hd), jnp.float32),
            "x_prev": jnp.zeros((batch, cfg.d_model), jnp.float32)}


# ---------------------------------------------------------------------------
# RWKV channel mix (the arch's FFN; uses token shift too)
# ---------------------------------------------------------------------------

def init_rwkv_channel_mix(key, d_model: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    ramp = jnp.arange(d_model, dtype=jnp.float32) / d_model
    return {
        "mu_k": 0.5 * (1 + ramp), "mu_r": 0.5 * (1 + ramp),
        "wk": L.he_init(k1, (d_model, d_ff), d_model),
        "wv": L.he_init(k2, (d_ff, d_model), d_ff),
        "wr": L.he_init(k3, (d_model, d_model), d_model),
    }


def rwkv_channel_mix(params, x: Array, x_prev: Optional[Array] = None):
    """Squared-ReLU channel mix. Returns (y, x_last) for the decode shift."""
    dt = x.dtype
    xs = _token_shift(x, x_prev)
    xk = x + (xs - x) * params["mu_k"].astype(dt)
    xr = x + (xs - x) * params["mu_r"].astype(dt)
    kk = jnp.square(jax.nn.relu(xk @ params["wk"].astype(dt)))
    y = jax.nn.sigmoid(xr @ params["wr"].astype(dt)) * \
        (kk @ params["wv"].astype(dt))
    return y, x[:, -1].astype(jnp.float32)


# ---------------------------------------------------------------------------
# Mamba (S6) block
# ---------------------------------------------------------------------------

class MambaConfig(NamedTuple):
    d_model: int
    d_state: int = 16
    expand: int = 2
    conv_kernel: int = 4
    scan_chunk: int = 64

    @property
    def d_inner(self) -> int:
        return self.expand * self.d_model

    @property
    def dt_rank(self) -> int:
        return max(1, -(-self.d_model // 16))


def init_mamba(key, cfg: MambaConfig):
    d, di, n, r = cfg.d_model, cfg.d_inner, cfg.d_state, cfg.dt_rank
    ks = jax.random.split(key, 6)
    # S4D-real init for A; dt bias init for softplus ~ [1e-3, 1e-1]
    a = jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None], (di, 1))
    dt_init = jnp.exp(jax.random.uniform(ks[4], (di,), jnp.float32)
                      * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    inv_softplus = dt_init + jnp.log(-jnp.expm1(-dt_init))
    return {
        "w_in": L.he_init(ks[0], (d, 2 * di), d),
        "conv_w": L.truncated_normal(ks[1], (cfg.conv_kernel, di), 0.2),
        "conv_b": jnp.zeros((di,), jnp.float32),
        "w_x": L.he_init(ks[2], (di, r + 2 * n), di),
        "w_dt": L.he_init(ks[3], (r, di), r),
        "dt_bias": inv_softplus,
        "a_log": jnp.log(a),
        "d_skip": jnp.ones((di,), jnp.float32),
        "w_out": L.he_init(ks[5], (di, d), di),
    }


def _causal_conv(x: Array, w: Array, b: Array,
                 conv_state: Optional[Array] = None):
    """Depthwise causal conv along time. x: (B, S, di); w: (K, di).

    Returns (y: (B, S, di), new_conv_state: (B, K-1, di))."""
    kk = w.shape[0]
    if conv_state is None:
        conv_state = jnp.zeros((x.shape[0], kk - 1, x.shape[-1]), x.dtype)
    xp = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)
    y = sum(xp[:, i:i + x.shape[1]] * w[i].astype(x.dtype)
            for i in range(kk))
    new_state = xp[:, -(kk - 1):].astype(jnp.float32)
    return y + b.astype(x.dtype), new_state


def mamba_block(params, cfg: MambaConfig, x: Array,
                state: Optional[dict] = None, chunk: Optional[int] = None):
    """x: (B, S, D). state = {"conv": (B, K-1, di), "h": (B, di, n)}.
    Returns (y (B, S, D), new_state)."""
    b, s, d = x.shape
    di, n, r = cfg.d_inner, cfg.d_state, cfg.dt_rank
    dt_ = x.dtype

    xz = x @ params["w_in"].astype(dt_)
    xi, z = xz[..., :di], xz[..., di:]
    conv_state = state["conv"] if state is not None else None
    xi, new_conv = _causal_conv(xi, params["conv_w"], params["conv_b"],
                                conv_state)
    xi = jax.nn.silu(xi)
    xi = shard_act(xi, "batch", "seq", "ssm_channels")

    proj = xi @ params["w_x"].astype(dt_)
    dt_low, b_in, c_in = proj[..., :r], proj[..., r:r + n], proj[..., r + n:]
    dt = jax.nn.softplus(dt_low.astype(jnp.float32) @ params["w_dt"]
                         + params["dt_bias"])
    a = -jnp.exp(params["a_log"])

    h0 = state["h"] if state is not None else None
    y, h_fin = la.mamba_chunked(xi, dt, a, b_in, c_in, params["d_skip"],
                                h0, chunk=chunk or cfg.scan_chunk)
    y = (y.astype(dt_) * jax.nn.silu(z)) @ params["w_out"].astype(dt_)
    new_state = {"conv": new_conv, "h": h_fin}
    return y, new_state


def mamba_block_decode(params, cfg: MambaConfig, x: Array, state: dict):
    """Single-token decode: x (B, 1, D)."""
    b, _, d = x.shape
    di, n, r = cfg.d_inner, cfg.d_state, cfg.dt_rank
    dt_ = x.dtype

    xz = (x @ params["w_in"].astype(dt_))[:, 0]
    xi, z = xz[..., :di], xz[..., di:]
    # conv ring update
    conv = state["conv"]                                  # (B, K-1, di)
    window = jnp.concatenate([conv.astype(dt_), xi[:, None]], axis=1)
    y = jnp.einsum("bkd,kd->bd", window, params["conv_w"].astype(dt_)) \
        + params["conv_b"].astype(dt_)
    new_conv = window[:, 1:].astype(jnp.float32)
    xi = jax.nn.silu(y)

    proj = xi @ params["w_x"].astype(dt_)
    dt_low, b_in, c_in = proj[..., :r], proj[..., r:r + n], proj[..., r + n:]
    dt = jax.nn.softplus(dt_low.astype(jnp.float32) @ params["w_dt"]
                         + params["dt_bias"])
    a = -jnp.exp(params["a_log"])
    yd, h = la.mamba_decode_step(xi, dt, a, b_in, c_in, params["d_skip"],
                                 state["h"])
    out = (yd.astype(dt_) * jax.nn.silu(z)) @ params["w_out"].astype(dt_)
    return out[:, None], {"conv": new_conv, "h": h}


def init_mamba_state(batch: int, cfg: MambaConfig):
    return {"conv": jnp.zeros((batch, cfg.conv_kernel - 1, cfg.d_inner),
                              jnp.float32),
            "h": jnp.zeros((batch, cfg.d_inner, cfg.d_state), jnp.float32)}
