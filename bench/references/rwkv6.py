"""Plain RWKV-6 reference for the ``lm`` configurations, and their weights.

Imports nothing of the program. ``init_params`` makes the served weights
from the seed on the device, in one jitted call, in the tree layout the
program's serving path takes; the benchmark hands the same arrays to the
program and to this reference.

``logits`` is a float32 forward at "highest" matmul precision over one
sequence: per layer RMSNorm, the time mix (token shift, r/k/v/g
projections, data-dependent decay exp(-exp(w0 + tanh(x A) B)), the WKV
recurrence S_t = diag(w_t) S_{t-1} + k_t v_t^T read out as
y_t = r_t (S_{t-1} + diag(u) k_t v_t^T), per-head GroupNorm, the gate and
output projection), RMSNorm, the squared-ReLU channel mix; then RMSNorm
and the unembedding. The WKV recurrence runs token by token (lax.scan),
one layer per call, so the reference fits beside nothing else.

The control (``logits(..., int8=True)`` over ``quantize(params)``) is
the step below bf16 that a later change might take, int8 matrix
products: every multiplied weight rounded to int8 with a scale per output
channel, every product's input rounded to int8 with a scale per token.
"""

from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
MATRICES = ("wr", "wk", "wv", "wg", "wo", "w_lora_a", "w_lora_b")
FFN_MATRICES = ("wk", "wv", "wr")


def _shapes(c: dict) -> Dict[str, tuple]:
    d, ff, lora = c["d_model"], c["d_ff"], c["decay_lora"]
    h, hs = d // c["head_size"], c["head_size"]
    return {"d": d, "ff": ff, "lora": lora, "h": h, "hs": hs,
            "L": c["num_layers"], "V": c["vocab"]}


def init_params(c: dict, seed_key: int):
    """Random served weights (bf16) from ``seed_key``, on the device.

    Matrices are He-scaled normals; mixing coefficients uniform in [0, 1];
    the decay base spans RWKV-6's -6 .. -1 ramp and the decay LoRA is
    small, so the log-decay -exp(w0 + tanh(x A) B) stays inside [-1, 0].
    """
    s = _shapes(c)
    d, ff, lora, h, hs, L, V = (s[k] for k in
                                ("d", "ff", "lora", "h", "hs", "L", "V"))

    def make(key):
        ks = iter(jax.random.split(key, 32))

        def normal(shape, std):
            return std * jax.random.normal(next(ks), shape, F32)

        def he(shape, fan_in):
            return normal(shape, (2.0 / fan_in) ** 0.5)

        def unif(shape):
            return jax.random.uniform(next(ks), shape, F32)

        ramp = jnp.arange(d, dtype=F32) / (d - 1)
        tmix = {
            "mu_r": unif((L, d)), "mu_k": unif((L, d)), "mu_v": unif((L, d)),
            "mu_w": unif((L, d)), "mu_g": unif((L, d)),
            "wr": he((L, d, d), d), "wk": he((L, d, d), d),
            "wv": he((L, d, d), d), "wg": he((L, d, d), d),
            "wo": he((L, d, d), d),
            "w0": jnp.broadcast_to(-6.0 + 5.0 * ramp ** 0.7, (L, d))
            + normal((L, d), 0.05) - 0.1,
            "w_lora_a": normal((L, d, lora), 0.02),
            "w_lora_b": normal((L, lora, d), 0.01),
            "u": normal((L, h, hs), 0.5),
            "ln_x": {"scale": 1.0 + normal((L, d), 0.1),
                     "bias": normal((L, d), 0.1)},
        }
        cmix = {"mu_k": unif((L, d)), "mu_r": unif((L, d)),
                "wk": he((L, d, ff), d), "wv": he((L, ff, d), ff),
                "wr": he((L, d, d), d)}
        params = {
            "embed": {"table": normal((V, d), 0.02)},
            "blocks": {"p0": {"norm1": {"scale": 1.0 + normal((L, d), 0.1)},
                              "norm2": {"scale": 1.0 + normal((L, d), 0.1)},
                              "rwkv": tmix, "rwkv_ffn": cmix}},
            "final_norm": {"scale": 1.0 + normal((d,), 0.1)},
            "unembed": {"table": normal((V, d), 0.02)},
        }
        return jax.tree_util.tree_map(lambda x: x.astype(jnp.bfloat16),
                                      params)

    return jax.block_until_ready(jax.jit(make)(jax.random.PRNGKey(seed_key)))


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _shift(x):
    return jnp.concatenate([jnp.zeros_like(x[:1]), x[:-1]], 0)


def _matmul(int8: bool):
    """a @ w, with a rounded to int8 per row (per token) for the control."""
    if not int8:
        return lambda a, w: a @ w
    return lambda a, w: _int8(a, -1) @ w


@functools.partial(jax.jit, static_argnames=("h", "hs", "ln_eps",
                                             "norm_eps", "int8"))
def _layer(p, x, *, h, hs, ln_eps, norm_eps, int8=False):
    """One layer over x (S, d) float32."""
    p = jax.tree_util.tree_map(lambda a: a.astype(F32), p)
    mm = _matmul(int8)
    t, c = p["rwkv"], p["rwkv_ffn"]
    s, d = x.shape
    hx = _rmsnorm(x, p["norm1"]["scale"], norm_eps)
    xs = _shift(hx)

    def mix(mu):
        return hx + (xs - hx) * mu

    r = mm(mix(t["mu_r"]), t["wr"])
    k = mm(mix(t["mu_k"]), t["wk"])
    v = mm(mix(t["mu_v"]), t["wv"])
    g = jax.nn.silu(mm(mix(t["mu_g"]), t["wg"]))
    dd = mm(jnp.tanh(mm(mix(t["mu_w"]), t["w_lora_a"])), t["w_lora_b"])
    w = jnp.exp(-jnp.exp(t["w0"] + dd))
    heads = lambda z: z.reshape(s, h, hs)
    u = t["u"]

    def step(state, rwkv):
        rt, wt, kt, vt = rwkv                       # (h, hs) each
        kv = kt[:, :, None] * vt[:, None, :]        # (h, hs, hs)
        yt = jnp.einsum("hk,hkv->hv", rt, state + u[:, :, None] * kv)
        return wt[:, :, None] * state + kv, yt

    _, y = jax.lax.scan(step, jnp.zeros((h, hs, hs), F32),
                        (heads(r), heads(w), heads(k), heads(v)), unroll=16)
    y = y.reshape(s, h, hs)
    mean = y.mean(-1, keepdims=True)
    var = ((y - mean) ** 2).mean(-1, keepdims=True)
    y = ((y - mean) * jax.lax.rsqrt(var + ln_eps)).reshape(s, d)
    y = y * t["ln_x"]["scale"] + t["ln_x"]["bias"]
    x = x + mm(y * g, t["wo"])

    h2 = _rmsnorm(x, p["norm2"]["scale"], norm_eps)
    xs2 = _shift(h2)
    xk = h2 + (xs2 - h2) * c["mu_k"]
    xr = h2 + (xs2 - h2) * c["mu_r"]
    kk = jnp.square(jax.nn.relu(mm(xk, c["wk"])))
    return x + jax.nn.sigmoid(mm(xr, c["wr"])) * mm(kk, c["wv"])


@functools.partial(jax.jit, static_argnames=("int8",))
def _head(final_scale, table, x, norm_eps, int8=False):
    x = _rmsnorm(x, final_scale.astype(F32), norm_eps)
    return _matmul(int8)(x, table.astype(F32).T)


def logits(params, c: dict, tokens: np.ndarray, rows: np.ndarray,
           int8: bool = False) -> np.ndarray:
    """Float32 logits (len(rows), vocab) of ``tokens`` at positions
    ``rows`` (with ``int8``, every product's input rounded per token, for
    the control). The sequence is padded at its end to a power of two (at
    least 512) so that few lengths compile; a causal model's earlier
    positions cannot see the padding."""
    s = _shapes(c)
    n = len(tokens)
    padded = np.zeros(max(512, 1 << (n - 1).bit_length()), np.int32)
    padded[:n] = tokens
    blocks = params["blocks"]["p0"]
    with jax.default_matmul_precision("highest"):
        x = params["embed"]["table"][jnp.asarray(padded)].astype(F32)
        for layer in range(s["L"]):
            p = jax.tree_util.tree_map(lambda a: a[layer], blocks)
            x = _layer(p, x, h=s["h"], hs=s["hs"], ln_eps=c["ln_x_eps"],
                       norm_eps=c["norm_eps"], int8=int8)
        out = _head(params["final_norm"]["scale"], params["unembed"]["table"],
                    x[jnp.asarray(rows)], c["norm_eps"], int8=int8)
    return np.asarray(out, np.float32)


# --------------------------------------------------------------------------
# the control: int8 weights
# --------------------------------------------------------------------------

def _int8(w, axis):
    """Symmetric int8 rounding with one scale per slice along ``axis``
    (the reduced, input dimension)."""
    scale = jnp.max(jnp.abs(w), axis=axis, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return (jnp.round(w / scale) * scale).astype(w.dtype)


def quantize(params):
    """``params`` with every multiplied weight rounded to per-channel int8
    (matrices over their input axis, the unembedding per row)."""
    t = dict(params["blocks"]["p0"]["rwkv"])
    c = dict(params["blocks"]["p0"]["rwkv_ffn"])
    q = jax.jit(lambda w: _int8(w.astype(F32), -2))
    for name in MATRICES:
        t[name] = q(t[name])
    for name in FFN_MATRICES:
        c[name] = q(c[name])
    blocks = dict(params["blocks"]["p0"], rwkv=t, rwkv_ffn=c)
    table = jax.jit(lambda w: _int8(w.astype(F32), -1))(
        params["unembed"]["table"])
    return dict(params, blocks={"p0": blocks}, unembed={"table": table})


def gaps(ref: np.ndarray, picked: np.ndarray) -> np.ndarray:
    """Per position, how far the picked token's reference logit lies
    below the reference's best."""
    return ref.max(-1) - ref[np.arange(len(picked)), picked]
