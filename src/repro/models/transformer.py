"""Composable decoder covering all 10 assigned architectures.

The stack is a *period pattern* of LayerSpecs (configs.base) repeated
``num_periods`` times. The runtime `lax.scan`s over periods with stacked
per-period parameters, so HLO size and compile time are flat in depth
(16-60 layer models share one block program), and XLA's latency-hiding
scheduler can overlap the per-period FSDP all-gathers with compute.

Modes:
  * train    — full-sequence forward, returns (logits, aux_loss).
  * prefill  — full-sequence forward, returns (last-token logits, caches).
  * decode   — single-token step with caches, returns (logits, caches).

Caches are a dict keyed by pattern position (``p0``...), each leaf stacked
over periods — attention layers hold KVCache ring buffers, RWKV/Mamba
layers hold O(1) recurrent state (which is why `long_500k` decode is flat
in context length for the SSM/hybrid archs; DESIGN.md §3.1).
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import LayerSpec, ModelConfig
from repro.models import attention, layers as L, moe as moe_lib, ssm
from repro.sharding import shard_act

Array = jnp.ndarray


# ---------------------------------------------------------------------------
# sub-config adapters
# ---------------------------------------------------------------------------

def _attn_cfg(cfg: ModelConfig, spec: LayerSpec) -> attention.AttnConfig:
    return attention.AttnConfig(
        d_model=cfg.d_model, num_heads=cfg.num_heads,
        num_kv_heads=cfg.num_kv_heads, head_dim=cfg.head_dim,
        bias=cfg.qkv_bias, qk_norm=cfg.qk_norm, rope_theta=spec.rope_theta,
        window=spec.window, kv_block=cfg.kv_block)


def _moe_cfg(cfg: ModelConfig) -> moe_lib.MoEConfig:
    return moe_lib.MoEConfig(
        d_model=cfg.d_model, d_ff=cfg.moe_d_ff or cfg.d_ff,
        num_experts=cfg.num_experts,
        experts_per_token=cfg.experts_per_token,
        capacity_factor=cfg.capacity_factor, act=cfg.act)


def _rwkv_cfg(cfg: ModelConfig) -> ssm.RWKVConfig:
    return ssm.RWKVConfig(d_model=cfg.d_model, head_dim=cfg.rwkv_head_dim,
                          scan_chunk=cfg.scan_chunk)


def _mamba_cfg(cfg: ModelConfig) -> ssm.MambaConfig:
    return ssm.MambaConfig(d_model=cfg.d_model, d_state=cfg.ssm_state,
                           expand=cfg.ssm_expand,
                           scan_chunk=cfg.scan_chunk)


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------

def _init_layer(key, cfg: ModelConfig, spec: LayerSpec):
    k1, k2, k3, k4 = jax.random.split(key, 4)
    d = cfg.d_model
    p: Dict[str, Any] = {"norm1": L.init_rmsnorm(d),
                         "norm2": L.init_rmsnorm(d)}
    if spec.mixer == "attn":
        p["attn"] = attention.init_attention(k1, _attn_cfg(cfg, spec))
    elif spec.mixer == "rwkv":
        p["rwkv"] = ssm.init_rwkv_time_mix(k1, _rwkv_cfg(cfg))
    elif spec.mixer == "mamba":
        p["mamba"] = ssm.init_mamba(k1, _mamba_cfg(cfg))
    else:
        raise ValueError(spec.mixer)
    if spec.mlp == "dense":
        p["mlp"] = L.init_mlp(k2, d, cfg.d_ff)
    elif spec.mlp == "moe":
        p["moe"] = moe_lib.init_moe(k2, _moe_cfg(cfg))
    elif spec.mlp == "rwkv_ffn":
        p["rwkv_ffn"] = ssm.init_rwkv_channel_mix(k2, d, cfg.d_ff)
    else:
        raise ValueError(spec.mlp)
    return p


def init_model(key, cfg: ModelConfig):
    keys = jax.random.split(key, len(cfg.pattern) + 3)
    params: Dict[str, Any] = {}
    if cfg.input_mode == "tokens":
        params["embed"] = L.init_embedding(keys[-1], cfg.vocab, cfg.d_model)
    blocks = {}
    for i, spec in enumerate(cfg.pattern):
        pk = jax.random.split(keys[i], cfg.num_periods)
        blocks[f"p{i}"] = jax.vmap(
            lambda k, s=spec: _init_layer(k, cfg, s))(pk)
    params["blocks"] = blocks
    params["final_norm"] = L.init_rmsnorm(cfg.d_model)
    if not (cfg.tie_embeddings and cfg.input_mode == "tokens"):
        params["unembed"] = L.init_unembed(keys[-2], cfg.vocab, cfg.d_model)
    return params


def param_count(params) -> int:
    return int(sum(x.size for x in jax.tree_util.tree_leaves(params)))


def active_param_count(params, cfg: ModelConfig) -> int:
    """6*N_active*D accounting for MoE: experts count at k/E of their size."""
    total = 0
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        p = "/".join(str(k) for k in path)
        n = leaf.size
        if "expert_" in p and cfg.num_experts:
            n = n * cfg.experts_per_token // cfg.num_experts
        total += n
    return int(total)


# ---------------------------------------------------------------------------
# one layer
# ---------------------------------------------------------------------------

def _apply_layer(p, cfg: ModelConfig, spec: LayerSpec, x: Array,
                 positions: Array, cache, mode: str,
                 pos_scalar: Optional[Array], cache_slots: int):
    new_cache: Optional[Dict[str, Any]] = None
    h = L.rmsnorm(p["norm1"], x)
    if spec.mixer == "attn":
        acfg = _attn_cfg(cfg, spec)
        if mode == "decode":
            y, kvc = attention.attention(p["attn"], acfg, h, positions,
                                         cache=cache["attn"],
                                         position_scalar=pos_scalar)
            new_cache = {"attn": kvc}
        else:
            slots = None
            if mode == "prefill":
                slots = min(cache_slots, spec.window) if spec.window \
                    else cache_slots
            y, kvc = attention.attention(p["attn"], acfg, h, positions,
                                         make_cache_slots=slots)
            if kvc is not None:
                new_cache = {"attn": kvc}
    elif spec.mixer == "rwkv":
        rcfg = _rwkv_cfg(cfg)
        if mode == "decode":
            if h.shape[1] == 1:
                y, st = ssm.rwkv_time_mix_decode(p["rwkv"], rcfg, h,
                                                 cache["rwkv"])
            else:       # chunked prefill: state-carried chunk-parallel scan
                y, st = ssm.rwkv_time_mix(p["rwkv"], rcfg, h,
                                          cache["rwkv"])
        else:
            y, st = ssm.rwkv_time_mix(p["rwkv"], rcfg, h, None)
        new_cache = {"rwkv": st}
    elif spec.mixer == "mamba":
        mcfg = _mamba_cfg(cfg)
        if mode == "decode":
            if h.shape[1] == 1:
                y, st = ssm.mamba_block_decode(p["mamba"], mcfg, h,
                                               cache["mamba"])
            else:       # chunked prefill continuation
                y, st = ssm.mamba_block(p["mamba"], mcfg, h,
                                        cache["mamba"])
        else:
            y, st = ssm.mamba_block(p["mamba"], mcfg, h, None)
        new_cache = {"mamba": st}
    else:
        raise ValueError(spec.mixer)
    x = x + y
    x = shard_act(x, "batch", "seq", None)

    h2 = L.rmsnorm(p["norm2"], x)
    aux = jnp.zeros((), jnp.float32)
    if spec.mlp == "dense":
        y2 = L.mlp(p["mlp"], h2, act=cfg.act)
    elif spec.mlp == "moe":
        y2, aux = moe_lib.moe(p["moe"], _moe_cfg(cfg), h2)
    elif spec.mlp == "rwkv_ffn":
        x_prev = cache.get("ffn_x") if (cache and mode == "decode") else None
        y2, ffn_x = ssm.rwkv_channel_mix(p["rwkv_ffn"], h2, x_prev)
        if new_cache is None:
            new_cache = {}
        new_cache["ffn_x"] = ffn_x
    else:
        raise ValueError(spec.mlp)
    x = x + y2
    x = shard_act(x, "batch", "seq", None)
    return x, new_cache, aux


# ---------------------------------------------------------------------------
# full model
# ---------------------------------------------------------------------------

def apply_model(params, cfg: ModelConfig, *, tokens: Optional[Array] = None,
                embeds: Optional[Array] = None,
                positions: Optional[Array] = None,
                caches=None, mode: str = "train",
                pos_scalar: Optional[Array] = None,
                cache_slots: int = 0):
    """Returns (logits, aux_loss, new_caches_or_None)."""
    assert mode in ("train", "prefill", "decode"), mode
    dt = cfg.dtype
    if embeds is not None:
        x = embeds.astype(dt)
    else:
        x = L.embed(params["embed"], tokens, dt)
        if cfg.scale_embed:
            x = x * jnp.asarray(math.sqrt(cfg.d_model), dt)
    b, s, _ = x.shape
    x = shard_act(x, "batch", "seq", None)

    if positions is None:
        if mode == "decode":
            # pos_scalar: scalar (shared clock) or (B,) vector — per-row
            # clocks for continuous batching; x may carry a chunk (S >= 1)
            # of consecutive tokens starting at that position per row.
            p0 = jnp.asarray(pos_scalar, jnp.int32)
            if p0.ndim == 0:
                p0 = jnp.broadcast_to(p0, (b,))
            positions = p0[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
        else:
            positions = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32)[None],
                                         (b, s))

    pattern = cfg.pattern
    want_caches = mode != "train"

    def body(xc, xs_):
        bp, cache_p = xs_
        aux_t = jnp.zeros((), jnp.float32)
        new_caches = {}
        for i, spec in enumerate(pattern):
            ci = cache_p[f"p{i}"] if cache_p is not None else None
            xc, nc, aux = _apply_layer(bp[f"p{i}"], cfg, spec, xc, positions,
                                       ci, mode, pos_scalar, cache_slots)
            if want_caches:
                new_caches[f"p{i}"] = nc
            aux_t = aux_t + aux
        ys = {"aux": aux_t}
        if want_caches:
            ys["caches"] = new_caches
        return xc, ys

    if cfg.remat and mode == "train":
        if cfg.remat_policy == "dots":
            # recompute elementwise chains, keep MXU dot outputs — trades
            # residency for recompute bytes (§Perf rwkv6 iteration 4)
            body = jax.checkpoint(
                body, policy=jax.checkpoint_policies.checkpoint_dots)
        else:
            body = jax.checkpoint(body)

    x, ys = jax.lax.scan(body, x, (params["blocks"], caches))
    aux_loss = jnp.sum(ys["aux"])
    new_caches = ys.get("caches")

    x = L.rmsnorm(params["final_norm"], x)
    if mode == "prefill":
        x = x[:, -1:]       # prefill callers only consume the last logits
    # decode chunks (s > 1) keep ALL s positions: the unembed over the
    # full chunk is what speculative verify and prompt scoring consume —
    # the compute already happened, this only sizes the output.
    if cfg.tie_embeddings and cfg.input_mode == "tokens":
        table = params["embed"]["table"]
    else:
        table = params["unembed"]["table"]
    with jax.named_scope("lm_head"):
        logits = L.logits({"table": table}, x)
    logits = shard_act(logits, "batch", "seq", "vocab")
    return logits, aux_loss, new_caches


# ---------------------------------------------------------------------------
# decode-cache allocation (static shapes for serving / dry-run)
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, slots: int,
                per_slot_pos: bool = False,
                paged_global_attn: bool = False,
                paged_window_attn: bool = False):
    """Zero caches for decode: dict p<i> -> stacked-over-periods leaves.

    ``per_slot_pos=True`` allocates the per-row KV position layout
    (pos: (periods, batch, slots)) so every batch row carries its own
    decode clock — the layout serve.slots.SlotManager pools. With it,
    EVERY cache leaf has the batch axis at position 1, which is what
    makes slot gather/scatter a single-axis indexing op.

    ``paged_global_attn=True`` leaves ``{"attn": None}`` for layers whose
    slot axis would span the full ``slots`` (global attention, or a
    window >= slots): those leaves live in a block pool owned by the
    paged slot backing (serve.paging) instead of being reserved per slot.

    ``paged_window_attn=True`` additionally drops the dense ring leaves
    of sliding-window layers with ``window < slots``: their rings page
    through a ring-mode PageTable group (blocks map lazily while a
    request ramps up to ``window`` written positions, then the full ring
    stays resident), so Pareto-short requests stop reserving a dense
    ``window``-row slab they never fill. SSM state is O(1) per slot —
    it cannot strand pool memory and always stays dense.
    """
    np_, d = cfg.num_periods, cfg.d_model
    caches = {}
    for i, spec in enumerate(cfg.pattern):
        if spec.mixer == "attn":
            sl = min(slots, spec.window) if spec.window else slots
            if (paged_global_attn and sl == slots) or \
                    (paged_window_attn and sl < slots):
                caches[f"p{i}"] = {"attn": None}
                continue
            pos = (jnp.full((np_, batch, sl), -1, jnp.int32)
                   if per_slot_pos else jnp.full((np_, sl), -1, jnp.int32))
            caches[f"p{i}"] = {"attn": attention.KVCache(
                k=jnp.zeros((np_, batch, sl, cfg.num_kv_heads,
                             cfg.head_dim), jnp.bfloat16),
                v=jnp.zeros((np_, batch, sl, cfg.num_kv_heads,
                             cfg.head_dim), jnp.bfloat16),
                pos=pos)}
        elif spec.mixer == "rwkv":
            h, hd = d // cfg.rwkv_head_dim, cfg.rwkv_head_dim
            caches[f"p{i}"] = {
                "rwkv": {"s": jnp.zeros((np_, batch, h, hd, hd),
                                        jnp.float32),
                         "x_prev": jnp.zeros((np_, batch, d), jnp.float32)},
                "ffn_x": jnp.zeros((np_, batch, d), jnp.float32)}
        elif spec.mixer == "mamba":
            mcfg = _mamba_cfg(cfg)
            caches[f"p{i}"] = {"mamba": {
                "conv": jnp.zeros((np_, batch, mcfg.conv_kernel - 1,
                                   mcfg.d_inner), jnp.float32),
                "h": jnp.zeros((np_, batch, mcfg.d_inner, mcfg.d_state),
                               jnp.float32)}}
    return caches


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------

def lm_loss(logits: Array, labels: Array, mask: Optional[Array] = None,
            z_weight: float = 1e-4) -> Tuple[Array, Dict[str, Array]]:
    """Masked CE (fp32) + z-loss. labels: (B, S) int32; mask 1.0 = keep."""
    logits = logits.astype(jnp.float32)
    if mask is None:
        mask = jnp.ones(labels.shape, jnp.float32)
    logz = jax.scipy.special.logsumexp(logits, axis=-1)
    ll = jnp.take_along_axis(logits, labels[..., None].astype(jnp.int32),
                             axis=-1)[..., 0]
    denom = jnp.maximum(jnp.sum(mask), 1.0)
    ce = jnp.sum((logz - ll) * mask) / denom
    zl = z_weight * jnp.sum(jnp.square(logz) * mask) / denom
    metrics = {"ce": ce, "z_loss": zl}
    return ce + zl, metrics
