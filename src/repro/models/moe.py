"""Mixture-of-Experts layer: token-choice top-k routing with capacity.

Two dispatch paths:

* ``_moe_gspmd`` — global-capacity scatter/gather dispatch, sharding left
  to GSPMD. Correct everywhere (single device, any mesh), but at
  production scale XLA materializes and ALL-REDUCES the replicated
  (E, C_global, D) buffer — measured 820 GB/device/step of all-reduce on
  olmoe train_4k (EXPERIMENTS.md §Perf, MoE baseline).

* ``_moe_shard_map`` — GShard-style local-group dispatch (§Perf MoE
  iteration 1): each device routes its own tokens into a local-capacity
  (E, c_loc, D) buffer, exchanges token-shards for expert-shards with ONE
  ``all_to_all`` along the expert ('model') axis, runs its local experts,
  and reverses the exchange. Collective traffic per layer becomes
  tokens_loc x k x D — ~100x less than the scatter path. Capacity
  semantics become per-group (standard GShard local groups; documented
  divergence from the global-capacity oracle when capacity is tight).

The shard_map path activates when the configured mesh has a 'model' axis
that divides num_experts; otherwise the GSPMD path runs (single-device
tests, reduced smoke configs).

Routing is dependency-free (noted in DESIGN.md §3.3: the paper's technique
does not apply to dispatch itself); the expert FFNs are dense MXU work.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.sharding import current_mesh, logical, shard_act
from repro.sharding.partition import param_spec

Array = jnp.ndarray


class MoEConfig(NamedTuple):
    d_model: int
    d_ff: int
    num_experts: int
    experts_per_token: int
    capacity_factor: float = 1.25
    act: str = "swiglu"
    router_aux_weight: float = 0.01


def init_moe(key, cfg: MoEConfig):
    kr, kg, ku, kd = jax.random.split(key, 4)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff
    return {
        "router": L.truncated_normal(kr, (d, e), 0.02),
        "expert_gate": L.he_init(kg, (e, d, f), d),
        "expert_up": L.he_init(ku, (e, d, f), d),
        "expert_down": L.he_init(kd, (e, f, d), f),
    }


def capacity(n_tokens: int, cfg: MoEConfig) -> int:
    c = int(n_tokens * cfg.experts_per_token * cfg.capacity_factor
            / cfg.num_experts)
    return max(4, -(-c // 4) * 4)


def moe(params, cfg: MoEConfig, x: Array):
    """x: (B, S, D) -> (y: (B, S, D), aux_loss: scalar fp32)."""
    mesh = current_mesh()
    if mesh is not None and not mesh.empty and "model" in mesh.axis_names:
        from repro.sharding import resolve_axes
        m = dict(zip(mesh.axis_names, mesh.devices.shape))["model"]
        spec = resolve_axes(x.shape, ("batch", "seq", None))
        sharded_over_model = any(
            "model" in ((e,) if isinstance(e, str) else tuple(e))
            for e in spec if e is not None)
        # shard_map pays off only when tokens actually shard over 'model';
        # decode (seq=1) would run the exchange 'model'-times redundantly
        # (measured 5x regression on jamba decode — §Perf MoE notes).
        if m > 1 and cfg.num_experts % m == 0 and sharded_over_model:
            return _moe_shard_map(params, cfg, x, mesh, m)
    return _moe_gspmd(params, cfg, x)


def _local_dispatch(xt: Array, top_e: Array, top_p: Array, e: int, c: int):
    """Scatter n local tokens into an (E, c, D) buffer; returns the buffer
    plus (flat_e, flat_pos, keep, flat_p) for the combine."""
    n, d = xt.shape
    k = top_e.shape[-1]
    flat_e = top_e.reshape(-1)
    flat_p = top_p.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    flat_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = flat_pos < c
    flat_pos = jnp.minimum(flat_pos, c - 1)
    tok_idx = jnp.repeat(jnp.arange(n), k)
    src = jnp.where(keep[:, None], xt[tok_idx], 0).astype(xt.dtype)
    xb = jnp.zeros((e, c, d), xt.dtype).at[flat_e, flat_pos].add(src)
    return xb, (flat_e, flat_pos, keep, flat_p)


def _router(params, cfg: MoEConfig, xt: Array):
    logits = (xt.astype(jnp.float32)
              @ params["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, cfg.experts_per_token)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
    return probs, top_p, top_e


def _aux_loss(cfg: MoEConfig, probs: Array, top_e: Array) -> Array:
    me = jnp.mean(probs, axis=0)
    ce = jnp.mean(jnp.sum(jax.nn.one_hot(
        top_e, cfg.num_experts, dtype=jnp.float32), axis=1), axis=0)
    return cfg.router_aux_weight * cfg.num_experts * jnp.sum(me * ce)


def _moe_shard_map(params, cfg: MoEConfig, x: Array, mesh, m: int):
    """GShard local-group dispatch with an all-to-all expert exchange."""
    from repro.sharding import resolve_axes

    e, k = cfg.num_experts, cfg.experts_per_token
    e_loc = e // m
    axes = mesh.axis_names

    # divisibility-aware specs (decode has seq=1, long-context has batch=1;
    # whatever cannot shard arrives replicated and is simply not gathered)
    x_spec = resolve_axes(x.shape, ("batch", "seq", None))
    router_spec = param_spec("router", params["router"].shape)
    wg_spec = param_spec("expert_gate", params["expert_gate"].shape)

    used: set = set()
    for entry in x_spec:
        if entry is not None:
            used.update((entry,) if isinstance(entry, str) else entry)
    unused_axes = tuple(a for a in axes if a not in used)

    def _gather_axes(val, spec, dim):
        """all_gather `val` along every mesh axis spec[dim] names."""
        entry = spec[dim] if dim < len(spec) else None
        if entry is None:
            return val
        names = (entry,) if isinstance(entry, str) else tuple(entry)
        for name in names:
            val = jax.lax.all_gather(val, name, axis=dim, tiled=True)
        return val

    def body(router_w, wg, wu, wd, xs):
        bl, sl, d = xs.shape
        n = bl * sl
        c = capacity(n, cfg)
        xt = xs.reshape(n, d)

        router_w = _gather_axes(_gather_axes(router_w, router_spec, 0),
                                router_spec, 1)
        probs, top_p, top_e = _router({"router": router_w}, cfg, xt)
        aux = _aux_loss(cfg, probs, top_e)
        aux = jax.lax.pmean(aux, axes)

        xb, (flat_e, flat_pos, keep, flat_p) = _local_dispatch(
            xt, top_e, top_p, e, c)

        # token-shards -> expert-shards: one all_to_all over 'model'
        xe = xb.reshape(m, e_loc, c, d)
        xe = jax.lax.all_to_all(xe, "model", split_axis=0, concat_axis=0,
                                tiled=False)          # (m, e_loc, c, d)
        xe = xe.transpose(1, 0, 2, 3).reshape(e_loc, m * c, d)

        # local experts: FSDP all-gather of the weight shards (dim 1)
        def gather_w(wshard):
            return _gather_axes(wshard, wg_spec, 1)

        dt = xs.dtype
        wg_f = gather_w(wg).astype(dt)
        wu_f = gather_w(wu).astype(dt)
        # wd shards dim1 = d_ff over 'data' per param_spec positional rules
        wd_f = gather_w(wd).astype(dt)
        h = jnp.einsum("ecd,edf->ecf", xe, wg_f)
        h = jax.nn.silu(h) if cfg.act == "swiglu" else jax.nn.gelu(h)
        h = h * jnp.einsum("ecd,edf->ecf", xe, wu_f)
        ye = jnp.einsum("ecf,efd->ecd", h, wd_f)      # (e_loc, m*c, d)

        # reverse exchange: expert-shards -> token-shards
        ye = ye.reshape(e_loc, m, c, d).transpose(1, 0, 2, 3)
        ye = jax.lax.all_to_all(ye, "model", split_axis=0, concat_axis=0,
                                tiled=False)
        yb = ye.reshape(e, c, d)

        gathered = yb[flat_e, flat_pos]
        weighted = gathered * (flat_p * keep)[:, None].astype(dt)
        y = jnp.sum(weighted.reshape(n, k, d), axis=1)
        y = y.reshape(bl, sl, d)
        if unused_axes:
            # mesh axes x could not shard over (decode: seq=1; batch=1)
            # hold identical token copies: the pmean is an identity that
            # makes the replication explicit for shard_map's out check.
            y = jax.lax.pmean(y, unused_axes)
        return y, aux

    wrapped = jax.shard_map(
        body, mesh=mesh,
        in_specs=(router_spec, wg_spec, wg_spec, wg_spec, x_spec),
        out_specs=(x_spec, P()))
    y, aux = wrapped(params["router"], params["expert_gate"],
                     params["expert_up"], params["expert_down"], x)
    return y, aux[()] if aux.ndim else aux


def _moe_gspmd(params, cfg: MoEConfig, x: Array):
    """Global-capacity scatter dispatch; sharding left to GSPMD."""
    b, s, d = x.shape
    n = b * s
    k = cfg.experts_per_token
    e = cfg.num_experts
    c = capacity(n, cfg)
    dt = x.dtype

    xt = x.reshape(n, d)
    router_logits = (xt.astype(jnp.float32)
                     @ params["router"].astype(jnp.float32))      # (N, E)
    probs = jax.nn.softmax(router_logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                        # (N, k)
    top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)        # renorm

    # load-balancing aux loss (Switch-style)
    me = jnp.mean(probs, axis=0)                                  # (E,)
    ce = jnp.mean(
        jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32), axis=1), axis=0)
    aux = cfg.router_aux_weight * e * jnp.sum(me * ce)

    # --- capacity-bounded dispatch -------------------------------------
    flat_e = top_e.reshape(-1)                                    # (N*k,)
    flat_p = top_p.reshape(-1)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)           # (N*k, E)
    pos = jnp.cumsum(onehot, axis=0) - onehot                     # rank
    flat_pos = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = flat_pos < c
    flat_pos = jnp.minimum(flat_pos, c - 1)

    tok_idx = jnp.repeat(jnp.arange(n), k)                        # (N*k,)
    xb = jnp.zeros((e, c, d), dt)
    src = jnp.where(keep[:, None], xt[tok_idx], 0).astype(dt)
    xb = xb.at[flat_e, flat_pos].add(src)                         # dispatch
    xb = shard_act(xb, "experts", "expert_capacity", None)

    # --- expert FFNs (batched over the expert axis) ---------------------
    wg = params["expert_gate"].astype(dt)
    wu = params["expert_up"].astype(dt)
    wd = params["expert_down"].astype(dt)
    h = jnp.einsum("ecd,edf->ecf", xb, wg)
    h = jax.nn.silu(h) if cfg.act == "swiglu" else jax.nn.gelu(h)
    h = h * jnp.einsum("ecd,edf->ecf", xb, wu)
    yb = jnp.einsum("ecf,efd->ecd", h, wd)                        # (E, C, D)
    yb = shard_act(yb, "experts", "expert_capacity", None)

    # --- combine ---------------------------------------------------------
    gathered = yb[flat_e, flat_pos]                               # (N*k, D)
    weighted = gathered * (flat_p * keep)[:, None].astype(dt)
    y = jnp.sum(weighted.reshape(n, k, d), axis=1)
    return y.reshape(b, s, d), aux
