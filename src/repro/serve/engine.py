"""Serving steps: prefill and single-token decode over static-shape caches.

Decode is the dependency-bound 1-D recurrence of serving — each step
consumes the previous step's cache/state (the paper's global-counter
pattern at request scale). Attention layers carry KV ring buffers; RWKV/
Mamba layers carry O(1) recurrent state, making decode cost flat in
context length (the long_500k story).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import transformer as T
from repro.obs import trace as obs_trace
from repro.sharding import named_sharding


@dataclasses.dataclass(frozen=True)
class SamplingPolicy:
    """Per-request sampling knobs threaded through the fused decode steps.

    ``temperature <= 0`` is greedy (exact argmax of the raw logits —
    the differential-harness contract). ``top_k = 0`` disables top-k;
    ``top_p = 1.0`` disables nucleus filtering. Both filters are exact
    identities when disabled, so default-policy streams are bitwise
    unchanged.
    """

    temperature: float = 0.0
    top_k: int = 0
    top_p: float = 1.0

    def __post_init__(self):
        if self.temperature < 0.0:
            raise ValueError(f"temperature must be >= 0: {self.temperature}")
        if self.top_k < 0:
            raise ValueError(f"top_k must be >= 0 (0 disables): {self.top_k}")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError(f"top_p must be in (0, 1]: {self.top_p}")

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0

    def fingerprint(self):
        """Hashable identity for memo keys (RequestCache, coalescing)."""
        return (float(self.temperature), int(self.top_k), float(self.top_p))


def _filter_topk_topp(lg: jnp.ndarray, top_ks: jnp.ndarray,
                      top_ps: jnp.ndarray) -> jnp.ndarray:
    """Mask logits (B, V) outside the per-row top-k / nucleus sets to -inf.

    top_ks (B,) int32 (0 = disabled) and top_ps (B,) fp32 (1.0 =
    disabled) are value thresholds against the descending sort: ties at
    the cut survive together, and a disabled filter keeps every entry,
    making the whole function a bitwise identity for the defaults.
    """
    v = lg.shape[-1]
    srt = jnp.sort(lg, axis=-1)[:, ::-1]                  # descending
    k = jnp.clip(jnp.where(top_ks <= 0, v, top_ks), 1, v)
    kth = jnp.take_along_axis(srt, (k - 1)[:, None], axis=-1)
    keep_k = lg >= kth
    # exclusive cumsum of sorted probs: entry i kept iff the mass strictly
    # before it is < top_p — always keeps the argmax, disabled at p = 1.
    probs = jax.nn.softmax(srt, axis=-1)
    cum = jnp.cumsum(probs, axis=-1) - probs
    nk = jnp.maximum(jnp.sum((cum < top_ps[:, None]).astype(jnp.int32),
                             axis=-1), 1)
    nth = jnp.take_along_axis(srt, (nk - 1)[:, None], axis=-1)
    keep_p = lg >= nth
    return jnp.where(keep_k & keep_p, lg, -jnp.inf)


def sample_token(logits: jnp.ndarray, key=None, temperature=0.0,
                 top_k=0, top_p=1.0) -> jnp.ndarray:
    """logits: (B, 1, V) -> (B,) int32. temperature 0 = greedy.

    ``temperature`` may be a python float (shared) or a (B,) array —
    per-slot temperatures for continuous batching — and ``top_k`` /
    ``top_p`` likewise (python scalars or (B,) vectors). The array path
    uses the Gumbel-max identity (categorical(l/T) == argmax(l/T + g))
    with a per-row where() so greedy rows stay exactly argmax of the RAW
    logits regardless of the filters.
    """
    lg = logits[:, -1].astype(jnp.float32)
    greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
    scalars = (isinstance(temperature, (int, float))
               and isinstance(top_k, int)
               and isinstance(top_p, (int, float)))
    if scalars:
        if temperature <= 0.0 or key is None:
            return greedy
        if top_k > 0 or top_p < 1.0:                # skip the sort when off
            b = lg.shape[0]
            lg = _filter_topk_topp(
                lg, jnp.full((b,), top_k, jnp.int32),
                jnp.full((b,), top_p, jnp.float32))
        return jax.random.categorical(key, lg / temperature).astype(jnp.int32)
    b = lg.shape[0]
    temps = jnp.broadcast_to(jnp.asarray(temperature, jnp.float32), (b,))
    ks = jnp.broadcast_to(jnp.asarray(top_k, jnp.int32), (b,))
    ps = jnp.broadcast_to(jnp.asarray(top_p, jnp.float32), (b,))
    filt = _filter_topk_topp(lg, ks, ps)
    g = jax.random.gumbel(key, lg.shape, jnp.float32)
    scaled = filt / jnp.maximum(temps, 1e-6)[:, None] + g
    sampled = jnp.argmax(scaled, axis=-1).astype(jnp.int32)
    return jnp.where(temps > 0.0, sampled, greedy)


def make_prefill_step(cfg: ModelConfig, cache_slots: int):
    """prefill(params, tokens|embeds) -> (last_logits, caches)."""

    def prefill(params, batch: Dict[str, jnp.ndarray]):
        logits, _, caches = T.apply_model(
            params, cfg, tokens=batch.get("tokens"),
            embeds=batch.get("embeds"), mode="prefill",
            cache_slots=cache_slots)
        return logits, caches

    return prefill


def make_decode_step(cfg: ModelConfig, temperature: float = 0.0):
    """decode(params, caches, inp, pos[, key]) -> (next_tok, logits, caches).

    inp: {"tokens": (B,1)} or {"embeds": (B,1,D)}; pos: int32 scalar —
    the absolute position of the incoming token.
    """

    def decode(params, caches, inp: Dict[str, jnp.ndarray],
               pos: jnp.ndarray, key: Optional[jnp.ndarray] = None):
        logits, _, caches = T.apply_model(
            params, cfg, tokens=inp.get("tokens"),
            embeds=inp.get("embeds"), mode="decode", caches=caches,
            pos_scalar=pos)
        nxt = sample_token(logits, key, temperature)
        return nxt, logits, caches

    return decode


# ---------------------------------------------------------------------------
# continuous-batching steps: per-slot position vectors (serve.scheduler)
# ---------------------------------------------------------------------------

def make_slot_decode_step(cfg: ModelConfig):
    """decode(params, caches, tokens, pos, temps, key[, top_ks, top_ps])
    -> (next_tok, logits, caches) with PER-SLOT clocks.

    tokens: (B, 1) int32; pos: (B,) int32 — each row's absolute position;
    temps: (B,) fp32 per-slot temperature (0 = greedy); top_ks (B,) int32
    / top_ps (B,) fp32 optional per-slot filters (None = disabled).
    Caches must use the per-row position layout
    (init_caches(per_slot_pos=True)).
    """

    def decode(params, caches, tokens: jnp.ndarray, pos: jnp.ndarray,
               temps: jnp.ndarray, key: jnp.ndarray,
               top_ks: Optional[jnp.ndarray] = None,
               top_ps: Optional[jnp.ndarray] = None):
        logits, _, caches = T.apply_model(
            params, cfg, tokens=tokens, mode="decode", caches=caches,
            pos_scalar=pos)
        nxt = sample_token(logits, key, temps,
                           0 if top_ks is None else top_ks,
                           1.0 if top_ps is None else top_ps)
        return nxt, logits, caches

    return decode


def make_chunk_step(cfg: ModelConfig):
    """chunk(params, caches, tokens, pos) -> (logits (B, C, V), caches).

    Chunked prefill AND the teacher-forced verify path: tokens (B, C)
    are C consecutive tokens per row, starting at absolute position
    pos[b]. Attention appends the chunk to the cache and masks by
    absolute position (causal within the chunk for free); SSM layers run
    the state-carried chunk-parallel scan. Every row must carry a FULL
    chunk — exactness comes from never padding inside a chunk (remainder
    tokens go through the decode ramp). Logits cover EVERY chunk
    position (bitwise identical to stepping the same tokens one at a
    time through the decode step) — prompt scoring and speculative
    verification consume the non-final positions.
    """

    def chunk(params, caches, tokens: jnp.ndarray, pos: jnp.ndarray):
        logits, _, caches = T.apply_model(
            params, cfg, tokens=tokens, mode="decode", caches=caches,
            pos_scalar=pos)
        return logits, caches

    return chunk


# ---------------------------------------------------------------------------
# speculative verify-accept: teacher-force k drafts through the chunk
# path, accept the agreeing prefix, roll the cache back in-program
# ---------------------------------------------------------------------------

def _ring_gather(leaf: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """Gather ring rows idx (B, S) from a cache leaf (P, B, slots, ...)
    along the slot axis (index 2), modulo that leaf's view length."""
    take = jax.vmap(jax.vmap(lambda l, i: l[i], in_axes=(0, 0)),
                    in_axes=(0, None))
    return take(leaf, idx % leaf.shape[2])


def _ring_scatter(leaf: jnp.ndarray, idx: jnp.ndarray,
                  rows: jnp.ndarray) -> jnp.ndarray:
    """Inverse of _ring_gather: write rows (P, B, S, ...) back at ring
    indices idx (B, S). Indices within a row are distinct (the verify
    span never exceeds the smallest view length), so the scatter is
    deterministic."""
    put = jax.vmap(jax.vmap(lambda l, i, r: l.at[i].set(r),
                            in_axes=(0, 0, 0)),
                   in_axes=(0, None, 0))
    return put(leaf, idx % leaf.shape[2], rows)


def _snapshot_span(caches, idx):
    """Pre-step snapshot: the ring rows every attention leaf will
    (re)write for absolute positions idx (B, S)."""
    from repro.models.attention import KVCache  # local: avoid import cycle

    return {key: KVCache(k=_ring_gather(e["attn"].k, idx),
                         v=_ring_gather(e["attn"].v, idx),
                         pos=_ring_gather(e["attn"].pos, idx))
            for key, e in caches.items()}


def _restore_span(caches, idx, saved, limit):
    """Post-step rollback: keep chunk writes at absolute positions
    <= limit[b] (the last accepted position), restore the snapshot
    everywhere else — inactive rows pass limit = -1 and get a full undo,
    so the cache only ever holds committed-correct entries."""
    from repro.models.attention import KVCache

    keep = idx <= limit[:, None]                    # (B, S)

    def mix(new, old):
        k2 = keep.reshape((1,) + keep.shape + (1,) * (new.ndim - 3))
        return jnp.where(k2, new, old)

    out = {}
    for key, e in caches.items():
        kv, sv = e["attn"], saved[key]
        e = dict(e)
        e["attn"] = KVCache(
            k=_ring_scatter(kv.k, idx, mix(_ring_gather(kv.k, idx), sv.k)),
            v=_ring_scatter(kv.v, idx, mix(_ring_gather(kv.v, idx), sv.v)),
            pos=_ring_scatter(kv.pos, idx,
                              mix(_ring_gather(kv.pos, idx), sv.pos)))
        out[key] = e
    return out


def make_verify_step(cfg: ModelConfig):
    """verify(params, caches, tokens, pos, prompt_len, max_pos, score,
    active, temps, top_ks, top_ps, key) ->
    (out_tok (B, S), accept_n (B,), logprobs (B, S), caches).

    One fused speculative tick over the whole pool. tokens (B, S) carry
    [t, d_1..d_k] per row (S = k+1): the true next token t at absolute
    position pos[b] followed by k drafts. The chunk path teacher-forces
    all S positions, then the accept rule takes the longest prefix of
    drafts agreeing with the model's own greedy predictions — under
    greedy sampling this makes the emitted stream bit-identical to
    one-token-at-a-time decode. Rows with temps > 0 accept nothing and
    sample their first token under the full per-slot policy (exactly the
    non-speculative semantics). ``forced`` teacher-forcing positions
    (draft position < prompt_len, i.e. the decode ramp) auto-accept;
    accepts clamp to max_pos[b] (last position allowed to commit) and,
    for score rows, to k-1 so every prompt position's logprob is
    surfaced exactly once. Rejected (and inactive-row) cache writes are
    rolled back in-program via a span snapshot, so the pool cache never
    holds uncommitted state.

    Requires an attention-only pattern (SSM chunk scans are
    irreversible) and S <= the smallest attention view length (distinct
    ring indices for the rollback scatter) — callers gate both.
    """
    for spec in cfg.pattern:
        if spec.mixer != "attn" or spec.mlp == "rwkv_ffn":
            raise ValueError(
                "speculative verify needs an attention-only pattern with "
                f"stateless MLPs; got mixer={spec.mixer!r} mlp={spec.mlp!r} "
                "(SSM/rwkv_ffn chunk scans cannot be rolled back)")

    def verify(params, caches, tokens: jnp.ndarray, pos: jnp.ndarray,
               prompt_len: jnp.ndarray, max_pos: jnp.ndarray,
               score: jnp.ndarray, active: jnp.ndarray,
               temps: jnp.ndarray, top_ks: jnp.ndarray,
               top_ps: jnp.ndarray, key: jnp.ndarray):
        s = tokens.shape[1]
        k = s - 1
        idx = pos[:, None] + jnp.arange(s, dtype=jnp.int32)[None, :]
        saved = _snapshot_span(caches, idx)
        logits, _, caches = T.apply_model(
            params, cfg, tokens=tokens, mode="decode", caches=caches,
            pos_scalar=pos)
        lg = logits.astype(jnp.float32)             # (B, S, V)
        greedy = jnp.argmax(lg, axis=-1).astype(jnp.int32)
        drafts = tokens[:, 1:]                      # (B, k)
        # a draft at chunk slot i+1 occupies absolute position pos+i+1;
        # ramp positions (< prompt_len) are teacher-forced true tokens
        # and auto-accept — greedy agreement only gates real samples.
        forced = (idx[:, :k] + 1) < prompt_len[:, None]
        match = (greedy[:, :k] == drafts) | forced
        n = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=-1), axis=-1)
        n = jnp.where(temps > 0.0, 0, n)
        n = jnp.where(score, jnp.minimum(n, k - 1), n)
        n = jnp.minimum(n, jnp.maximum(max_pos - pos, 0))
        n = jnp.where(active, n, 0)
        limit = jnp.where(active, pos + n, -1)
        caches = _restore_span(caches, idx, saved, limit)
        # out_tok[:, i] = the model's prediction after consuming chunk
        # slot i; sampled rows replace slot 0 with a policy sample (their
        # only emission this tick — accept_n is 0 for them).
        first = sample_token(lg[:, :1], key, temps, top_ks, top_ps)
        out_tok = greedy.at[:, 0].set(first)
        # logprobs[:, i] = log p(token fed at slot i+1 | prefix); the
        # final slot scores the model's own bonus prediction.
        fed = jnp.concatenate([drafts, out_tok[:, -1:]], axis=-1)
        lp = jnp.take_along_axis(jax.nn.log_softmax(lg, axis=-1),
                                 fed[..., None], axis=-1)[..., 0]
        return out_tok, n.astype(jnp.int32), lp, caches

    return verify


@functools.lru_cache(maxsize=None)
def jit_verify_step(cfg: ModelConfig):
    return obs_trace.instrumented_jit(
        jax.jit(make_verify_step(cfg), donate_argnums=(1,)),
        name=f"verify_step[{cfg.name}]", prefix="serve.engine")


# ModelConfig is a frozen dataclass, so jitted step programs are shared
# process-wide per config (one compile per (cfg, shape) — a new Scheduler
# or generate() call never retraces; same discipline as runtime.dispatch).
# The caches argument is donated: the pool is the scarce resource, and
# without donation every step materializes a second full copy of it.
# Callers must drop their reference (`_, caches = step(params, caches, …)`).

@functools.lru_cache(maxsize=None)
def jit_chunk_step(cfg: ModelConfig):
    return obs_trace.instrumented_jit(
        jax.jit(make_chunk_step(cfg), donate_argnums=(1,)),
        name=f"chunk_step[{cfg.name}]", prefix="serve.engine")


@functools.lru_cache(maxsize=None)
def jit_slot_decode_step(cfg: ModelConfig):
    return obs_trace.instrumented_jit(
        jax.jit(make_slot_decode_step(cfg), donate_argnums=(1,)),
        name=f"slot_decode_step[{cfg.name}]", prefix="serve.engine")


# ---------------------------------------------------------------------------
# paged steps: caches split into dense per-slot leaves + a physical block
# pool read through a page table (serve.paging / serve.slots paged backing)
# ---------------------------------------------------------------------------

def _merge_paged(dense, paged, rows, block_size):
    """Rebuild the full cache tree the model steps expect: dense entries
    pass through; paged attention layers (dense holds None) get a per-slot
    view gathered through the page-table ``rows[key]``. View lengths vary
    per key — cache_slots for global-attention layers, the ring length
    for sliding-window layers — and each key's trash floor is recovered
    from its flat pool's shape (the trash block is the last
    ``block_size`` rows). Its ops carry the ``state_gather`` scope."""
    from repro.models import attention  # local: avoid import cycle

    caches = {}
    with jax.named_scope("state_gather"):
        for key, entry in dense.items():
            if key in paged:
                entry = dict(entry)
                entry["attn"] = attention.paged_view(
                    paged[key], rows[key],
                    attention.paged_live_rows(paged[key], block_size))
            caches[key] = entry
    return caches


def _split_paged(caches, paged, rows):
    """Inverse of _merge_paged: scatter updated views back into the pool
    and strip them from the dense tree (None placeholders restored).
    Its ops carry the ``state_scatter`` scope."""
    from repro.models import attention

    dense, paged_new = {}, {}
    with jax.named_scope("state_scatter"):
        for key, entry in caches.items():
            if key in paged:
                entry = dict(entry)
                view = entry["attn"]
                entry["attn"] = None
                paged_new[key] = attention.paged_writeback(
                    paged[key], view, rows[key])
            dense[key] = entry
    return dense, paged_new


@functools.lru_cache(maxsize=None)
def jit_paged_decode_step(cfg: ModelConfig):
    """Fused page-gather -> decode -> page-scatter over the whole pool.

    dense: cache tree with None at paged attention entries (per-slot SSM
    state, any unpaged leaves); paged: dict pattern-key -> flat KVCache
    block pool; rows: dict pattern-key -> (B, V_key) flat physical row
    per view position (keys in one page-table group share the array);
    block_size (static): every group's block size — each key's trash
    floor is its flat pool's rows minus one block. One jitted program per
    cfg — same one-fused-program-per-tick property as the contiguous
    path, the page tables are just extra gather indices.
    """
    step = make_slot_decode_step(cfg)

    def run(params, dense, paged, rows, tokens, pos, temps, key,
            top_ks, top_ps, block_size: int):
        caches = _merge_paged(dense, paged, rows, block_size)
        nxt, logits, caches = step(params, caches, tokens, pos, temps, key,
                                   top_ks, top_ps)
        dense, paged = _split_paged(caches, paged, rows)
        return nxt, logits, dense, paged

    return obs_trace.instrumented_jit(
        jax.jit(run, donate_argnums=(1, 2), static_argnums=(10,)),
        name=f"paged_decode_step[{cfg.name}]", prefix="serve.engine")


@functools.lru_cache(maxsize=None)
def jit_paged_chunk_step(cfg: ModelConfig):
    """Fused gather -> chunk-prefill -> scatter for the paged layout,
    returning (logits (m, C, V), dense, paged).

    ``idx`` selects the sub-batch of slots (pad-by-repeat contract as the
    contiguous pooled chunk step); ``rows`` values are already
    per-sub-row (len(idx), V_key). Dense leaves gather/scatter on the
    slot axis, paged leaves through their page tables. Logits cover every
    chunk position of every sub-row (prompt scoring reads them; plain
    prefill ignores them). The dense state's gather and scatter carry the
    ``state_gather`` / ``state_scatter`` scopes.
    """
    step = make_chunk_step(cfg)

    def run(params, dense, paged, idx, rows, tokens, pos, block_size: int):
        with jax.named_scope("state_gather"):
            sub = jax.tree_util.tree_map(
                lambda l: jnp.take(l, idx, axis=1), dense)
        caches = _merge_paged(sub, paged, rows, block_size)
        logits, caches = step(params, caches, tokens, pos)
        sub, paged = _split_paged(caches, paged, rows)
        with jax.named_scope("state_scatter"):
            dense = jax.tree_util.tree_map(
                lambda l, s: l.at[:, idx].set(s.astype(l.dtype)), dense,
                sub)
        return logits, dense, paged

    return obs_trace.instrumented_jit(
        jax.jit(run, donate_argnums=(1, 2), static_argnums=(7,)),
        name=f"paged_chunk_step[{cfg.name}]", prefix="serve.engine")


@functools.lru_cache(maxsize=None)
def jit_paged_verify_step(cfg: ModelConfig):
    """Fused page-gather -> verify-accept -> rollback -> page-scatter
    over the whole pool (same full-pool ``rows`` contract as
    jit_paged_decode_step). The span snapshot/restore operates on the
    gathered per-slot views, so the writeback only ever lands committed
    rows in the physical block pool.
    """
    step = make_verify_step(cfg)

    def run(params, dense, paged, rows, tokens, pos, prompt_len, max_pos,
            score, active, temps, top_ks, top_ps, key, block_size: int):
        caches = _merge_paged(dense, paged, rows, block_size)
        out_tok, n, lp, caches = step(
            params, caches, tokens, pos, prompt_len, max_pos, score,
            active, temps, top_ks, top_ps, key)
        dense, paged = _split_paged(caches, paged, rows)
        return out_tok, n, lp, dense, paged

    return obs_trace.instrumented_jit(
        jax.jit(run, donate_argnums=(1, 2), static_argnums=(14,)),
        name=f"paged_verify_step[{cfg.name}]", prefix="serve.engine")


# ---------------------------------------------------------------------------
# sharded steps: the slot pool split over a 1-D device mesh
# ---------------------------------------------------------------------------
#
# Every per-slot cache leaf carries the slot axis at position 1
# ((periods, B, ...)), so sharding the pool is sharding that axis:
# stacked arrays hold all shards' segments back-to-back (dense: B =
# num_shards * slots_per_shard; paged flat pools: num_shards segments of
# (num_blocks + 1) * block_size rows, each segment ending in its OWN
# trash block), and the fused step runs once per tick spanning every
# shard. Three compilation strategies behind one factory signature:
#
#   * num_shards == 1, no mesh — delegate to the unsharded jitted step
#     (the SAME compiled program: the mesh=1 differential is structurally
#     bit-identical).
#   * num_shards > 1, no mesh  — jax.vmap over the shard axis (multi-
#     shard semantics on a single-device CI host).
#   * mesh                     — jax.shard_map over the mesh axis: one
#     fused program, one shard per device, block ids never cross shards.
#     check_vma=False: the body is shard-local (no collectives, every
#     output sharded), and the model's scans start from fresh constant
#     carries that the varying-axes check would reject.
#
# Row vectors passed to these steps are SHARD-LOCAL physical rows (each
# shard indexes only its own flat-pool segment); host-side block ops
# (reset/gather/upload/copy_block_rows) keep using GLOBAL rows into the
# stacked arrays.

def _check_shard_mesh(num_shards: int, mesh, axis):
    if num_shards < 1:
        raise ValueError(f"num_shards must be >= 1, got {num_shards}")
    if mesh is not None:
        if axis not in mesh.axis_names:
            raise ValueError(f"axis {axis!r} not in mesh axes "
                             f"{mesh.axis_names}")
        if mesh.shape[axis] != num_shards:
            raise ValueError(
                f"mesh axis {axis!r} has {mesh.shape[axis]} device(s) "
                f"but num_shards={num_shards}: the slot-pool shard count "
                "must match the mesh")


def _split_shard_axis(n: int):
    """(tree fns) stacked (P, n*x, ...) <-> per-shard (P, n, x, ...)."""
    def split(l):
        return l.reshape(l.shape[:1] + (n, l.shape[1] // n) + l.shape[2:])

    def fuse(l):
        return l.reshape(l.shape[:1] + (l.shape[1] * l.shape[2],)
                         + l.shape[3:])

    return split, fuse


def _make_sharded_decode_inner(cfg: ModelConfig, block_size: int):
    """Per-shard decode body shared by the vmap and shard_map paths:
    operates on ONE shard's dense/paged segment with shard-local rows.
    ``key`` arrives as (2,) under vmap and (1, 2) under shard_map."""
    step = make_slot_decode_step(cfg)

    def inner(params, dense, paged, rows, tokens, pos, temps, key,
              top_ks, top_ps):
        key = key.reshape(2)
        caches = _merge_paged(dense, paged, rows, block_size)
        nxt, logits, caches = step(params, caches, tokens, pos, temps,
                                   key, top_ks, top_ps)
        dense, paged = _split_paged(caches, paged, rows)
        return nxt, logits, dense, paged

    return inner


@functools.lru_cache(maxsize=None)
def jit_sharded_decode_step(cfg: ModelConfig, num_shards: int,
                            block_size: int, mesh=None,
                            axis: Optional[str] = None):
    """Fused decode over the sharded pool. Signature of the returned fn:
    run(params, dense, paged, rows, tokens, pos, temps, keys, top_ks,
    top_ps) -> (nxt (B,), logits (B, 1, V), dense, paged) with
    B = num_shards * slots_per_shard, ``rows`` shard-local, and ``keys``
    (num_shards, 2) per-shard PRNG keys. The lru key folds num_shards,
    block_size AND the mesh + axis name, so a resized mesh can never
    reuse a stale compiled program."""
    _check_shard_mesh(num_shards, mesh, axis)
    if num_shards == 1 and mesh is None:
        base = jit_paged_decode_step(cfg)

        def run(params, dense, paged, rows, tokens, pos, temps, keys,
                top_ks, top_ps):
            return base(params, dense, paged, rows, tokens, pos, temps,
                        keys.reshape(2), top_ks, top_ps, block_size)

        return run
    inner = _make_sharded_decode_inner(cfg, block_size)
    n = num_shards
    if mesh is None:
        split, fuse = _split_shard_axis(n)
        tm = jax.tree_util.tree_map

        def run(params, dense, paged, rows, tokens, pos, temps, keys,
                top_ks, top_ps):
            nxt, logits, dense, paged = jax.vmap(
                inner, in_axes=(None, 1, 1, 0, 0, 0, 0, 0, 0, 0),
                out_axes=(0, 0, 1, 1))(
                params, tm(split, dense), tm(split, paged),
                tm(lambda r: r.reshape((n, -1) + r.shape[1:]), rows),
                tokens.reshape((n, -1) + tokens.shape[1:]),
                pos.reshape(n, -1), temps.reshape(n, -1), keys,
                top_ks.reshape(n, -1), top_ps.reshape(n, -1))
            return (nxt.reshape(-1),
                    logits.reshape((-1,) + logits.shape[2:]),
                    tm(fuse, dense), tm(fuse, paged))
    else:
        from jax.sharding import PartitionSpec as P
        run = jax.shard_map(
            inner, mesh=mesh, check_vma=False,
            in_specs=(P(), P(None, axis), P(None, axis), P(axis), P(axis),
                      P(axis), P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(axis), P(None, axis), P(None, axis)))
    return obs_trace.instrumented_jit(
        jax.jit(run, donate_argnums=(1, 2)),
        name=f"sharded_decode_step[{cfg.name}x{num_shards}]",
        prefix="serve.engine")


def _make_sharded_chunk_inner(cfg: ModelConfig, block_size: int):
    """Per-shard chunk-prefill body. Each shard gets its own padded
    sub-batch (idx (m,) shard-local slots, pad-by-repeat); ``live`` False
    marks a shard with nothing to prefill this call: its rows point at
    the shard's trash block and its dense writes are reverted, so the
    step is a semantic no-op there. Operands arrive with a leading
    size-1 shard axis under shard_map and without it under vmap — the
    reshapes normalize."""
    step = make_chunk_step(cfg)

    def inner(params, dense, paged, idx, rows, tokens, pos, live):
        idx = idx.reshape(idx.shape[-1])
        rows = {k: r.reshape(r.shape[-2:]) for k, r in rows.items()}
        tokens = tokens.reshape(tokens.shape[-2:])
        pos = pos.reshape(pos.shape[-1])
        live = live.reshape(())
        tm = jax.tree_util.tree_map
        sub = tm(lambda l: jnp.take(l, idx, axis=1), dense)
        caches = _merge_paged(sub, paged, rows, block_size)
        logits, caches = step(params, caches, tokens, pos)
        sub2, paged = _split_paged(caches, paged, rows)
        # idle shard: paged writes landed in the trash block (masked on
        # every read); dense writes are reverted here
        sub2 = tm(lambda a, b: jnp.where(live, a, b.astype(a.dtype)),
                  sub2, sub)
        dense = tm(lambda l, s: l.at[:, idx].set(s.astype(l.dtype)),
                   dense, sub2)
        return logits, dense, paged

    return inner


@functools.lru_cache(maxsize=None)
def jit_sharded_chunk_step(cfg: ModelConfig, num_shards: int,
                           block_size: int, mesh=None,
                           axis: Optional[str] = None):
    """Fused chunk-prefill over the sharded pool. run(params, dense,
    paged, idx, rows, tokens, pos, live) -> (logits (n, m, C, V), dense,
    paged): idx (n, m) shard-LOCAL slot ids (pad-by-repeat within a
    shard), rows shard-local (n, m, V_key), tokens (n, m, C), pos
    (n, m), live (n,) bool (False = idle shard: idx/rows carry trash)."""
    _check_shard_mesh(num_shards, mesh, axis)
    if num_shards == 1 and mesh is None:
        base = jit_paged_chunk_step(cfg)

        def run(params, dense, paged, idx, rows, tokens, pos, live):
            logits, dense, paged = base(
                params, dense, paged, idx[0],
                {k: r[0] for k, r in rows.items()}, tokens[0], pos[0],
                block_size)
            return logits[None], dense, paged

        return run
    inner = _make_sharded_chunk_inner(cfg, block_size)
    n = num_shards
    if mesh is None:
        split, fuse = _split_shard_axis(n)
        tm = jax.tree_util.tree_map

        def run(params, dense, paged, idx, rows, tokens, pos, live):
            logits, dense, paged = jax.vmap(
                inner, in_axes=(None, 1, 1, 0, 0, 0, 0, 0),
                out_axes=(0, 1, 1))(
                params, tm(split, dense), tm(split, paged), idx, rows,
                tokens, pos, live)
            return logits, tm(fuse, dense), tm(fuse, paged)
    else:
        from jax.sharding import PartitionSpec as P
        smapped = jax.shard_map(
            inner, mesh=mesh, check_vma=False,
            in_specs=(P(), P(None, axis), P(None, axis), P(axis), P(axis),
                      P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(None, axis), P(None, axis)))

        def run(params, dense, paged, idx, rows, tokens, pos, live):
            logits, dense, paged = smapped(params, dense, paged, idx,
                                           rows, tokens, pos, live)
            # shard_map concatenates per-shard (m, C, V) on axis 0
            return (logits.reshape((n, -1) + logits.shape[1:]),
                    dense, paged)
    return obs_trace.instrumented_jit(
        jax.jit(run, donate_argnums=(1, 2)),
        name=f"sharded_chunk_step[{cfg.name}x{num_shards}]",
        prefix="serve.engine")


def _make_sharded_verify_inner(cfg: ModelConfig, block_size: int):
    """Per-shard speculative verify-accept body (rows/accept semantics
    are per-slot, so sharding is a pure partition of the pool)."""
    step = make_verify_step(cfg)

    def inner(params, dense, paged, rows, tokens, pos, prompt_len,
              max_pos, score, active, temps, top_ks, top_ps, key):
        key = key.reshape(2)
        caches = _merge_paged(dense, paged, rows, block_size)
        out_tok, n, lp, caches = step(
            params, caches, tokens, pos, prompt_len, max_pos, score,
            active, temps, top_ks, top_ps, key)
        dense, paged = _split_paged(caches, paged, rows)
        return out_tok, n, lp, dense, paged

    return inner


@functools.lru_cache(maxsize=None)
def jit_sharded_verify_step(cfg: ModelConfig, num_shards: int,
                            block_size: int, mesh=None,
                            axis: Optional[str] = None):
    """Fused speculative verify over the sharded pool (full-pool row
    contract of jit_paged_verify_step, shard-local rows, per-shard keys
    (num_shards, 2))."""
    _check_shard_mesh(num_shards, mesh, axis)
    if num_shards == 1 and mesh is None:
        base = jit_paged_verify_step(cfg)

        def run(params, dense, paged, rows, tokens, pos, prompt_len,
                max_pos, score, active, temps, top_ks, top_ps, keys):
            return base(params, dense, paged, rows, tokens, pos,
                        prompt_len, max_pos, score, active, temps,
                        top_ks, top_ps, keys.reshape(2), block_size)

        return run
    inner = _make_sharded_verify_inner(cfg, block_size)
    n = num_shards
    if mesh is None:
        split, fuse = _split_shard_axis(n)
        tm = jax.tree_util.tree_map

        def run(params, dense, paged, rows, tokens, pos, prompt_len,
                max_pos, score, active, temps, top_ks, top_ps, keys):
            shard_rows = lambda x: x.reshape((n, -1) + x.shape[1:])  # noqa: E731
            out_tok, acc, lp, dense, paged = jax.vmap(
                inner,
                in_axes=(None, 1, 1, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0, 0),
                out_axes=(0, 0, 0, 1, 1))(
                params, tm(split, dense), tm(split, paged),
                tm(shard_rows, rows), shard_rows(tokens),
                pos.reshape(n, -1), prompt_len.reshape(n, -1),
                max_pos.reshape(n, -1), score.reshape(n, -1),
                active.reshape(n, -1), temps.reshape(n, -1),
                top_ks.reshape(n, -1), top_ps.reshape(n, -1), keys)
            return (out_tok.reshape((-1,) + out_tok.shape[2:]),
                    acc.reshape(-1), lp.reshape((-1,) + lp.shape[2:]),
                    tm(fuse, dense), tm(fuse, paged))
    else:
        from jax.sharding import PartitionSpec as P
        run = jax.shard_map(
            inner, mesh=mesh, check_vma=False,
            in_specs=(P(), P(None, axis), P(None, axis), P(axis), P(axis),
                      P(axis), P(axis), P(axis), P(axis), P(axis),
                      P(axis), P(axis), P(axis), P(axis)),
            out_specs=(P(axis), P(axis), P(axis), P(None, axis),
                       P(None, axis)))
    return obs_trace.instrumented_jit(
        jax.jit(run, donate_argnums=(1, 2)),
        name=f"sharded_verify_step[{cfg.name}x{num_shards}]",
        prefix="serve.engine")


@functools.partial(jax.jit, donate_argnums=(0,))
def reset_block_rows(paged, rows):
    """Zero the physical rows of freshly-mapped blocks (k=v=0, pos=-1) —
    the paged counterpart of SlotManager.alloc's slot reset. ``rows`` may
    be padded with trash rows (identical writes: deterministic)."""
    from repro.models.attention import KVCache

    return {key: KVCache(k=c.k.at[:, rows].set(0),
                         v=c.v.at[:, rows].set(0),
                         pos=c.pos.at[:, rows].set(-1))
            for key, c in paged.items()}


@jax.jit
def gather_block_rows(paged, rows):
    """Pull the physical ``rows`` of every paged cache leaf — the
    device half of swap-out preemption (the host then ``device_get``s
    the result into a SwapStore). ``rows`` comes from
    PageTable.block_rows over the victim's mapped blocks, pow2-padded
    with trash rows so compiles stay O(log blocks_per_slot)."""
    from repro.models.attention import KVCache

    return {key: KVCache(k=jnp.take(c.k, rows, axis=1),
                         v=jnp.take(c.v, rows, axis=1),
                         pos=jnp.take(c.pos, rows, axis=1))
            for key, c in paged.items()}


@functools.partial(jax.jit, donate_argnums=(0,))
def upload_block_rows(paged, saved, rows):
    """Write saved block bytes into freshly-mapped physical ``rows`` —
    the resume half of swap preemption (inverse of gather_block_rows,
    same PageTable.block_rows layout). Pad rows land in the trash block
    with identical (zero) payloads, so the scatter is deterministic."""
    from repro.models.attention import KVCache

    return {key: KVCache(
        k=c.k.at[:, rows].set(saved[key].k.astype(c.k.dtype)),
        v=c.v.at[:, rows].set(saved[key].v.astype(c.v.dtype)),
        pos=c.pos.at[:, rows].set(saved[key].pos.astype(jnp.int32)))
            for key, c in paged.items()}


@functools.partial(jax.jit, donate_argnums=(0,))
def copy_block_rows(paged, src_rows, dst_rows):
    """Device-side block copy: duplicate the physical ``src_rows`` into
    ``dst_rows`` on every paged cache leaf — the copy half of
    copy-on-write (PageTable.cow_block picks the blocks; this moves the
    bytes without a host round-trip). Row vectors use the same
    PageTable.block_rows layout as gather/upload and may be pow2-padded
    with trash->trash pairs (the trash block copies onto itself:
    harmless, deterministic)."""
    from repro.models.attention import KVCache

    return {key: KVCache(
        k=c.k.at[:, dst_rows].set(jnp.take(c.k, src_rows, axis=1)),
        v=c.v.at[:, dst_rows].set(jnp.take(c.v, src_rows, axis=1)),
        pos=c.pos.at[:, dst_rows].set(jnp.take(c.pos, src_rows, axis=1)))
            for key, c in paged.items()}


def generate(params, cfg: ModelConfig, prompt, max_new_tokens: int,
             *, temperature: float = 0.0, top_k: int = 0,
             top_p: float = 1.0, eos_token: Optional[int] = None,
             prefill_chunk: int = 32, cache_slots: int = 0,
             key: Optional[jnp.ndarray] = None, batch: int = 1):
    """Per-request generation — the scheduler's single-request oracle.

    Consumes the prompt with the SAME chunked-prefill + decode-ramp
    policy the continuous scheduler uses (full ``prefill_chunk`` chunks
    over the first L-1 tokens, remainder teacher-forced through decode),
    so a Scheduler run is token-identical to mapping this over requests
    under greedy sampling. Returns (tokens: np-able (g,) int32, reason).

    ``batch`` replicates the request over that many cache rows, so its
    programs have the batch shape of a Scheduler with ``batch`` slots.
    On the TPU, XLA compiles each batch size to its own rounding (a
    one-row program folds the batch dim away; tiles follow the row
    count), and bf16 logits of unrelated batch sizes differ in the last
    bits — enough to flip a near-tied greedy pick. There the oracle for
    an ``n``-slot Scheduler is ``generate(..., batch=n)``.
    """
    import numpy as np

    prompt = jnp.asarray(prompt, jnp.int32)
    ln = int(prompt.shape[0])
    assert ln >= 1, "empty prompt"
    slots = cache_slots or (ln + max_new_tokens)
    caches = T.init_caches(cfg, batch=batch, slots=slots, per_slot_pos=True)
    chunk_fn = jit_chunk_step(cfg)
    decode_fn = jit_slot_decode_step(cfg)
    if key is None:
        key = jax.random.PRNGKey(0)

    rows = lambda x: jnp.full((batch,), x, x.dtype)
    ctx = 0
    while ln - 1 - ctx >= prefill_chunk:
        toks = jnp.tile(prompt[None, ctx:ctx + prefill_chunk], (batch, 1))
        _, caches = chunk_fn(params, caches, toks, rows(jnp.int32(ctx)))
        ctx += prefill_chunk

    temps = rows(jnp.float32(temperature))
    tks = rows(jnp.int32(top_k))
    tps = rows(jnp.float32(top_p))
    out, reason, last = [], "length", None
    while len(out) < max_new_tokens:
        tok = prompt[ctx] if ctx < ln else last
        key, ks = jax.random.split(key)
        nxt, _, caches = decode_fn(params, caches, rows(tok)[:, None],
                                   rows(jnp.int32(ctx)), temps, ks, tks, tps)
        ctx += 1
        last = nxt[0]
        if ctx >= ln:                       # prompt consumed: real sample
            out.append(int(last))
            if eos_token is not None and out[-1] == eos_token:
                reason = "eos"
                break
    return np.asarray(out, np.int32), reason


# ---------------------------------------------------------------------------
# cache shardings (mirror transformer.init_caches structure)
# ---------------------------------------------------------------------------

def cache_shardings(cfg: ModelConfig, cache_shapes: Any):
    """NamedShardings for a cache pytree (from its eval_shape shapes).

    Mirrors the structure built by transformer.init_caches / emitted by the
    prefill scan: dict p<i> -> per-mixer state, every leaf stacked over
    periods (leading axis replicated).
    """
    from repro.models.attention import KVCache  # local: avoid import cycle

    def ns(leaf, *names):
        return named_sharding(leaf.shape, (None,) + tuple(names))

    out = {}
    for i, spec in enumerate(cfg.pattern):
        c = cache_shapes[f"p{i}"]
        entry = {}
        if spec.mixer == "attn":
            kv = c["attn"]
            entry["attn"] = KVCache(
                k=ns(kv.k, "cache_batch", "cache_seq", "cache_kv_heads",
                     "cache_head_dim"),
                v=ns(kv.v, "cache_batch", "cache_seq", "cache_kv_heads",
                     "cache_head_dim"),
                # shared pos is (periods, S); per-row pos (periods, B, S)
                pos=(ns(kv.pos, "cache_batch", None)
                     if len(kv.pos.shape) == 3 else ns(kv.pos, None)))
        elif spec.mixer == "rwkv":
            st = c["rwkv"]
            entry["rwkv"] = {
                "s": ns(st["s"], "cache_batch", "ssm_heads", None, None),
                "x_prev": ns(st["x_prev"], "cache_batch", None)}
            if "ffn_x" in c:
                entry["ffn_x"] = ns(c["ffn_x"], "cache_batch", None)
        elif spec.mixer == "mamba":
            st = c["mamba"]
            entry["mamba"] = {
                "conv": ns(st["conv"], "cache_batch", None, "ssm_channels"),
                "h": ns(st["h"], "cache_batch", "ssm_channels", "ssm_state")}
        out[f"p{i}"] = entry
    return out
