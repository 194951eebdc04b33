"""SlotManager — a fixed pool of cache slots for continuous batching.

The paper's runtime keeps a pool of workers saturated on dependency-bound
work; at LM-serving scale the scarce resource is the static-shape decode
cache. This module owns a pool of B cache slots over the engine's
KV/recurrent caches (``transformer.init_caches(per_slot_pos=True)``):
requests are *allocated* a slot, their prefilled state lives in that
slot's rows of every cache leaf, and eviction on EOS/max-tokens frees the
slot for the next admission — the batch shape never changes, only the
masks do.

Two storage backings sit behind one facade:

  * contiguous — every slot reserves its worst-case rows of every leaf
    (``cache_slots`` for global attention, the full ``window`` ring for
    sliding-window layers; the original layout).
  * paged      — attention KV leaves live in shared block pools
    (``serve.paging``: BlockPool + PageTable, blocks mapped on demand as
    a request's write position grows, freed at retire), so short
    requests stop stranding pool memory the way coarse-grain reservation
    strands the paper's L2. Keys sharing a view length form one
    *page-table group* over one pool: the global-KV group (view =
    ``cache_slots``) plus one ring-mode group per distinct window length
    (view = ``min(window, cache_slots)``; blocks map lazily while the
    request ramps up to ``window`` written positions, then the full ring
    stays resident). The fused steps gather a per-slot contiguous view
    through each group's page table before attending and scatter updates
    back (models.attention.paged_view / paged_writeback), keeping the
    one-fused-program-per-tick property — and every view is
    bit-identical to the contiguous layout, so greedy token streams are
    too.

With the per-row position layout every cache leaf carries the slot axis
at position 1 ((periods, B, ...)), so gather/scatter/reset are single-axis
indexing ops over the whole pytree, jitted once per sub-batch shape.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention, transformer as T
from repro.obs import metrics as obs_metrics
from repro.obs import trace as obs_trace
from repro.runtime import bucketing
from repro.serve import engine
from repro.serve.paging import (BlockPool, PageTable, PrefixIndex,
                                SwapEntry, SwapStore)

_SLOT_AXIS = 1      # every per_slot_pos cache leaf: (periods, B, ...)
# Chunk sub-batches never run a lone row: on the TPU a one-row program
# folds its batch dim away and rounds differently from the multi-row chunk
# programs, so a slot's stream would depend on how many other slots
# happened to prefill beside it.
MIN_CHUNK_ROWS = 2


@jax.jit
def _gather(caches, idx):
    return jax.tree_util.tree_map(
        lambda l: jnp.take(l, idx, axis=_SLOT_AXIS), caches)


# pool-sized updates donate the pool: without donation every scatter /
# reset / chunk step materializes a second full copy of the cache pool
@functools.partial(jax.jit, donate_argnums=(0,))
def _scatter(caches, sub, idx):
    return jax.tree_util.tree_map(
        lambda l, s: l.at[:, idx].set(s.astype(l.dtype)), caches, sub)


@functools.lru_cache(maxsize=None)
def _pooled_chunk_step(cfg: ModelConfig):
    """Fused gather -> chunk-prefill -> scatter over the pooled caches,
    returning (logits (m, C, V), caches).

    One jitted program (per cfg and sub-batch shape) instead of three
    dispatches: at small sub-batches the per-call overhead of separate
    gather/chunk/scatter calls rivals the chunk compute itself."""
    step = engine.make_chunk_step(cfg)

    @functools.partial(jax.jit, donate_argnums=(1,))
    def run(params, caches, idx, tokens, pos):
        sub = jax.tree_util.tree_map(
            lambda l: jnp.take(l, idx, axis=_SLOT_AXIS), caches)
        logits, sub = step(params, sub, tokens, pos)
        return logits, jax.tree_util.tree_map(
            lambda l, s: l.at[:, idx].set(s.astype(l.dtype)), caches, sub)

    return obs_trace.instrumented_jit(
        run, name=f"pooled_chunk_step[{cfg.name}]", prefix="serve.engine")


def _pad_rows(arr: np.ndarray, pad: int) -> np.ndarray:
    """Pad a saved block-bytes leaf (P, rows, ...) with ``pad`` zero rows
    — the payload for the trash rows a pow2-padded upload writes."""
    if pad == 0:
        return arr
    z = np.zeros((arr.shape[0], pad) + arr.shape[2:], arr.dtype)
    return np.concatenate([np.asarray(arr), z], axis=1)


@functools.partial(jax.jit, donate_argnums=(0,))
def _reset(caches, template, idx):
    """Write the zero-state template (slot axis = 1) into slots ``idx``."""

    def wipe(l, t):
        fresh = jnp.broadcast_to(
            t, t.shape[:_SLOT_AXIS] + (idx.shape[0],) + t.shape[2:])
        return l.at[:, idx].set(fresh.astype(l.dtype))

    return jax.tree_util.tree_map(wipe, caches, template)


def _attn_view_len(spec, cache_slots: int) -> int:
    """Positions an attention layer's slot view spans: the full
    ``cache_slots`` for global attention (or window >= cache_slots), the
    ring length for a shorter sliding window."""
    return min(cache_slots, spec.window) if spec.window else cache_slots


# ---------------------------------------------------------------------------
# storage backings
# ---------------------------------------------------------------------------

class _ContiguousBacking:
    """Every slot owns its worst-case rows of every leaf (the original
    reservation layout)."""

    is_paged = False

    def __init__(self, cfg: ModelConfig, num_slots: int, cache_slots: int):
        self.cfg = cfg
        self.num_slots = num_slots
        self.cache_slots = cache_slots
        self.caches = T.init_caches(cfg, num_slots, cache_slots,
                                    per_slot_pos=True)
        # one-slot zero template: reset = scatter-broadcast of this
        self._template = T.init_caches(cfg, 1, cache_slots,
                                       per_slot_pos=True)
        self.position_capacity = num_slots * cache_slots

    @property
    def total_rows(self) -> int:
        """Total attention cache positions reserved across the pool
        (global KV + window rings) — the equal-memory axis the windowed
        fig_serve arm compares allocators on (every attn leaf of one cfg
        has the same per-position byte cost)."""
        return sum(self.num_slots * _attn_view_len(s, self.cache_slots)
                   for s in self.cfg.pattern if s.mixer == "attn")

    def can_admit(self, prompt_len: int, prompt=None,
                  span: Optional[int] = None) -> bool:
        return True                     # a free slot is the only gate

    def fits_pool(self, n_positions: int) -> Optional[str]:
        return None                     # rows are pre-reserved

    def alloc_reset(self, slot: int, prompt_len: int, prompt=None,
                    span: Optional[int] = None) -> int:
        self.caches = _reset(self.caches, self._template,
                             jnp.asarray([slot], jnp.int32))
        return 0                        # no prefix sharing: prefill from 0

    def ensure(self, slot: int, upto_pos: int,
               write_from: Optional[int] = None) -> bool:
        return True                     # rows are pre-reserved

    def release_slot(self, slot: int) -> List[int]:
        return []                       # nothing block-granular to free

    def prefill_start(self, slot: int) -> int:
        return 0                        # no prefix sharing

    def register_prefix(self, slot: int, prompt, span: int,
                        upto_tokens: int) -> int:
        return 0                        # no prefix sharing

    def flush_prefix(self) -> int:
        return 0

    def gather(self, idx):
        return _gather(self.caches, jnp.asarray(idx, jnp.int32))

    def scatter(self, sub, idx):
        self.caches = _scatter(self.caches, sub,
                               jnp.asarray(idx, jnp.int32))

    def run_chunk(self, params, idx, tokens, pos):
        logits, self.caches = _pooled_chunk_step(self.cfg)(
            params, self.caches, jnp.asarray(idx, jnp.int32),
            jnp.asarray(tokens), jnp.asarray(pos))
        return logits

    def run_decode(self, params, tokens, pos, temps, key,
                   top_ks=None, top_ps=None):
        nxt, logits, self.caches = engine.jit_slot_decode_step(self.cfg)(
            params, self.caches, tokens, pos, temps, key, top_ks, top_ps)
        return nxt, logits

    def run_verify(self, params, tokens, pos, prompt_len, max_pos, score,
                   active, temps, top_ks, top_ps, key):
        out_tok, n, lp, self.caches = engine.jit_verify_step(self.cfg)(
            params, self.caches, tokens, pos, prompt_len, max_pos, score,
            active, temps, top_ks, top_ps, key)
        return out_tok, n, lp

    def stats(self) -> dict:
        return {"allocator": "contiguous"}


class _PageGroup:
    """One BlockPool + PageTable shared by the pattern keys whose slot
    views have the same length: the global-KV group (``view_len ==
    cache_slots``) or one ring group per distinct window length. Keys in
    a group advance in lockstep (every layer writes the same position
    each tick), so one logical->physical map serves them all — block b
    means rows [b*bs, (b+1)*bs) of every member key's flat pool."""

    def __init__(self, keys: List[str], num_slots: int, view_len: int,
                 cache_slots: int, block_size: int,
                 num_blocks: Optional[int]):
        self.keys = keys
        self.view_len = view_len
        self.ring = view_len < cache_slots
        if num_blocks is None:
            # equal-memory default: same position capacity as the dense
            # layout (num_slots full views)
            num_blocks = num_slots * (-(-view_len // block_size))
        self.pool = BlockPool(num_blocks, block_size)
        self.pt = PageTable(self.pool, num_slots, view_len, ring=self.ring)


class _PagedBacking:
    """Attention KV lives in shared block pools — one page-table group
    per view length (global KV + window rings when ``paged_window``);
    per-slot dense leaves (SSM state, rings kept dense when
    ``paged_window=False``) keep the contiguous layout. Each group's page
    table maps a slot's logical blocks to physical ones on demand; the
    fused steps read/write through flat row index vectors derived per
    group (gather-before-attend)."""

    is_paged = True

    def __init__(self, cfg: ModelConfig, num_slots: int, cache_slots: int,
                 block_size: int, num_blocks: Optional[int],
                 paged_window: bool = True,
                 num_window_blocks: Optional[int] = None,
                 swap_bytes_budget: Optional[int] = None,
                 prefix_sharing: bool = False,
                 prefix_align: Optional[int] = None,
                 prefix_capacity: int = 512,
                 create_arrays: bool = True,
                 dense_probe=None, template=None):
        self.cfg = cfg
        self.num_slots = num_slots
        self.cache_slots = cache_slots
        self.block_size = block_size
        # create_arrays=False is the per-shard mode (_ShardState): the
        # device arrays live stacked in the sharded owner; this instance
        # keeps only host state (pools, page tables, swaps, prefix index)
        # and redirects its device ops through the _dev_* hooks.
        if create_arrays:
            self.dense = T.init_caches(cfg, num_slots, cache_slots,
                                       per_slot_pos=True,
                                       paged_global_attn=True,
                                       paged_window_attn=paged_window)
            self._template = T.init_caches(cfg, 1, cache_slots,
                                           per_slot_pos=True,
                                           paged_global_attn=True,
                                           paged_window_attn=paged_window)
        else:
            self.dense = None
            self._template = template
        probe = self.dense if self.dense is not None else dense_probe
        # group the paged keys by view length: one pool + page table per
        # distinct length (the global group, rings per window size)
        by_view: Dict[int, List[str]] = {}
        self.key_view: Dict[str, int] = {}
        for i, spec in enumerate(cfg.pattern):
            key = f"p{i}"
            entry = probe.get(key)
            if not (entry and "attn" in entry and entry["attn"] is None):
                continue
            vl = _attn_view_len(spec, cache_slots)
            by_view.setdefault(vl, []).append(key)
            self.key_view[key] = vl
        self.groups: Dict[int, _PageGroup] = {
            vl: _PageGroup(keys, num_slots, vl, cache_slots, block_size,
                           num_blocks if vl == cache_slots
                           else num_window_blocks)
            for vl, keys in sorted(by_view.items(), reverse=True)}
        self.paged = ({
            key: attention.make_paged_cache(
                g.pool.num_blocks, block_size, cfg.num_kv_heads,
                cfg.head_dim, periods=cfg.num_periods)
            for g in self.groups.values() for key in g.keys}
            if create_arrays else None)
        g_global = self.groups.get(cache_slots)
        self.position_capacity = (g_global.pool.num_blocks * block_size
                                  if g_global else num_slots * cache_slots)
        self.swaps = SwapStore(max_bytes=swap_bytes_budget)
        # prefix sharing: only sound when EVERY layer's per-position state
        # is paged attention KV — a dense recurrent leaf (SSM state, an
        # unpaged ring) is a function of the whole prefix that skipping
        # prefill would leave stale
        shareable = (all(s.mixer == "attn" for s in cfg.pattern)
                     and len(self.key_view) == len(cfg.pattern))
        self.prefix: Optional[PrefixIndex] = (
            PrefixIndex(capacity=prefix_capacity)
            if prefix_sharing and shareable else None)
        # shared_pos must stay aligned to the scheduler's prefill-chunk
        # quantum (lcm'd with the block size by the caller): chunk-step
        # and decode-ramp KV are not interchangeable bitwise, and chunk
        # boundaries are absolute — so a sharer's remaining prefill must
        # chunk at the same offsets the unshared run would
        self.prefix_align = max(prefix_align or block_size, block_size)
        self._shared_pos: Dict[int, int] = {}   # slot -> prefill start
        self.cow_copies = 0         # CoW block copies, cumulative
        self.shared_chunks_mapped = 0   # chunks admitted read-shared
        # one-slot dense snapshot size is a constant (the template IS
        # that snapshot's shape): precompute for swap_bytes_estimate
        self._dense_slot_bytes = int(sum(
            l.nbytes for l in jax.tree_util.tree_leaves(self._template)))
        self._rows_cache: Optional[Dict[str, jnp.ndarray]] = None
        # bumped on every mapping change; the sharded owner keys its
        # concatenated rows cache on the tuple of shard epochs
        self._rows_epoch = 0

    def _invalidate_rows(self):
        self._rows_cache = None
        self._rows_epoch += 1

    # -- device-op hooks -------------------------------------------------
    # All device-array access funnels through these so _ShardState can
    # redirect a shard's ops into the owner's STACKED arrays (offsetting
    # slot indices and block rows) while the host-side bookkeeping above
    # stays byte-for-byte the same code path.

    def _dev_dense_reset(self, slot: int):
        self.dense = _reset(self.dense, self._template,
                            jnp.asarray([slot], jnp.int32))

    def _dev_dense_gather(self, slot: int):
        return jax.device_get(
            _gather(self.dense, jnp.asarray([slot], jnp.int32)))

    def _dev_dense_scatter(self, slot: int, sub):
        self.dense = _scatter(self.dense, sub,
                              jnp.asarray([slot], jnp.int32))

    def _dev_block_copy(self, g: "_PageGroup", src_rows, dst_rows):
        sub = {k: self.paged[k] for k in g.keys}
        self.paged.update(engine.copy_block_rows(sub, src_rows, dst_rows))

    def _dev_block_reset(self, g: "_PageGroup", rows):
        sub = {k: self.paged[k] for k in g.keys}
        self.paged.update(engine.reset_block_rows(sub, rows))

    def _dev_block_gather(self, g: "_PageGroup", rows):
        sub = {k: self.paged[k] for k in g.keys}
        return jax.device_get(engine.gather_block_rows(sub, rows))

    def _dev_block_upload(self, g: "_PageGroup", saved, rows):
        sub = {k: self.paged[k] for k in g.keys}
        self.paged.update(engine.upload_block_rows(sub, saved, rows))

    def _key_cache(self, key: str):
        """The flat paged array for ``key`` (shape queries only)."""
        return self.paged[key]

    @property
    def total_rows(self) -> int:
        """Attention cache positions actually allocated (physical block
        rows incl. each group's trash sentinel, plus any rings kept
        dense) — the equal-memory axis."""
        total = sum(len(g.keys) * (g.pool.num_blocks + 1) * self.block_size
                    for g in self.groups.values())
        for i, spec in enumerate(self.cfg.pattern):
            if spec.mixer == "attn" and f"p{i}" not in self.key_view:
                total += self.num_slots * _attn_view_len(spec,
                                                         self.cache_slots)
        return total

    # -- prefix sharing --------------------------------------------------

    def _share_cap(self, prompt_len: int, span: int) -> int:
        """Leading blocks of a ``prompt_len`` prompt eligible for
        read-sharing, given the request will write ``span`` positions
        total (prompt + generation budget). The block holding the last
        prompt position stays private (its KV is written during this
        request's prefill/decode), and a ring group only shares when the
        whole span fits its ring — a wrapped write would land inside the
        shared prefix, forcing a CoW the reserved-admission path could
        not absorb. 0 disables sharing for this request."""
        if self.prefix is None or prompt_len < 2:
            return 0
        cap = (prompt_len - 1) // self.block_size
        for g in self.groups.values():
            if g.ring:
                if span > g.view_len:
                    return 0
                cap = min(cap, g.view_len // self.block_size)
            else:
                cap = min(cap, g.pt.blocks_per_slot)
        return max(cap, 0)

    def _match_shared(self, prompt, prompt_len: int, span: int) \
            -> Tuple[int, List[Dict[int, int]], List[bytes]]:
        """Longest admissible shared prefix for ``prompt``: number of
        blocks (aligned down to the prefill-chunk quantum), the per-chunk
        {view_len: block} entries, and the chunk digests (LRU-refreshed —
        reclaim spares them)."""
        cap = self._share_cap(prompt_len, span)
        if cap <= 0:
            return 0, [], []
        keys = PrefixIndex.chunk_keys(prompt, self.block_size, cap)
        hit = self.prefix.match(keys)
        # align the shared region down to whole prefill chunks
        step = self.prefix_align // self.block_size
        n = (len(hit) // max(step, 1)) * max(step, 1)
        return n, hit[:n], keys

    def _reclaim(self, g: _PageGroup, need: int,
                 keep: Sequence[bytes] = ()) -> bool:
        """Free blocks for ``need`` new mappings in group ``g`` by
        evicting cold PrefixIndex entries (skipping ``keep`` — the chain
        the current admission is about to map). Evicting an entry only
        liberates blocks no live slot still shares; the loop runs until
        the group can map or the index is dry."""
        if self.prefix is None:
            return g.pt.can_map(need)
        keep_set = set(keep)
        while not g.pt.can_map(need):
            dropped = self.prefix.evict_lru(keep=keep_set)
            if dropped is None:
                return False
            for vl, b in dropped.items():
                self.groups[vl].pool.free(b)
        return True

    def prefill_start(self, slot: int) -> int:
        """First position ``slot``'s prefill must write — nonzero when
        admission mapped a shared prefix (its KV is already resident)."""
        return self._shared_pos.get(slot, 0)

    def register_prefix(self, slot: int, prompt, span: int,
                        upto_tokens: int) -> int:
        """Publish ``slot``'s fully-prefilled leading blocks into the
        PrefixIndex (called once prefill completes). Only positions
        consumed via chunk steps or inherited shared blocks
        (``upto_tokens``) are eligible — decode-ramp KV is not
        bitwise-interchangeable with the chunk-step KV an unshared run
        would compute. Each published block gains an index-held
        reference, so it outlives this donor. Returns entries
        inserted."""
        if self.prefix is None:
            return 0
        cap = min(self._share_cap(len(prompt), span),
                  max(upto_tokens, 0) // self.block_size)
        if cap <= 0:
            return 0
        keys = PrefixIndex.chunk_keys(prompt, self.block_size, cap)
        inserted = 0
        for i, key in enumerate(keys):
            blocks: Dict[int, int] = {}
            for vl, g in self.groups.items():
                b = int(g.pt.table[slot, i])
                if b == g.pt.trash:
                    blocks = {}
                    break
                blocks[vl] = b
            if not blocks:
                break
            for vl, b in blocks.items():
                self.groups[vl].pool.ref(b)
            if self.prefix.publish(key, blocks):
                inserted += 1
            else:           # already indexed (first publisher won)
                for vl, b in blocks.items():
                    self.groups[vl].pool.free(b)
        while len(self.prefix) > self.prefix.capacity:
            dropped = self.prefix.evict_lru()
            for vl, b in dropped.items():
                self.groups[vl].pool.free(b)
        return inserted

    def flush_prefix(self) -> int:
        """Drop every PrefixIndex entry (releasing the index's block
        references) — the test/leak-check hook: after a flush and full
        retire, blocks_used must be 0 again."""
        if self.prefix is None:
            return 0
        n = 0
        while True:
            dropped = self.prefix.evict_lru()
            if dropped is None:
                return n
            for vl, b in dropped.items():
                self.groups[vl].pool.free(b)
            n += 1

    def prefix_holds(self) -> Dict[int, np.ndarray]:
        """Per-group index-held refcounts (check_invariants helper)."""
        if self.prefix is None:
            return {vl: np.zeros(g.pool.num_blocks, np.int64)
                    for vl, g in self.groups.items()}
        return self.prefix.holds(
            {vl: g.pool.num_blocks for vl, g in self.groups.items()})

    # -- page-table lifecycle -------------------------------------------

    def can_admit(self, prompt_len: int, prompt=None,
                  span: Optional[int] = None) -> bool:
        n = max(prompt_len, 1)
        shared, _, keys = (self._match_shared(prompt, len(prompt),
                                              span or prompt_len)
                           if prompt is not None and self.prefix is not None
                           else (0, [], []))
        return all(self._reclaim(g, g.pt.blocks_for(n) - shared, keep=keys)
                   for g in self.groups.values())

    def fits_pool(self, n_positions: int) -> Optional[str]:
        """None if a request spanning ``n_positions`` could be mapped on
        an EMPTY pool (every group), else why not — the submit-time
        feasibility check behind the scheduler's progress guarantee.
        Ring groups clamp via blocks_for: a ring never needs more than
        the full ring resident."""
        for g in self.groups.values():
            need = g.pt.blocks_for(n_positions)
            if need > g.pool.num_blocks:
                what = (f"window-{g.view_len} ring" if g.ring
                        else "global-KV")
                return (f"request needs {need} {what} blocks > pool "
                        f"{g.pool.num_blocks}")
        return None

    def alloc_reset(self, slot: int, prompt_len: int, prompt=None,
                    span: Optional[int] = None) -> int:
        """Reset ``slot`` and map its prompt blocks. With prefix sharing
        on and ``prompt`` given, the longest indexed chunk-aligned
        prefix is mapped read-shared first (its KV is already resident —
        prefill starts past it); the remainder maps private as usual.
        Returns the prefill start position (0 without a hit)."""
        self._dev_dense_reset(slot)
        shared_pos = 0
        if self.prefix is not None and prompt is not None:
            n, hit, _ = self._match_shared(prompt, len(prompt),
                                           span or prompt_len)
            if n:
                for vl, g in self.groups.items():
                    g.pt.map_shared(slot, [e[vl] for e in hit])
                shared_pos = n * self.block_size
                self.shared_chunks_mapped += n
                self._invalidate_rows()
        self._shared_pos[slot] = shared_pos
        ok = self.ensure(slot, max(prompt_len, 1) - 1)
        if not ok:
            raise RuntimeError(
                "alloc_reset after can_admit ran out of blocks")
        return shared_pos

    def _cow_copy(self, g: _PageGroup, pairs: List[Tuple[int, int]]):
        """Duplicate each (old, new) physical block pair on device —
        pow2-padded with trash->trash pairs like every block-rows
        kernel."""
        n = bucketing.round_up_pow2(len(pairs), 1)
        srcs = [p[0] for p in pairs] + [g.pt.trash] * (n - len(pairs))
        dsts = [p[1] for p in pairs] + [g.pt.trash] * (n - len(pairs))
        self._dev_block_copy(
            g, jnp.asarray(PageTable.block_rows(srcs, self.block_size)),
            jnp.asarray(PageTable.block_rows(dsts, self.block_size)))
        self.cow_copies += len(pairs)
        self._invalidate_rows()

    def ensure(self, slot: int, upto_pos: int,
               write_from: Optional[int] = None) -> bool:
        """Map (and zero) every block covering positions [0, upto_pos] in
        every group — ring groups clamp to their ring, so past the window
        they are a no-op — and copy-on-write any *shared* block the
        upcoming write over [``write_from``, ``upto_pos``] (default: just
        ``upto_pos``, the decode case) would touch: the writer gets a
        private copy, so no sharer ever observes the write. False on pool
        exhaustion (the scheduler's preempt-on-OOB path); blocks mapped
        or copied so far stay, and a retry after preemption is
        idempotent."""
        lo = upto_pos if write_from is None else write_from
        ok_all = True
        for g in self.groups.values():
            if g.pool.shared_count:
                pairs: List[Tuple[int, int]] = []
                for lb in g.pt.write_blocks(slot, lo, upto_pos):
                    if not g.pt.is_shared(slot, lb):
                        continue
                    got = g.pt.cow_block(slot, lb)
                    if got is None and self._reclaim(g, 1):
                        got = g.pt.cow_block(slot, lb)
                    if got is None:
                        ok_all = False
                        break
                    pairs.append(got)
                if pairs:
                    self._cow_copy(g, pairs)
            ok, new = g.pt.ensure(slot, upto_pos)
            if not ok and self._reclaim(g, 1):
                ok, more = g.pt.ensure(slot, upto_pos)
                new = new + more
            if new:
                # pow2-pad the reset batch with trash-block rows so the
                # jitted reset compiles O(log blocks_per_slot) shapes,
                # not one per count
                n = bucketing.round_up_pow2(len(new), 1)
                blocks = list(new) + [g.pt.trash] * (n - len(new))
                rows = PageTable.block_rows(blocks, self.block_size)
                self._dev_block_reset(g, jnp.asarray(rows))
                self._invalidate_rows()
            ok_all = ok_all and ok
        return ok_all

    def release_slot(self, slot: int) -> List[int]:
        freed: List[int] = []
        for g in self.groups.values():
            freed += g.pt.free_slot(slot)
        self._shared_pos.pop(slot, None)
        if freed:
            self._invalidate_rows()
        return freed

    # -- swap-out preemption --------------------------------------------

    def _swap_rows(self, g: _PageGroup, blocks: List[int]) -> jnp.ndarray:
        """Flat rows for a block list, pow2-padded with trash rows so the
        jitted gather/upload compile O(log blocks_per_slot) shapes."""
        n = bucketing.round_up_pow2(len(blocks), 1)
        padded = list(blocks) + [g.pt.trash] * (n - len(blocks))
        return jnp.asarray(PageTable.block_rows(padded, self.block_size))

    def swap_bytes_estimate(self, slot: int) -> int:
        """Bytes a swap_out of ``slot`` would park host-side — computed
        from shapes BEFORE any device gather, so a SwapStore budget
        rejection costs nothing."""
        bs = self.block_size
        total = self._dense_slot_bytes
        for g in self.groups.values():
            nb = g.pt.mapped_blocks(slot)
            for key in g.keys:
                c = self._key_cache(key)
                row = (int(np.prod(c.k.shape[2:])) * c.k.dtype.itemsize
                       + int(np.prod(c.v.shape[2:])) * c.v.dtype.itemsize
                       + c.pos.dtype.itemsize)
                total += nb * bs * row * c.k.shape[0]
        return total

    def swap_out(self, slot: int, rid: int) -> Optional[int]:
        """Copy ``slot``'s mapped block bytes (every group) + dense
        leaves to the host SwapStore (keyed by ``rid``) and free the
        physical blocks — the victim's decode work survives eviction.
        Returns bytes moved, or None when the store's byte budget cannot
        hold the entry (nothing is gathered or freed; the scheduler falls
        back to recompute-preemption for this victim)."""
        if self.swaps.max_bytes is not None \
                and not self.swaps.can_hold(self.swap_bytes_estimate(slot)):
            self.swaps.reject()         # the store owns the count
            return None
        bs = self.block_size
        blocks: Dict[int, int] = {}
        paged_host: Dict[str, attention.KVCache] = {}
        for vl, g in self.groups.items():
            phys = [int(b) for b in g.pt.table[slot] if b != g.pt.trash]
            blocks[vl] = len(phys)
            if phys and g.keys:
                keep = len(phys) * bs
                got = self._dev_block_gather(g, self._swap_rows(g, phys))
                paged_host.update({
                    key: attention.KVCache(k=c.k[:, :keep], v=c.v[:, :keep],
                                           pos=c.pos[:, :keep])
                    for key, c in got.items()})
            # shared blocks are RELEASED, not stolen: the bytes were just
            # gathered (a copy), and swap_out only drops this slot's
            # reference — sharers and the PrefixIndex keep theirs
            _, released = g.pt.swap_out(slot)
            if sorted(released) != sorted(phys):
                raise RuntimeError(f"swap_out released {released} != "
                                   f"mapped {phys} (group {vl})")
            if released:
                self._invalidate_rows()
        dense_host = self._dev_dense_gather(slot)
        self._shared_pos.pop(slot, None)
        return self.swaps.put(rid, SwapEntry(
            blocks=blocks, paged=paged_host, dense=dense_host))

    def can_admit_swapped(self, rid: int) -> bool:
        entry = self.swaps.get(rid)
        return all(self._reclaim(g, entry.blocks.get(vl, 0))
                   for vl, g in self.groups.items())

    def swap_in(self, slot: int, rid: int) -> int:
        """Resume ``rid`` in (free, unreset) ``slot``: map fresh blocks
        for each group's saved logical prefix, upload the saved bytes,
        scatter the dense snapshot — every cache row the request had
        written reads bit-identically to the never-preempted layout.
        Returns bytes moved. Caller guarantees can_admit_swapped just
        held."""
        bs = self.block_size
        entry = self.swaps.pop(rid)
        for vl, g in self.groups.items():
            nb = entry.blocks.get(vl, 0)
            if not nb:
                continue
            new = g.pt.swap_in(slot, nb)
            if new is None:
                raise RuntimeError(
                    "swap_in after can_admit_swapped ran out of blocks")
            if g.keys:
                rows = self._swap_rows(g, new)
                pad = int(rows.shape[0]) - nb * bs
                saved = {
                    key: attention.KVCache(
                        k=_pad_rows(entry.paged[key].k, pad),
                        v=_pad_rows(entry.paged[key].v, pad),
                        pos=_pad_rows(entry.paged[key].pos, pad))
                    for key in g.keys}
                self._dev_block_upload(g, saved, rows)
            self._invalidate_rows()
        self._dev_dense_scatter(slot, entry.dense)
        self._shared_pos[slot] = 0      # resumed mappings are private
        return entry.nbytes

    # -- device-facing row vectors --------------------------------------

    def _rows_all(self) -> Dict[str, jnp.ndarray]:
        if self._rows_cache is None:
            per_group = {vl: jnp.asarray(g.pt.rows())
                         for vl, g in self.groups.items()}
            self._rows_cache = {key: per_group[vl]
                                for key, vl in self.key_view.items()}
        return self._rows_cache

    def _rows_for(self, idx) -> Dict[str, jnp.ndarray]:
        per_group = {vl: jnp.asarray(g.pt.rows(idx))
                     for vl, g in self.groups.items()}
        return {key: per_group[vl] for key, vl in self.key_view.items()}

    # -- data movement ---------------------------------------------------

    def gather(self, idx):
        sub = _gather(self.dense, jnp.asarray(idx, jnp.int32))
        rows = self._rows_for(idx)
        for key, flat in self.paged.items():
            sub[key] = dict(sub[key])
            sub[key]["attn"] = attention.paged_view(
                flat, rows[key],
                attention.paged_live_rows(flat, self.block_size))
        return sub

    def scatter(self, sub, idx):
        """Write a gathered sub-tree back. View positions whose blocks are
        unmapped scatter into the trash block (dropped) — callers only
        write back what gather handed out, so mapped data round-trips."""
        rows = self._rows_for(idx)
        stripped = {}
        for key, entry in sub.items():
            if key in self.paged:
                entry = dict(entry)
                self.paged[key] = attention.paged_writeback(
                    self.paged[key], entry["attn"], rows[key])
                entry["attn"] = None
            stripped[key] = entry
        self.dense = _scatter(self.dense, stripped,
                              jnp.asarray(idx, jnp.int32))

    def run_chunk(self, params, idx, tokens, pos):
        rows = self._rows_for(idx)
        logits, self.dense, self.paged = engine.jit_paged_chunk_step(
            self.cfg)(
            params, self.dense, self.paged, jnp.asarray(idx, jnp.int32),
            rows, jnp.asarray(tokens), jnp.asarray(pos), self.block_size)
        return logits

    def run_decode(self, params, tokens, pos, temps, key,
                   top_ks=None, top_ps=None):
        b = tokens.shape[0]
        if top_ks is None:
            top_ks = jnp.zeros((b,), jnp.int32)
        if top_ps is None:
            top_ps = jnp.ones((b,), jnp.float32)
        nxt, logits, self.dense, self.paged = engine.jit_paged_decode_step(
            self.cfg)(params, self.dense, self.paged, self._rows_all(),
                      tokens, pos, temps, key, top_ks, top_ps,
                      self.block_size)
        return nxt, logits

    def run_verify(self, params, tokens, pos, prompt_len, max_pos, score,
                   active, temps, top_ks, top_ps, key):
        out_tok, n, lp, self.dense, self.paged = engine.jit_paged_verify_step(
            self.cfg)(params, self.dense, self.paged, self._rows_all(),
                      tokens, pos, prompt_len, max_pos, score, active,
                      temps, top_ks, top_ps, key, self.block_size)
        return out_tok, n, lp

    def stats(self) -> dict:
        used = sum(g.pool.used_count for g in self.groups.values())
        total = sum(g.pool.num_blocks for g in self.groups.values())
        prefix_stats = (self.prefix.stats() if self.prefix is not None
                        else {"prefix_entries": 0, "prefix_lookups": 0,
                              "prefix_hit_chunks": 0, "prefix_published": 0,
                              "prefix_evicted": 0})
        out = {"allocator": "paged",
               "page_groups": len(self.groups),
               "blocks_total": total,
               "blocks_used": used,
               "blocks_free": total - used,
               "block_size": self.block_size,
               "block_utilization": used / max(total, 1),
               "shared_blocks": sum(g.pool.shared_count
                                    for g in self.groups.values()),
               "cow_copies": self.cow_copies,
               "prefix_shared_chunks": self.shared_chunks_mapped,
               **prefix_stats,
               **self.swaps.stats()}
        for vl, g in self.groups.items():
            if g.ring:
                out[f"ring{vl}_blocks_total"] = g.pool.num_blocks
                out[f"ring{vl}_blocks_used"] = g.pool.used_count
        return out

    def metrics(self) -> dict:
        """Registry 'paging' provider: the numeric stats() keys."""
        return {k: v for k, v in self.stats().items() if k != "allocator"}


# ---------------------------------------------------------------------------
# the sharded backing: per-shard block pools over stacked device arrays
# ---------------------------------------------------------------------------

class _ShardState(_PagedBacking):
    """Host-side state of ONE shard of a sharded pool: its own
    BlockPool/PageTable groups, SwapStore, PrefixIndex and shared-pos map
    — block ids never cross shards, so paging, CoW sharing, swap and the
    window rings stay shard-local by construction. Device ops are
    redirected into the owner's STACKED arrays: slot indices offset by
    the shard's dense segment, block rows by its flat-pool segment."""

    def __init__(self, owner: "_ShardedPagedBacking", shard: int,
                 *args, **kw):
        self._owner = owner
        self.shard = shard
        super().__init__(*args, create_arrays=False,
                         dense_probe=owner.dense,
                         template=owner._template, **kw)

    # -- offsets ---------------------------------------------------------

    def _gslot(self, slot: int) -> jnp.ndarray:
        return jnp.asarray([self.shard * self.num_slots + slot], jnp.int32)

    def _row_base(self, g: _PageGroup) -> int:
        return self.shard * (g.pool.num_blocks + 1) * self.block_size

    # -- device-op hooks over the owner's stacked arrays -----------------

    def _dev_dense_reset(self, slot: int):
        o = self._owner
        o.dense = _reset(o.dense, o._template, self._gslot(slot))

    def _dev_dense_gather(self, slot: int):
        return jax.device_get(
            _gather(self._owner.dense, self._gslot(slot)))

    def _dev_dense_scatter(self, slot: int, sub):
        o = self._owner
        o.dense = _scatter(o.dense, sub, self._gslot(slot))

    def _dev_block_copy(self, g: _PageGroup, src_rows, dst_rows):
        o, base = self._owner, self._row_base(g)
        sub = {k: o.paged[k] for k in g.keys}
        o.paged.update(engine.copy_block_rows(sub, src_rows + base,
                                              dst_rows + base))

    def _dev_block_reset(self, g: _PageGroup, rows):
        o = self._owner
        sub = {k: o.paged[k] for k in g.keys}
        o.paged.update(engine.reset_block_rows(
            sub, rows + self._row_base(g)))

    def _dev_block_gather(self, g: _PageGroup, rows):
        o = self._owner
        sub = {k: o.paged[k] for k in g.keys}
        return jax.device_get(engine.gather_block_rows(
            sub, rows + self._row_base(g)))

    def _dev_block_upload(self, g: _PageGroup, saved, rows):
        o = self._owner
        sub = {k: o.paged[k] for k in g.keys}
        o.paged.update(engine.upload_block_rows(
            sub, saved, rows + self._row_base(g)))

    def _key_cache(self, key: str):
        return self._owner.paged[key]


class _ShardedPagedBacking:
    """The paged slot pool sharded over a 1-D device mesh.

    Stacked device arrays hold every shard's segment back-to-back —
    dense leaves carry ``num_shards * slots_per_shard`` slots on the
    slot axis; each paged flat pool holds ``num_shards`` segments of
    ``(num_blocks + 1) * block_size`` rows, each segment ending in its
    OWN trash block — and one fused program per tick spans all shards
    (``engine.jit_sharded_*_step``: a delegate to the unsharded program
    at ``num_shards == 1``, vmap over the shard axis without a mesh,
    ``shard_map`` over ``mesh``'s axis with one). All host bookkeeping
    (pools, page tables, swap stores, prefix indices) lives per shard in
    ``_ShardState``s: a block id is only ever meaningful within its
    shard, so nothing block-granular crosses shards — the ONLY cross-
    shard channel is ``migrate_swapped``, which hands a host-side
    SwapEntry between shard SwapStores (work-stealing a preempted
    request without losing its prefill progress)."""

    is_paged = True
    is_sharded = True

    def __init__(self, cfg: ModelConfig, num_slots: int, cache_slots: int,
                 block_size: int, num_blocks: Optional[int],
                 paged_window: bool = True,
                 num_window_blocks: Optional[int] = None,
                 swap_bytes_budget: Optional[int] = None,
                 prefix_sharing: bool = False,
                 prefix_align: Optional[int] = None,
                 prefix_capacity: int = 512, *,
                 num_shards: int = 1, mesh=None,
                 axis: Optional[str] = None):
        engine._check_shard_mesh(num_shards, mesh, axis)
        if num_slots % num_shards:
            raise ValueError(f"num_slots={num_slots} must divide evenly "
                             f"over {num_shards} shard(s)")
        self.cfg = cfg
        self.num_slots = num_slots
        self.num_shards = num_shards
        self.slots_per_shard = num_slots // num_shards
        self.cache_slots = cache_slots
        self.block_size = block_size
        self.mesh = mesh
        self.axis = axis
        self.dense = T.init_caches(cfg, num_slots, cache_slots,
                                   per_slot_pos=True, paged_global_attn=True,
                                   paged_window_attn=paged_window)
        self._template = T.init_caches(cfg, 1, cache_slots,
                                       per_slot_pos=True,
                                       paged_global_attn=True,
                                       paged_window_attn=paged_window)
        # num_blocks / num_window_blocks / swap_bytes_budget are PER
        # SHARD: mesh scaling holds per-device cache memory constant and
        # multiplies capacity by the shard count
        self.shards = [
            _ShardState(self, s, cfg, self.slots_per_shard, cache_slots,
                        block_size, num_blocks, paged_window=paged_window,
                        num_window_blocks=num_window_blocks,
                        swap_bytes_budget=swap_bytes_budget,
                        prefix_sharing=prefix_sharing,
                        prefix_align=prefix_align,
                        prefix_capacity=prefix_capacity)
            for s in range(num_shards)]
        s0 = self.shards[0]
        self.key_view = s0.key_view
        self.paged = {
            key: attention.make_paged_cache(
                num_shards * (g.pool.num_blocks + 1) - 1, block_size,
                cfg.num_kv_heads, cfg.head_dim, periods=cfg.num_periods)
            for g in s0.groups.values() for key in g.keys}
        self.position_capacity = num_shards * s0.position_capacity
        self._rows_cache: Optional[Dict[str, jnp.ndarray]] = None
        self._rows_key: Optional[Tuple[int, ...]] = None

    @property
    def total_rows(self) -> int:
        return self.num_shards * self.shards[0].total_rows

    def _loc(self, slot: int) -> Tuple[_ShardState, int]:
        return (self.shards[slot // self.slots_per_shard],
                slot % self.slots_per_shard)

    def shard_of(self, slot: int) -> int:
        return slot // self.slots_per_shard

    def shard_free_blocks(self, shard: int) -> int:
        """Free blocks across the shard's groups — the least-loaded
        placement signal."""
        return sum(g.pool.num_blocks - g.pool.used_count
                   for g in self.shards[shard].groups.values())

    # -- routed lifecycle (slot ids are GLOBAL; block state shard-local) -

    def can_admit(self, prompt_len: int, prompt=None,
                  span: Optional[int] = None, shard: int = 0) -> bool:
        return self.shards[shard].can_admit(prompt_len, prompt=prompt,
                                            span=span)

    def fits_pool(self, n_positions: int) -> Optional[str]:
        return self.shards[0].fits_pool(n_positions)

    def alloc_reset(self, slot: int, prompt_len: int, prompt=None,
                    span: Optional[int] = None) -> int:
        sh, loc = self._loc(slot)
        return sh.alloc_reset(loc, prompt_len, prompt=prompt, span=span)

    def prefill_start(self, slot: int) -> int:
        sh, loc = self._loc(slot)
        return sh.prefill_start(loc)

    def register_prefix(self, slot: int, prompt, span: int,
                        upto_tokens: int) -> int:
        sh, loc = self._loc(slot)
        return sh.register_prefix(loc, prompt, span, upto_tokens)

    def flush_prefix(self) -> int:
        return sum(sh.flush_prefix() for sh in self.shards)

    def ensure(self, slot: int, upto_pos: int,
               write_from: Optional[int] = None) -> bool:
        sh, loc = self._loc(slot)
        return sh.ensure(loc, upto_pos, write_from=write_from)

    def release_slot(self, slot: int) -> List[int]:
        sh, loc = self._loc(slot)
        return sh.release_slot(loc)

    # -- swap + cross-shard migration ------------------------------------

    def swap_bytes_estimate(self, slot: int) -> int:
        sh, loc = self._loc(slot)
        return sh.swap_bytes_estimate(loc)

    def swap_out(self, slot: int, rid: int) -> Optional[int]:
        sh, loc = self._loc(slot)
        return sh.swap_out(loc, rid)

    def swapped_shard(self, rid: int) -> Optional[int]:
        for s, sh in enumerate(self.shards):
            if rid in sh.swaps:
                return s
        return None

    def can_admit_swapped(self, rid: int) -> bool:
        s = self.swapped_shard(rid)
        return s is not None and self.shards[s].can_admit_swapped(rid)

    def swap_in(self, slot: int, rid: int) -> int:
        sh, loc = self._loc(slot)
        if rid not in sh.swaps:
            raise RuntimeError(
                f"rid {rid} is not swapped on shard {sh.shard} — "
                "migrate_swapped before a cross-shard swap_in")
        return sh.swap_in(loc, rid)

    def migrate_swapped(self, rid: int, dst_shard: int) -> bool:
        """Move ``rid``'s parked SwapEntry from its home shard's store to
        ``dst_shard``'s (the work-stealing path: host bytes change owner,
        nothing touches the device, prefill progress is preserved).
        False when the entry isn't swapped, is already there, or the
        destination's byte budget can't hold it — the caller simply
        leaves the request where it is."""
        src = self.swapped_shard(rid)
        if src is None or src == dst_shard:
            return False
        dst = self.shards[dst_shard].swaps
        entry = self.shards[src].swaps.get(rid)
        if dst.max_bytes is not None and not dst.can_hold(entry.nbytes):
            return False
        dst.migrate_in(rid, self.shards[src].swaps.migrate_out(rid))
        return True

    def can_steal_swapped(self, rid: int, dst_shard: int) -> bool:
        """True when ``dst_shard`` could hold AND admit ``rid``'s parked
        entry right now: its SwapStore budget fits the bytes and every
        page-table group can reclaim the saved block count. The steal
        pass checks this BEFORE migrating, so a steal never strands an
        entry on a shard that can't admit it."""
        src = self.swapped_shard(rid)
        if src is None or src == dst_shard:
            return False
        entry = self.shards[src].swaps.get(rid)
        dst = self.shards[dst_shard]
        if dst.swaps.max_bytes is not None \
                and not dst.swaps.can_hold(entry.nbytes):
            return False
        return all(dst._reclaim(g, entry.blocks.get(vl, 0))
                   for vl, g in dst.groups.items())

    # -- device-facing row vectors ---------------------------------------

    def _rows_all(self) -> Dict[str, jnp.ndarray]:
        """Shard-LOCAL rows, concatenated (num_slots, V) per key — the
        fused sharded steps split the slot axis so each shard indexes its
        own flat-pool segment. Cached on the tuple of shard epochs."""
        key = tuple(sh._rows_epoch for sh in self.shards)
        if self._rows_cache is None or self._rows_key != key:
            per = [sh._rows_all() for sh in self.shards]
            self._rows_cache = {
                k: jnp.concatenate([p[k] for p in per], axis=0)
                for k in per[0]}
            self._rows_key = key
        return self._rows_cache

    def _rows_for(self, idx) -> Dict[str, jnp.ndarray]:
        """GLOBAL stacked-array rows for slots ``idx`` (host gather /
        scatter paths): shard-local rows offset into the shard's flat
        segment, with each shard's local trash rows canonicalized onto
        the stacked pool's LAST block — paged_view/paged_writeback treat
        rows past ``total - block_size`` as trash, so per-shard trash
        keeps masking globally."""
        n, bs = self.num_shards, self.block_size
        per_vl: Dict[int, jnp.ndarray] = {}
        for vl, g0 in self.shards[0].groups.items():
            nb = g0.pool.num_blocks
            seg, live = (nb + 1) * bs, nb * bs
            rows = []
            for slot in idx:
                sh, loc = self._loc(slot)
                r = np.asarray(sh.groups[vl].pt.rows([loc]))[0]
                rows.append(np.where(r >= live,
                                     n * seg - bs + (r - live),
                                     sh.shard * seg + r))
            per_vl[vl] = jnp.asarray(np.stack(rows))
        return {k: per_vl[vl] for k, vl in self.key_view.items()}

    # gather/scatter operate on self.dense/self.paged/self._rows_for with
    # GLOBAL rows — the _PagedBacking bodies apply verbatim
    gather = _PagedBacking.gather
    scatter = _PagedBacking.scatter

    # -- fused steps ------------------------------------------------------

    def _keys_for(self, key) -> jnp.ndarray:
        """(num_shards, 2) per-shard PRNG keys. One shard passes the key
        through untouched (the delegate path consumes the same bits the
        unsharded step would — bit-identical sampled streams); more
        shards split it (sampled streams legitimately diverge across
        shard counts; greedy is the cross-count correctness bar)."""
        return key[None] if self.num_shards == 1 \
            else jax.random.split(key, self.num_shards)

    def run_chunk(self, params, idx, tokens, pos):
        """Chunk-prefill slots ``idx`` (GLOBAL ids, UNPADDED — unlike the
        single-pool backing, the owner pads per shard: each shard's
        sub-batch pads by repeating its first entry to a common pow2
        width; a shard with nothing to prefill runs dead — its rows point
        at its trash block and its dense writes are reverted in-program).
        Returns per-position logits (len(idx), C, V) in input order."""
        n, k = self.num_shards, self.slots_per_shard
        tokens = np.asarray(tokens)
        pos_in = np.asarray(pos)
        per: List[List[int]] = [[] for _ in range(n)]
        t_of = np.zeros(len(idx), np.int32)
        for j, slot in enumerate(idx):
            s = slot // k
            t_of[j] = len(per[s])
            per[s].append(j)
        m = bucketing.round_up_pow2(max(len(p) for p in per),
                                    MIN_CHUNK_ROWS)
        idx_a = np.zeros((n, m), np.int32)
        tok_a = np.zeros((n, m) + tokens.shape[1:], tokens.dtype)
        pos_a = np.zeros((n, m), pos_in.dtype)
        live = np.zeros((n,), bool)
        shard_rows: List[Dict[str, jnp.ndarray]] = []
        for s in range(n):
            js = per[s]
            if js:
                live[s] = True
                js = js + [js[0]] * (m - len(js))   # pad-by-repeat
                loc = [idx[j] - s * k for j in js]
                idx_a[s] = loc
                tok_a[s] = tokens[js]
                pos_a[s] = pos_in[js]
                shard_rows.append(self.shards[s]._rows_for(loc))
            else:
                sh = self.shards[s]
                shard_rows.append({
                    key: jnp.full(
                        (m, vl),
                        sh.groups[vl].pool.num_blocks * self.block_size,
                        jnp.int32)
                    for key, vl in self.key_view.items()})
        rows = {key: jnp.stack([sr[key] for sr in shard_rows])
                for key in self.key_view}
        step = engine.jit_sharded_chunk_step(self.cfg, n, self.block_size,
                                             self.mesh, self.axis)
        logits, self.dense, self.paged = step(
            params, self.dense, self.paged, jnp.asarray(idx_a), rows,
            jnp.asarray(tok_a), jnp.asarray(pos_a), jnp.asarray(live))
        s_of = jnp.asarray([slot // k for slot in idx])
        return logits[s_of, jnp.asarray(t_of)]

    def run_decode(self, params, tokens, pos, temps, key,
                   top_ks=None, top_ps=None):
        b = tokens.shape[0]
        if top_ks is None:
            top_ks = jnp.zeros((b,), jnp.int32)
        if top_ps is None:
            top_ps = jnp.ones((b,), jnp.float32)
        step = engine.jit_sharded_decode_step(
            self.cfg, self.num_shards, self.block_size, self.mesh,
            self.axis)
        nxt, logits, self.dense, self.paged = step(
            params, self.dense, self.paged, self._rows_all(), tokens, pos,
            temps, self._keys_for(key), top_ks, top_ps)
        return nxt, logits

    def run_verify(self, params, tokens, pos, prompt_len, max_pos, score,
                   active, temps, top_ks, top_ps, key):
        step = engine.jit_sharded_verify_step(
            self.cfg, self.num_shards, self.block_size, self.mesh,
            self.axis)
        out_tok, acc, lp, self.dense, self.paged = step(
            params, self.dense, self.paged, self._rows_all(), tokens, pos,
            prompt_len, max_pos, score, active, temps, top_ks, top_ps,
            self._keys_for(key))
        return out_tok, acc, lp

    # -- stats ------------------------------------------------------------

    def stats(self) -> dict:
        agg: Dict[str, object] = {}
        for sh in self.shards:
            for k2, v in sh.stats().items():
                if isinstance(v, bool) or not isinstance(v, (int, float)):
                    continue
                agg[k2] = agg.get(k2, 0) + v
        agg["allocator"] = "paged"
        agg["page_groups"] = len(self.shards[0].groups)
        agg["block_size"] = self.block_size
        agg["block_utilization"] = (agg["blocks_used"]
                                    / max(agg["blocks_total"], 1))
        agg["num_shards"] = self.num_shards
        return agg

    metrics = _PagedBacking.metrics

    def shard_metrics(self) -> dict:
        """Per-shard block/swap gauges, ``shard<i>.``-prefixed (the
        SlotManager adds slot occupancy; the scheduler adds placement and
        steal counters on top under ``serve.shard``)."""
        out = {}
        for s, sh in enumerate(self.shards):
            st = sh.stats()
            out[f"shard{s}.blocks_free"] = st["blocks_free"]
            out[f"shard{s}.blocks_used"] = st["blocks_used"]
            out[f"shard{s}.swapped_held"] = st["swapped_held"]
        return out


# ---------------------------------------------------------------------------
# the facade
# ---------------------------------------------------------------------------

class SlotManager:
    """Fixed pool of ``num_slots`` decode-cache slots.

    Host-side bookkeeping (free list, per-slot owner + validity mask)
    plus jitted whole-pytree gather/scatter/reset over the pooled caches.
    Each slot's clock lives in the caches' per-row ``pos`` leaves (and
    the scheduler's request state); ``valid[i]`` masks live slots (the
    scheduler decodes the full pool every step; dead rows compute but
    are never read).

    ``paged=True`` swaps the storage backing for the block-granular
    allocator (module docstring): ``alloc`` then also needs the prompt's
    blocks free in every page-table group, ``ensure`` must be called
    before a slot's write position grows, and ``release`` returns the
    physical blocks it freed. ``paged_window`` (default on) pages
    sliding-window rings through ring-mode groups as well; off keeps
    them dense per slot (the PR-3/4 layout).
    """

    def __init__(self, cfg: ModelConfig, num_slots: int, cache_slots: int,
                 *, paged: bool = False, block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 paged_window: bool = True,
                 num_window_blocks: Optional[int] = None,
                 swap_bytes_budget: Optional[int] = None,
                 prefix_sharing: bool = False,
                 prefix_align: Optional[int] = None,
                 prefix_capacity: int = 512,
                 mesh_shards: Optional[int] = None,
                 mesh=None, mesh_axis: str = "slots"):
        self.cfg = cfg
        self.num_slots = num_slots
        self.cache_slots = cache_slots
        if prefix_sharing and not paged:
            raise ValueError("prefix_sharing needs the paged backing "
                             "(blocks are the sharing granule)")
        self.sharded = mesh_shards is not None
        if self.sharded and not paged:
            raise ValueError("mesh_shards needs the paged backing "
                             "(blocks are the per-shard granule)")
        if mesh is not None and not self.sharded:
            raise ValueError("mesh without mesh_shards: pass "
                             "mesh_shards=len(mesh devices)")
        self.num_shards = mesh_shards if self.sharded else 1
        if num_slots % self.num_shards:
            raise ValueError(f"num_slots={num_slots} must divide evenly "
                             f"over {self.num_shards} shard(s)")
        self.slots_per_shard = num_slots // self.num_shards
        if self.sharded:
            self.backing = _ShardedPagedBacking(
                cfg, num_slots, cache_slots, block_size, num_blocks,
                paged_window=paged_window,
                num_window_blocks=num_window_blocks,
                swap_bytes_budget=swap_bytes_budget,
                prefix_sharing=prefix_sharing, prefix_align=prefix_align,
                prefix_capacity=prefix_capacity, num_shards=mesh_shards,
                mesh=mesh, axis=mesh_axis if mesh is not None else None)
        else:
            self.backing = (_PagedBacking(
                cfg, num_slots, cache_slots, block_size, num_blocks,
                paged_window=paged_window,
                num_window_blocks=num_window_blocks,
                swap_bytes_budget=swap_bytes_budget,
                prefix_sharing=prefix_sharing, prefix_align=prefix_align,
                prefix_capacity=prefix_capacity)
                if paged else
                _ContiguousBacking(cfg, num_slots, cache_slots))
        self._free: List[int] = list(range(num_slots - 1, -1, -1))
        self.owner: List[Optional[int]] = [None] * num_slots
        self.valid = np.zeros(num_slots, bool)
        obs_metrics.REGISTRY.register_provider("serve.slots", self)
        if paged:
            obs_metrics.REGISTRY.register_provider("paging", self.backing)

    @property
    def paged(self) -> bool:
        return self.backing.is_paged

    @property
    def caches(self):
        """The pooled cache pytree (contiguous backing only — the paged
        backing's state is ``backing.dense`` + ``backing.paged``)."""
        return self.backing.caches

    @caches.setter
    def caches(self, value):
        assert not self.backing.is_paged, \
            "caches is the contiguous backing's state; the paged backing " \
            "holds backing.dense + backing.paged (use gather/scatter)"
        self.backing.caches = value

    @property
    def position_capacity(self) -> int:
        """Global-KV cache positions backing the pool (the equal-memory
        axis fig_serve's global-attention comparison uses)."""
        return self.backing.position_capacity

    @property
    def total_rows(self) -> int:
        """ALL attention cache positions allocated (global KV + window
        rings, paged incl. trash sentinels) — the equal-memory axis for
        windowed models."""
        return self.backing.total_rows

    # -- lifecycle -----------------------------------------------------------

    @property
    def free_count(self) -> int:
        return len(self._free)

    def free_count_shard(self, shard: int) -> int:
        k = self.slots_per_shard
        return sum(1 for i in self._free if i // k == shard)

    def shard_of_slot(self, slot: int) -> int:
        return slot // self.slots_per_shard

    def shard_free_blocks(self, shard: int) -> int:
        """Free blocks on ``shard`` (sharded backing) — the least-loaded
        placement signal the scheduler reads."""
        return self.backing.shard_free_blocks(shard)

    def _pop_free(self, shard: Optional[int]) -> int:
        """Claim a free slot — the most recently freed one (LIFO), or the
        most recently freed one WITHIN ``shard`` when given. With one
        shard both forms pop the same slot, so the sharded n=1 admission
        path allocates bit-identically to the unsharded one."""
        if shard is None:
            return self._free.pop()
        k = self.slots_per_shard
        for i in range(len(self._free) - 1, -1, -1):
            if self._free[i] // k == shard:
                return self._free.pop(i)
        raise RuntimeError(f"no free slot on shard {shard}")

    @property
    def live(self) -> List[int]:
        return [i for i in range(self.num_slots) if self.valid[i]]

    def can_admit(self, prompt_len: int = 0, prompt=None,
                  span: Optional[int] = None,
                  shard: Optional[int] = None) -> bool:
        """A free slot AND (paged) enough free blocks for the prompt in
        every page-table group. With prefix sharing, ``prompt`` (tokens)
        discounts blocks an indexed shared prefix already holds, and
        ``span`` (prompt + generation budget) bounds ring-group
        eligibility. On a sharded pool ``shard`` scopes both checks to
        that shard's slots and block pools."""
        if shard is not None:
            return (self.free_count_shard(shard) > 0
                    and self.backing.can_admit(prompt_len, prompt=prompt,
                                               span=span, shard=shard))
        return bool(self._free) and self.backing.can_admit(
            prompt_len, prompt=prompt, span=span)

    def fits_pool(self, n_positions: int) -> Optional[str]:
        """None if a request spanning ``n_positions`` could ever be
        mapped on an empty pool; else the reason it can't (the
        scheduler's submit-time ValueError)."""
        return self.backing.fits_pool(n_positions)

    def alloc(self, owner: int, prompt_len: int = 0, prompt=None,
              span: Optional[int] = None,
              shard: Optional[int] = None) -> Optional[int]:
        """Claim a free slot for request ``owner``; zero its cache rows
        (paged: map + zero the blocks covering the prompt — an indexed
        shared prefix of ``prompt`` maps read-shared instead, see
        ``prefill_start``). ``shard`` pins the slot to one shard of a
        sharded pool. Returns the slot index, or None when the
        pool/blocks are exhausted."""
        if not self.can_admit(prompt_len, prompt=prompt, span=span,
                              shard=shard):
            return None
        slot = self._pop_free(shard)
        self.backing.alloc_reset(slot, prompt_len, prompt=prompt, span=span)
        self.owner[slot] = owner
        self.valid[slot] = True
        return slot

    def prefill_start(self, slot: int) -> int:
        """First position ``slot``'s prefill must write: 0 normally, the
        shared-prefix length when the last alloc mapped indexed blocks
        (their KV is already resident — prefill skips them)."""
        return self.backing.prefill_start(slot)

    def register_prefix(self, slot: int, prompt, span: int,
                        upto_tokens: int) -> int:
        """Publish ``slot``'s prefilled leading blocks into the prefix
        index (paged + prefix_sharing only; no-op otherwise)."""
        return self.backing.register_prefix(slot, prompt, span, upto_tokens)

    def flush_prefix(self) -> int:
        """Drop every prefix-index entry (releases index block holds)."""
        return self.backing.flush_prefix()

    def ensure(self, slot: int, upto_pos: int,
               write_from: Optional[int] = None) -> bool:
        """Grow slot storage to cover writes over
        [``write_from`` (default ``upto_pos``), ``upto_pos``]. Always
        True for contiguous; paged backing also copies-on-write any
        shared block in the write span, and returns False when the pool
        is out of blocks (the scheduler then preempts)."""
        if not self.valid[slot]:
            raise RuntimeError(f"slot {slot} is not live")
        return self.backing.ensure(slot, upto_pos, write_from=write_from)

    def release(self, slot: int) -> List[int]:
        """Evict (EOS / max-tokens / abort / preempt): mark free; returns
        the physical blocks handed back (paged) — the stale cache rows are
        masked out by ``valid`` until the next alloc resets them."""
        if not self.valid[slot]:
            raise RuntimeError(f"slot {slot} is not live")
        self.owner[slot] = None
        self.valid[slot] = False
        self._free.append(slot)
        return self.backing.release_slot(slot)

    # -- swap-out preemption (paged backing only) -----------------------

    def swap_out(self, slot: int) -> Optional[int]:
        """Preempt WITHOUT discarding work: park the slot's mapped block
        bytes + dense leaves in the backing's SwapStore (keyed by the
        owning rid), free the blocks and the slot. Returns bytes moved
        to host — or None when the SwapStore byte budget rejects the
        entry, in which case the slot stays LIVE and the caller must
        fall back to recompute-preemption."""
        if not self.valid[slot]:
            raise RuntimeError(f"slot {slot} is not live")
        if not self.backing.is_paged:
            raise RuntimeError("swap-out needs the paged backing")
        rid = self.owner[slot]
        nbytes = self.backing.swap_out(slot, rid)
        if nbytes is None:
            return None
        self.owner[slot] = None
        self.valid[slot] = False
        self._free.append(slot)
        return nbytes

    def is_swapped(self, rid: int) -> bool:
        if not self.backing.is_paged:
            return False
        if self.sharded:
            return self.backing.swapped_shard(rid) is not None
        return rid in self.backing.swaps

    def swapped_shard(self, rid: int) -> Optional[int]:
        """Shard whose SwapStore holds ``rid`` (sharded backing)."""
        return self.backing.swapped_shard(rid)

    def migrate_swapped(self, rid: int, dst_shard: int) -> bool:
        """Work-steal a swapped-out request to ``dst_shard``'s SwapStore
        (host bytes change owner; prefill progress is preserved). False
        when not swapped / already there / over the destination budget."""
        return self.backing.migrate_swapped(rid, dst_shard)

    def can_steal_swapped(self, rid: int, dst_shard: int) -> bool:
        """Could ``dst_shard`` hold and admit ``rid``'s swapped entry
        right now (free slot + swap budget + free blocks)?"""
        return (self.free_count_shard(dst_shard) > 0
                and self.backing.can_steal_swapped(rid, dst_shard))

    def can_admit_swapped(self, rid: int) -> bool:
        """A free slot AND blocks for the request's saved prefix in
        every page-table group (sharded: both scoped to the shard whose
        store holds the entry)."""
        if self.sharded:
            s = self.backing.swapped_shard(rid)
            return (s is not None and self.free_count_shard(s) > 0
                    and self.backing.can_admit_swapped(rid))
        return bool(self._free) and self.backing.can_admit_swapped(rid)

    def swap_in(self, rid: int) -> Optional[Tuple[int, int]]:
        """Resume a swapped-out request: claim a free slot, remap fresh
        blocks and upload the saved bytes — the slot reads bit-identical
        to the never-preempted layout, so decode continues at the saved
        position with zero recomputed steps. Returns (slot, bytes moved),
        or None when the pool can't host it yet."""
        if not self.can_admit_swapped(rid):
            return None
        slot = self._pop_free(self.backing.swapped_shard(rid)
                              if self.sharded else None)
        nbytes = self.backing.swap_in(slot, rid)
        self.owner[slot] = rid
        self.valid[slot] = True
        return slot, nbytes

    # -- pooled-cache data movement -----------------------------------------

    def gather(self, idx: Sequence[int]):
        """Sub-caches for slots ``idx`` (batch axis = len(idx)). The paged
        backing materializes the page-table views — bit-identical to the
        contiguous rows for every mapped position."""
        return self.backing.gather(idx)

    def scatter(self, sub, idx: Sequence[int]):
        """Write sub-caches (from a bucketed chunk step) back into slots.
        Duplicate indices must carry identical rows (the pad-by-repeat
        contract): the scatter then stays deterministic."""
        self.backing.scatter(sub, idx)

    def run_chunk(self, params, idx: Sequence[int], tokens, pos):
        """Chunk-prefill slots ``idx`` in place (fused gather -> chunk ->
        scatter, one dispatch); returns the per-position chunk logits
        (len(idx), C, V) — prompt scoring reads them, plain prefill
        ignores them. Same pad-by-repeat contract as scatter."""
        return self.backing.run_chunk(params, idx, tokens, pos)

    def run_decode(self, params, tokens, pos, temps, key,
                   top_ks=None, top_ps=None):
        """ONE fused decode over the whole pool; returns (next tokens,
        logits (B, 1, V)). top_ks/top_ps are optional (B,) per-slot
        sampling filters (None = disabled). (Paged:
        gather-through-page-tables -> decode -> scatter, still one jitted
        program per tick.)"""
        return self.backing.run_decode(params, tokens, pos, temps, key,
                                       top_ks, top_ps)

    def run_verify(self, params, tokens, pos, prompt_len, max_pos, score,
                   active, temps, top_ks, top_ps, key):
        """ONE fused speculative verify-accept tick over the whole pool
        (engine.make_verify_step contract): teacher-forces tokens
        (B, k+1), returns (out_tok (B, k+1), accept_n (B,), logprobs
        (B, k+1)); rejected cache writes are rolled back in-program, so
        the pool only ever holds committed rows."""
        return self.backing.run_verify(params, tokens, pos, prompt_len,
                                       max_pos, score, active, temps,
                                       top_ks, top_ps, key)

    def metrics(self) -> dict:
        """Registry 'serve.slots' provider: pool-facade levels (the
        backing's keys go out under 'paging' when paged)."""
        return {"num_slots": self.num_slots,
                "live": int(self.valid.sum()),
                "free": self.free_count,
                "cache_slots": self.cache_slots,
                "position_capacity": self.position_capacity,
                "total_rows": self.total_rows}

    def shard_metrics(self) -> dict:
        """Per-shard occupancy gauges (sharded backing only):
        ``shard<i>.live_slots`` / ``free_slots`` plus the backing's
        per-shard block/swap levels. The scheduler layers placement and
        steal counters on top under the ``serve.shard`` prefix."""
        out = {}
        k = self.slots_per_shard
        for s in range(self.num_shards):
            free = self.free_count_shard(s)
            out[f"shard{s}.live_slots"] = k - free
            out[f"shard{s}.free_slots"] = free
        if self.sharded:
            out.update(self.backing.shard_metrics())
        return out

    def stats(self) -> dict:
        return {**self.metrics(), **self.backing.stats()}
