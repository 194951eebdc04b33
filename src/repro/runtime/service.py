"""KernelService — one entry point for bulk dependency-bound kernel work.

The paper's pitch is that five very different dependency-bound kernels
(chain, Smith-Waterman, DTW, sort/seeding, 1-D scans) accelerate behind
*one* dispatch interface with minimal software changes. This registry is
that interface at traffic scale: heterogeneous requests go in, the service
groups them by kernel, buckets them by shape (``runtime.bucketing``),
batches each bucket through the worker-pool dispatcher
(``runtime.dispatch``) with host/device overlap (``runtime.pipeline``),
and scatters per-request results back in order.

    svc = KernelService(ServiceConfig(), reference=ref)   # ref: mapper/seed
    results = svc.submit([
        Request("chain", {"q": q, "r": r}),
        Request("dtw",   {"s": s, "r": r2}),
        Request("map",   {"read": read}),
        ...
    ])

Every kernel result is bit-identical to the corresponding direct call into
``repro.core`` / ``repro.apps.read_mapper``: batching is pure vmap over
the same per-request computation, and sentinel padding is appended *after*
the true data, which none of these left-to-right recurrences can see.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from repro.apps import read_mapper as rm
from repro.core import align as align_lib
from repro.core import chain as chain_lib
from repro.core import dtw as dtw_lib
from repro.core import seeding
from repro.core import sort as rsort
from repro.core import wavefront
from repro.core.scan1d import affine_scan
from repro.core.semiring import SEMIRINGS, finite_zero
from repro.kernels import mode as kmode
from repro.obs import metrics as obs_metrics
from repro.obs import sampler as obs_sampler
from repro.runtime import bucketing
from repro.runtime.autotune import Autotuner
from repro.runtime.dispatch import Dispatcher
from repro.runtime.pipeline import run_pipelined


@dataclasses.dataclass(frozen=True)
class ServiceConfig:
    """Static knobs; one compiled program per (kernel, bucket key)."""
    # bucketing
    seq_bucket: int = 64        # sw/dtw sequence quantum (tile-aligned)
    anchor_bucket: int = 256    # chain anchor quantum
    sort_bucket: int = 256
    scan_bucket: int = 64
    bucket_mode: str = "linear"     # 'linear' | 'pow2'
    # chain
    chain_T: int = 64
    chain_mode: str = "fission"     # fission | sequential | blocked
    chain_block: int = 16
    # align / dtw
    sw_params: align_lib.SWParams = align_lib.SWParams()
    sw_tile: int = 32
    dtw_tile: int = 32
    # sort / seed / scan
    sort_chunks: int = 4
    scan_semiring: str = "real"
    scan_mode: str = "sequential"
    # end-to-end mapper
    mapper: rm.MapperConfig = rm.MapperConfig()
    # pipeline
    pipeline_depth: int = 2

    def tuned(self, tuner: Autotuner) -> "ServiceConfig":
        """Override tile/chunk knobs from an autotune cache (fig9-seeded).
        The cache is always named by the caller: by default the service
        compiles from these knobs alone, never from a file outside the
        checkout."""
        over = {}
        dtw_tile = tuner.get("dtw.tile")
        if dtw_tile:
            over["dtw_tile"] = int(dtw_tile)
            over["sw_tile"] = int(dtw_tile)     # same engine, same knee
        chunk = tuner.get("ssm.chunk")
        if chunk:
            over["scan_bucket"] = int(chunk)
        return dataclasses.replace(self, **over) if over else self


@dataclasses.dataclass(frozen=True)
class Request:
    kernel: str
    payload: Dict[str, Any]


def _spec(size: int, mode: str) -> bucketing.BucketSpec:
    return bucketing.BucketSpec(size=size, mode=mode)


def _payload_key(payload: Dict) -> Tuple:
    """Content key for a kernel payload (bulk-submit dedup). dtype +
    shape ride along with the bytes for the same reason RequestCache.key
    carries them: equal bytes alone collide across dtypes/shapes."""
    parts: List[Tuple] = []
    for k in sorted(payload):
        v = payload[k]
        if isinstance(v, (np.ndarray, jnp.ndarray, list, tuple)):
            a = np.ascontiguousarray(v)
            parts.append((k, a.tobytes(), a.dtype.str, a.shape))
        else:
            parts.append((k, v))
    return tuple(parts)


def _copy_result(res: Any) -> Any:
    """Fresh arrays for a deduped duplicate: handing every requester the
    SAME array would let one caller's in-place edit corrupt another's
    result (the RequestCache aliasing bug, one layer down)."""
    return jax.tree_util.tree_map(
        lambda x: x.copy() if isinstance(x, np.ndarray) else x, res)


# --------------------------------------------------------------------------
# cached batched building blocks
# --------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _scan_fn(srname: str, mode: str):
    sr = SEMIRINGS[srname]

    def run(a, b, x0):
        return affine_scan(a, b, x0, sr, mode=mode)
    return run


@functools.lru_cache(maxsize=None)
def _sort_fn(num_chunks: int):
    def run(keys, vals):
        return rsort.radix_sort(keys, vals, num_chunks=num_chunks,
                                min_parallel=0)
    return run


@functools.lru_cache(maxsize=None)
def _sw_tile_batched(params: align_lib.SWParams, kernels: bool):
    """Batched SW tile: the Pallas tile where ``kernels`` (the platform's
    choice, kernels.mode.use_kernels), else the jnp tile."""
    if kernels:
        from repro.kernels import ops
        tile = ops.make_sw_tile_fn(params.match, params.mismatch, params.gap)
    else:
        tile = functools.partial(align_lib._sw_tile_fn, params)
    return jax.jit(jax.vmap(tile))


@functools.lru_cache(maxsize=None)
def _dtw_tile_batched(kernels: bool):
    if kernels:
        from repro.kernels import ops
        return jax.jit(jax.vmap(ops.dtw_tile_fn))
    return jax.jit(jax.vmap(dtw_lib._dtw_tile_fn))


def _sw_batched(a: np.ndarray, b: np.ndarray,
                params: align_lib.SWParams, tile: int) -> jnp.ndarray:
    """(B, na) x (B, nb) -> H matrices (B, na, nb) via the batched
    wavefront; per-row bit-identical to align.sw_tiled on that row."""
    bsz, na = a.shape
    nb = b.shape[1]
    ap = wavefront.pad_to_multiple(jnp.asarray(a, jnp.int32), tile, 1, 255)
    bp = wavefront.pad_to_multiple(jnp.asarray(b, jnp.int32), tile, 1, 255)
    npad, mpad = ap.shape[1], bp.shape[1]
    mat, _, _, _ = wavefront.run_wavefront_batched(
        _sw_tile_batched(params, kmode.use_kernels()), ap, bp,
        top0=jnp.zeros((bsz, mpad), jnp.float32),
        left0=jnp.zeros((bsz, npad), jnp.float32),
        corner0=jnp.zeros((bsz,), jnp.float32),
        tile_r=tile, tile_c=tile, assemble=True)
    return mat[:, :na, :nb]


def _dtw_batched(s: np.ndarray, r: np.ndarray, tile: int) -> jnp.ndarray:
    """(B, n) x (B, m) -> DTW matrices (B, n, m), per-row bit-identical to
    dtw.dtw_tiled on that row."""
    bsz, n = s.shape
    m = r.shape[1]
    big = jnp.float32(jnp.finfo(jnp.float32).max / 4)
    sp = wavefront.pad_to_multiple(jnp.asarray(s, jnp.float32), tile, 1, 1e18)
    rp = wavefront.pad_to_multiple(jnp.asarray(r, jnp.float32), tile, 1, 1e18)
    npad, mpad = sp.shape[1], rp.shape[1]
    mat, _, _, _ = wavefront.run_wavefront_batched(
        _dtw_tile_batched(kmode.use_kernels()), sp, rp,
        top0=jnp.full((bsz, mpad), big, jnp.float32),
        left0=jnp.full((bsz, npad), big, jnp.float32),
        corner0=jnp.zeros((bsz,), jnp.float32),
        tile_r=tile, tile_c=tile, assemble=True)
    return mat[:, :n, :m]


# --------------------------------------------------------------------------
# kernel adapters
# --------------------------------------------------------------------------

class KernelAdapter:
    """Bucket -> batch -> dispatch -> unpack for one kernel family.

    Subclasses implement ``bucket_key`` / ``prepare`` / ``launch`` /
    ``collect``; the generic ``run`` pipelines the buckets (padding the
    next bucket on the host while the current one computes)."""

    name: str = ""

    def __init__(self, svc: "KernelService"):
        self.svc = svc
        self.cfg = svc.cfg

    # hooks -------------------------------------------------------------
    def bucket_key(self, payload: Dict) -> Tuple:
        raise NotImplementedError

    def prepare(self, key: Tuple, payloads: List[Dict]):
        raise NotImplementedError

    def launch(self, key: Tuple, leaves):
        raise NotImplementedError

    def collect(self, key: Tuple, out, payloads: List[Dict]) -> List[Any]:
        raise NotImplementedError

    # generic pipeline ---------------------------------------------------
    def run(self, payloads: List[Dict]) -> List[Any]:
        """Dedup identical payloads (content hash — cheap next to a
        dispatch), run the unique set through the bucketed pipeline,
        fan results back out. A bulk submit repeating one hot read /
        key array pays for ONE dispatch; duplicates receive fresh
        array copies so no two requesters alias the same buffer."""
        keys = []
        for p in payloads:
            try:
                keys.append(_payload_key(p))
            except TypeError:       # unhashable extra → never deduped
                keys.append(object())
        first: Dict[Any, int] = {}
        uniq: List[int] = []
        for i, k in enumerate(keys):
            if k not in first:
                first[k] = len(uniq)
                uniq.append(i)
        if len(uniq) == len(payloads):
            return self._run_unique(payloads)
        self.svc.deduped_requests += len(payloads) - len(uniq)
        got = self._run_unique([payloads[i] for i in uniq])
        return [got[first[k]] if i == uniq[first[k]]
                else _copy_result(got[first[k]])
                for i, k in enumerate(keys)]

    def _run_unique(self, payloads: List[Dict]) -> List[Any]:
        groups = bucketing.group_by_key(
            [self.bucket_key(p) for p in payloads])
        results: List[Any] = [None] * len(payloads)

        def work():
            for key, rows in groups.items():
                yield key, rows, self.prepare(
                    key, [payloads[r] for r in rows])

        def launch(item):
            key, rows, leaves = item
            return key, rows, self.launch(key, leaves)

        for key, rows, out in run_pipelined(
                work(), launch, depth=self.cfg.pipeline_depth):
            out = jax.tree_util.tree_map(np.asarray, out)
            got = self.collect(key, out, [payloads[r] for r in rows])
            for r, res in zip(rows, got):
                results[r] = res
        return results


class ChainAdapter(KernelAdapter):
    """payload {q, r} -> {"f", "pred"} (minimap2 chain DP, §III-B)."""

    name = "chain"

    def bucket_key(self, p):
        return (_spec(self.cfg.anchor_bucket, self.cfg.bucket_mode)
                .padded(max(len(p["q"]), 1)),)

    def prepare(self, key, payloads):
        nb = key[0]
        qp = bucketing.pad_stack([np.asarray(p["q"], np.int32)
                                  for p in payloads], nb, 0)
        rp = bucketing.pad_stack([np.asarray(p["r"], np.int32)
                                  for p in payloads], nb, 2**30)
        vp = bucketing.valid_mask(
            bucketing.lengths_of([p["q"] for p in payloads]), nb)
        return qp, rp, vp

    def launch(self, key, leaves):
        block = self.cfg.chain_block
        if self.cfg.chain_mode == "blocked" and self.svc.tuner is not None:
            # per-bucket autotuned block (fig9 sweep); only the blocked
            # schedule consumes a block size — fission/sequential ignore
            # it, so the lookup would be misleading there
            block = int(self.svc.tuner.get_bucketed("chain.block", key[0],
                                                    block))
        if self.cfg.chain_mode == "fission" and kmode.use_kernels():
            # the Pallas chain kernel is the fission schedule on the chip
            fn = rm._chain_fn_pallas(self.cfg.chain_T)
        else:
            fn = rm._chain_fn(self.cfg.chain_T, self.cfg.chain_mode, block)
        return self.svc.dispatcher.run(fn, leaves)

    def collect(self, key, out, payloads):
        f, pred = out
        return [{"f": f[i, :len(p["q"])], "pred": pred[i, :len(p["q"])]}
                for i, p in enumerate(payloads)]


class SWAdapter(KernelAdapter):
    """payload {a, b} -> {"score", "end"} (Smith-Waterman, §III-B)."""

    name = "sw"

    def _padded(self, n):
        spec = _spec(self.cfg.seq_bucket, self.cfg.bucket_mode)
        return bucketing.round_up(spec.padded(n), self.cfg.sw_tile)

    def bucket_key(self, p):
        return (self._padded(len(p["a"])), self._padded(len(p["b"])))

    def prepare(self, key, payloads):
        na, nb = key
        a = bucketing.pad_stack([np.asarray(p["a"], np.int32)
                                 for p in payloads], na, 254)
        b = bucketing.pad_stack([np.asarray(p["b"], np.int32)
                                 for p in payloads], nb, 255)
        return a, b

    def launch(self, key, leaves):
        a, b = leaves
        return _sw_batched(a, b, self.cfg.sw_params, self.cfg.sw_tile)

    def collect(self, key, mats, payloads):
        out = []
        for i, p in enumerate(payloads):
            mat = mats[i, :len(p["a"]), :len(p["b"])]
            flat = int(np.argmax(mat))
            out.append({"score": mat.flat[flat],
                        "end": (flat // mat.shape[1], flat % mat.shape[1])})
        return out


class DTWAdapter(KernelAdapter):
    """payload {s, r} -> {"distance"} (dynamic time warping, §III-C)."""

    name = "dtw"

    def _padded(self, n):
        spec = _spec(self.cfg.seq_bucket, self.cfg.bucket_mode)
        return bucketing.round_up(spec.padded(n), self.cfg.dtw_tile)

    def bucket_key(self, p):
        return (self._padded(len(p["s"])), self._padded(len(p["r"])))

    def prepare(self, key, payloads):
        n, m = key
        s = bucketing.pad_stack([np.asarray(p["s"], np.float32)
                                 for p in payloads], n, 1e18)
        r = bucketing.pad_stack([np.asarray(p["r"], np.float32)
                                 for p in payloads], m, 1e18)
        return s, r

    def launch(self, key, leaves):
        s, r = leaves
        return _dtw_batched(s, r, self.cfg.dtw_tile)

    def collect(self, key, mats, payloads):
        return [{"distance": mats[i, len(p["s"]) - 1, len(p["r"]) - 1]}
                for i, p in enumerate(payloads)]


class SortAdapter(KernelAdapter):
    """payload {keys[, vals]} -> {"keys", "vals"} (chunked radix, §III-A)."""

    name = "sort"

    def bucket_key(self, p):
        return (_spec(self.cfg.sort_bucket, self.cfg.bucket_mode)
                .padded(max(len(p["keys"]), 1)),)

    def prepare(self, key, payloads):
        nb = key[0]
        keys = bucketing.pad_stack(
            [np.asarray(p["keys"], np.uint32) for p in payloads], nb,
            np.uint32(0xFFFFFFFF))
        vals = bucketing.pad_stack(
            [np.asarray(p["vals"], np.int32) if "vals" in p
             else np.arange(len(p["keys"]), dtype=np.int32)
             for p in payloads], nb, 0)
        return keys, vals

    def launch(self, key, leaves):
        chunks = self.cfg.sort_chunks
        if self.svc.tuner is not None:
            chunks = self.svc.tuner.get_bucketed("sort.chunks", key[0],
                                                 chunks)
        return self.svc.dispatcher.run(_sort_fn(int(chunks)), leaves)

    def collect(self, key, out, payloads):
        keys, vals = out
        return [{"keys": keys[i, :len(p["keys"])],
                 "vals": vals[i, :len(p["keys"])]}
                for i, p in enumerate(payloads)]


class SeedAdapter(KernelAdapter):
    """payload {read} -> {"q", "r"} anchors (minimizer seeding, §III-B).

    The reference index is service state (KernelService(reference=...)),
    broadcast to every worker (vmap in_axes None)."""

    name = "seed"

    def bucket_key(self, p):
        cfg = self.cfg.mapper
        return (bucketing.round_up(len(p["read"]), cfg.read_bucket),)

    def prepare(self, key, payloads):
        nb = key[0]
        reads = bucketing.pad_stack(
            [np.asarray(p["read"], np.int32) for p in payloads], nb, 0)
        lens = bucketing.lengths_of([p["read"] for p in payloads])
        index = self.svc.index
        return index.hashes, index.positions, reads, lens

    def launch(self, key, leaves):
        cfg = self.cfg.mapper
        n_chunks = cfg.num_workers if cfg.mode == "squire" else 1
        fn = rm._seed_fn(cfg.k, cfg.w, cfg.max_occ, n_chunks)
        return self.svc.dispatcher.run(fn, leaves,
                                       in_axes=(None, None, 0, 0))

    def collect(self, key, out, payloads):
        q, r, valid = out
        return [{"q": q[i][valid[i]], "r": r[i][valid[i]]}
                for i in range(len(payloads))]


class ScanAdapter(KernelAdapter):
    """payload {a, b, x0} -> {"xs"} (1-D affine recurrence, the global-
    counter pattern; semiring/mode from ServiceConfig)."""

    name = "scan1d"

    def bucket_key(self, p):
        return (_spec(self.cfg.scan_bucket, self.cfg.bucket_mode)
                .padded(max(len(p["a"]), 1)),)

    def prepare(self, key, payloads):
        nb = key[0]
        sr = SEMIRINGS[self.cfg.scan_semiring]
        dtype = np.float32
        one = np.asarray(sr.one, dtype)
        zero = np.asarray(finite_zero(sr, jnp.float32), dtype)
        a = bucketing.pad_stack([np.asarray(p["a"], dtype)
                                 for p in payloads], nb, one)
        b = bucketing.pad_stack([np.asarray(p["b"], dtype)
                                 for p in payloads], nb, zero)
        x0 = np.asarray([np.asarray(p["x0"], dtype) for p in payloads])
        return a, b, x0

    def launch(self, key, leaves):
        fn = _scan_fn(self.cfg.scan_semiring, self.cfg.scan_mode)
        return self.svc.dispatcher.run(fn, leaves)

    def collect(self, key, out, payloads):
        return [{"xs": out[i, :len(p["a"])]}
                for i, p in enumerate(payloads)]


class MapperAdapter(KernelAdapter):
    """payload {read} -> MapResult: the end-to-end mapper with each stage
    batched across the in-flight requests (the paper's Fig. 8 pipeline at
    traffic scale). Stage functions and padding are shared with
    ReadMapper, so results are bit-identical to per-read mapping."""

    name = "map"

    def run(self, payloads: List[Dict]) -> List[Any]:
        cfg = self.cfg.mapper
        svc = self.svc
        reads = [np.asarray(p["read"]) for p in payloads]
        results: List[Optional[rm.MapResult]] = [None] * len(reads)

        live = []
        for i, rd in enumerate(reads):
            if len(rd) < cfg.k + cfg.w:
                results[i] = rm.MapResult(-1, 0.0, 0.0, 0, 0)
            else:
                live.append(i)

        # -- seed: the same adapter the standalone "seed" kernel uses ----
        anchors: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        seeded = svc._adapters["seed"].run(
            [{"read": reads[i]} for i in live])
        for i, got in zip(live, seeded):
            nv = len(got["q"])
            if nv < 2:
                results[i] = rm.MapResult(-1, 0.0, 0.0, nv, 0)
            else:
                anchors[i] = (got["q"], got["r"])

        # -- chain (bucketed by padded anchor count) ---------------------
        windows: Dict[int, Tuple[float, int, int]] = {}
        chain_fn = rm.chain_stage(cfg.mode, cfg.band_T)
        groups = bucketing.group_by_key(
            [(bucketing.round_up(max(len(anchors[i][0]), 1),
                                 cfg.anchor_bucket),)
             for i in sorted(anchors)])
        order = sorted(anchors)
        for (nb,), rows in groups.items():
            idxs = [order[r] for r in rows]
            parts = [rm.chain_payload(anchors[i][0], anchors[i][1], cfg)
                     for i in idxs]
            qp = np.stack([x[0] for x in parts])
            rp = np.stack([x[1] for x in parts])
            vp = np.stack([x[2] for x in parts])
            f, pred = jax.tree_util.tree_map(
                np.asarray, svc.dispatcher.run(chain_fn, (qp, rp, vp)))
            for row, i in enumerate(idxs):
                qv, rv = anchors[i]
                nv = len(qv)
                chains = chain_lib.backtrack(f[row][:nv], pred[row][:nv],
                                             min_score=cfg.min_chain_score)
                if not chains:
                    results[i] = rm.MapResult(-1, 0.0, 0.0, nv, 0)
                    continue
                score, members = chains[0]
                lo, hi = rm.chain_window(qv, rv, members, len(reads[i]),
                                         len(svc.reference), cfg)
                if hi - lo < cfg.k:
                    results[i] = rm.MapResult(-1, 0.0, score, nv, 0)
                else:
                    windows[i] = (score, lo, hi)

        # -- align (bucketed by padded (read, window) shape) -------------
        pend = sorted(windows)
        pairs = {}
        for i in pend:
            _, lo, hi = windows[i]
            window = svc.reference[lo:hi].astype(np.int32)
            pairs[i] = rm.align_payload(reads[i], window, cfg)
        groups = bucketing.group_by_key(
            [(pairs[i][0].shape[0], pairs[i][1].shape[0]) for i in pend])
        for (na, nb), rows in groups.items():
            idxs = [pend[r] for r in rows]
            a = np.stack([pairs[i][0] for i in idxs])
            b = np.stack([pairs[i][1] for i in idxs])
            mats = np.asarray(self._align_batched(a, b))
            for row, i in enumerate(idxs):
                chain_score, lo, hi = windows[i]
                mat = mats[row]
                sw_score = float(mat.max())
                results[i] = rm.MapResult(
                    pos=lo, sw_score=sw_score, chain_score=chain_score,
                    n_anchors=len(anchors[i][0]),
                    align_cells=len(reads[i]) * (hi - lo))
        return results

    def _align_batched(self, a: np.ndarray, b: np.ndarray):
        cfg = self.cfg.mapper
        if cfg.mode == "squire":
            tile_b = _sw_tile_batched(cfg.sw_params, kmode.use_kernels())
            bsz = a.shape[0]
            ap = wavefront.pad_to_multiple(jnp.asarray(a), cfg.sw_tile,
                                           1, 255)
            bp = wavefront.pad_to_multiple(jnp.asarray(b), cfg.sw_tile,
                                           1, 255)
            mat, _, _, _ = wavefront.run_wavefront_batched(
                tile_b, ap, bp,
                top0=jnp.zeros((bsz, bp.shape[1]), jnp.float32),
                left0=jnp.zeros((bsz, ap.shape[1]), jnp.float32),
                corner0=jnp.zeros((bsz,), jnp.float32),
                tile_r=cfg.sw_tile, tile_c=cfg.sw_tile, assemble=True)
            return mat[:, :a.shape[1], :b.shape[1]]
        fn, _ = rm.sw_stage("baseline", cfg.sw_tile, cfg.sw_params)
        mats, _ = self.svc.dispatcher.run(fn, (a, b))
        return mats


class GenerateAdapter(KernelAdapter):
    """payload {prompt[, max_new_tokens, temperature]} -> {"tokens",
    "reason"}: LM decode traffic through the same front door as the
    dependency-bound kernels (ROADMAP serving-integration item).

    Decode is the request-scale 1-D recurrence, so batching happens in
    *time* (continuous batching), not in the request list: the adapter
    forwards the whole bulk to the attached ``serve.Scheduler``, whose
    slot pool interleaves prefill/decode/retire per step. Attach with
    ``KernelService(lm=Scheduler(...))``."""

    name = "generate"

    def run(self, payloads: List[Dict]) -> List[Any]:
        sched = self.svc.lm
        if sched is None:
            raise ValueError(
                "generate kernel needs KernelService(lm=serve.Scheduler)")
        rids = []
        for p in payloads:
            rids.extend(sched.submit(
                [np.asarray(p["prompt"], np.int32)],
                max_new_tokens=p.get("max_new_tokens"),
                temperature=p.get("temperature"),
                top_k=p.get("top_k"), top_p=p.get("top_p")))
        sched.drain()
        # pop: a long-lived service must not accumulate Completions
        done = [sched.results.pop(r) for r in rids]
        return [{"tokens": c.tokens, "reason": c.reason,
                 "accepted": c.accepted, "drafted": c.drafted}
                for c in done]


class ScoreAdapter(KernelAdapter):
    """payload {prompt} -> {"logprobs", "reason"}: per-token prompt
    logprobs (``logprobs[i-1] = log p(prompt[i] | prompt[:i])``) through
    the scheduler's chunk path — same slot pool, cache and admission
    machinery as 'generate', zero sampled tokens. Attach with
    ``KernelService(lm=Scheduler(...))``."""

    name = "score"

    def run(self, payloads: List[Dict]) -> List[Any]:
        sched = self.svc.lm
        if sched is None:
            raise ValueError(
                "score kernel needs KernelService(lm=serve.Scheduler)")
        rids = []
        for p in payloads:
            rids.extend(sched.score([np.asarray(p["prompt"], np.int32)]))
        sched.drain()
        done = [sched.results.pop(r) for r in rids]
        return [{"logprobs": c.logprobs, "reason": c.reason}
                for c in done]


_ADAPTERS = (ChainAdapter, SWAdapter, DTWAdapter, SortAdapter, SeedAdapter,
             ScanAdapter, MapperAdapter, GenerateAdapter, ScoreAdapter)


class KernelService:
    """The software Squire accelerator pool: submit heterogeneous kernel
    requests in bulk, get per-request results back in order."""

    def __init__(self, cfg: ServiceConfig = ServiceConfig(),
                 reference: Optional[np.ndarray] = None,
                 dispatcher: Optional[Dispatcher] = None,
                 lm: Optional[Any] = None,
                 tuner: Optional[Autotuner] = None):
        self.cfg = cfg
        self.dispatcher = dispatcher or Dispatcher()
        self.reference = (None if reference is None
                          else np.asarray(reference, np.int8))
        self.lm = lm            # serve.Scheduler for the 'generate' kernel
        # per-bucket knobs come from an autotune cache only when one is
        # passed; otherwise the ServiceConfig knobs alone decide
        self.tuner = tuner
        self._index = None
        self._adapters: Dict[str, KernelAdapter] = {
            a.name: a(self) for a in _ADAPTERS}
        # per-kernel traffic: requests routed / bulk submits seen /
        # duplicate payloads served from a sibling's dispatch
        self.request_counts = collections.Counter(
            dict.fromkeys(self.kernels, 0))
        self.submit_count = 0
        self.deduped_requests = 0
        obs_metrics.REGISTRY.register_provider("runtime.service", self)

    @property
    def index(self):
        """Lazily-built reference minimizer index (seed/map kernels)."""
        if self._index is None:
            if self.reference is None:
                raise ValueError(
                    "seed/map kernels need KernelService(reference=...)")
            m = self.cfg.mapper
            self._index = seeding.build_index(self.reference, m.k, m.w)
        return self._index

    @property
    def kernels(self) -> Tuple[str, ...]:
        return tuple(sorted(self._adapters))

    def metrics(self) -> Dict[str, Any]:
        """Registry 'runtime.service' provider: per-kernel request
        traffic (``requests.<kernel>``) + bulk submit count."""
        out: Dict[str, Any] = {"submits": self.submit_count,
                               "deduped_requests": self.deduped_requests}
        out.update({f"requests.{k}": int(v)
                    for k, v in sorted(self.request_counts.items())})
        return out

    def stats(self) -> Dict[str, Any]:
        """Service-level introspection: registered kernels + per-kernel
        traffic counters plus, when an LM scheduler is attached, its
        pool/occupancy counters (incl. the paged allocator's block
        utilization — serve.SlotManager.stats)."""
        out: Dict[str, Any] = {"kernels": list(self.kernels),
                               **self.metrics()}
        if self.lm is not None:
            out["lm"] = self.lm.stats()
        return out

    def submit(self, requests: Sequence[Request]) -> List[Any]:
        """Run a heterogeneous batch; results align with ``requests``."""
        results: List[Any] = [None] * len(requests)
        by_kernel: Dict[str, List[int]] = {}
        for i, req in enumerate(requests):
            if req.kernel not in self._adapters:
                raise KeyError(f"unknown kernel {req.kernel!r}; "
                               f"have {self.kernels}")
            by_kernel.setdefault(req.kernel, []).append(i)
        self.submit_count += 1
        for kernel, idxs in by_kernel.items():
            self.request_counts[kernel] += len(idxs)
            got = self._adapters[kernel].run(
                [requests[i].payload for i in idxs])
            for i, res in zip(idxs, got):
                results[i] = res
        obs_sampler.tick("service.submit")
        return results
