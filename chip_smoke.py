"""Smoke run of the system's two main paths on one TPU chip.

    python chip_smoke.py [--seed N]      # one chip: device, mapper, serving
    python chip_smoke.py --chips 4       # four chips: sharded serving only

Phases (none of their failures is caught; any one fails the run):

  device   the first device must be a TPU. There is no CPU fallback.
  mapper   an E. coli-sized reference (4.6 Mb) generated from --seed, its
           minimizer index on the device; 8 reads each of the ONT and PBHF1
           profiles mapped by ReadMapper(mode="squire") and by the batched
           KernelService. The Pallas kernels (chain, dp tile, radix rank)
           must be compiled: the platform's kernel mode says so and
           ``tpu_custom_call`` is in the lowered stage programs. Positions,
           SW and chain scores must equal a jitted jnp reference built from
           repro.core (stable argsort seeding, sequential chain, jnp-tile SW
           wavefront).
  serving  rwkv6-1.6b at its published widths (random bf16 weights from
           --seed): 8 requests of 128-512 prompt tokens and 32 greedy new
           tokens through serve.Scheduler with the paged allocator, token-
           identical to per-request serve.generate at the Scheduler's batch
           shape (generate(..., batch=8)); one prompt's logits
           against the same forward in float32 at "highest" matmul
           precision: the median over positions of the RMS difference
           within LOGIT_BAND of the logits' standard deviation.
  --chips 4  the same requests through the slot pool sharded over four
           devices (mesh_shards=4, 8 slots per device) against
           mesh_shards=1 (8 slots), token for token, with the cache state
           spanning all four devices.

Earlier lines print each phase's results (wall and compile seconds, peak
device memory). The last line is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": ...}}.
JAX's compile cache goes where JAX_COMPILATION_CACHE_DIR says, else to the
checkout's .jax_cache/.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

REF_LEN = 4_600_000           # E. coli K-12 genome size
READS_PER_PROFILE = 8
PROFILES = ("ONT", "PBHF1")
ARCH = "rwkv6-1.6b"
N_REQUESTS = 8
PROMPT_LENS = (128, 512)
NEW_TOKENS = 32
# bf16 against float32 logits, per prompt position: RMS of the difference
# over the vocabulary / std(f32 logits). Unrelated logits score sqrt(2).
# Random weights make a deep RWKV sensitive at the first positions (each
# head's readout is near rank-1 there and its GroupNorm amplifies
# rounding), so the gate is the median over positions; the largest
# |difference| / std is printed beside it.
LOGIT_BAND = 0.5


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def peak_bytes() -> int:
    import jax
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def require_tpu():
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, found {dev.platform!r} "
                         f"({dev.device_kind}); nothing was run")
    return dev


def assert_kernel(name: str, lowered_text: str):
    """The stage program holds a compiled Mosaic kernel."""
    if "tpu_custom_call" not in lowered_text:
        raise AssertionError(f"{name}: no tpu_custom_call in the lowered "
                             "program (a Pallas stage did not compile)")
    log(f"mapper: {name} lowers to tpu_custom_call")


# --------------------------------------------------------------------------
# mapper
# --------------------------------------------------------------------------

def _reference_mapper(reference, index, cfg):
    """Jitted jnp reference of one squire mapping built from repro.core:
    minimizer seeding with a stable argsort, the sequential chain scan,
    the jnp-tile SW wavefront. Inputs are padded to the mapper's buckets
    (sentinels the recurrences cannot see), so programs are shared."""
    import functools

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.apps import read_mapper as rm
    from repro.core import align as align_lib
    from repro.core import chain as chain_lib
    from repro.core import seeding

    @jax.jit
    def seed(read, valid_len):
        qpos, qh, qvalid = seeding.minimizers(read, cfg.k, cfg.w)
        qvalid &= jnp.arange(qpos.shape[0]) < valid_len - cfg.k - cfg.w + 2
        q, r, valid = seeding.lookup_anchors(index, qpos, qh, qvalid,
                                             cfg.max_occ)
        key = jnp.where(valid, r.astype(jnp.uint32), jnp.uint32(0xFFFFFFFF))
        order = jnp.argsort(key, stable=True)
        return q[order], r[order], valid[order]

    chain = jax.jit(functools.partial(chain_lib.chain_anchors, T=cfg.band_T,
                                      mode="sequential"))
    tile = jax.jit(functools.partial(align_lib._sw_tile_fn, cfg.sw_params))

    def map_read(read):
        """-> (pos, sw_score, chain_score, n_anchors), -1 when unmapped."""
        if len(read) < cfg.k + cfg.w:
            return -1, 0.0, 0.0, 0
        padded, n = rm.seed_payload(read, cfg)
        q, r, valid = map(np.asarray, seed(jnp.asarray(padded),
                                           jnp.asarray(n)))
        qv, rv = q[valid], r[valid]
        nv = len(qv)
        if nv < 2:
            return -1, 0.0, 0.0, nv
        qp, rp, vp = rm.chain_payload(qv, rv, cfg)
        f, pred = chain(jnp.asarray(qp), jnp.asarray(rp),
                        anchor_valid=jnp.asarray(vp))
        chains = chain_lib.backtrack(np.asarray(f)[:nv],
                                     np.asarray(pred)[:nv],
                                     min_score=cfg.min_chain_score)
        if not chains:
            return -1, 0.0, 0.0, nv
        score, members = chains[0]
        lo, hi = rm.chain_window(qv, rv, members, len(read), len(reference),
                                 cfg)
        if hi - lo < cfg.k:
            return -1, 0.0, score, nv
        a, b = rm.align_payload(read, reference[lo:hi].astype(np.int32), cfg)
        _, best = align_lib.sw_tiled(jnp.asarray(a), jnp.asarray(b),
                                     cfg.sw_params, tile_r=cfg.sw_tile,
                                     tile_c=cfg.sw_tile, tile_fn=tile)
        return lo, float(best), float(score), nv

    return map_read


def mapper_phase(seed: int, ref_len: int = REF_LEN,
                 reads_per_profile: int = READS_PER_PROFILE):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.apps import read_mapper as rm
    from repro.data import genomics
    from repro.kernels import mode, ops
    from repro.runtime import KernelService, Request, ServiceConfig

    if not mode.use_kernels() or mode.interpret():
        raise AssertionError("kernel mode: the platform did not choose the "
                             "compiled Pallas stages")
    cfg = rm.MapperConfig(mode="squire")
    t0 = time.perf_counter()
    reference = genomics.make_reference(ref_len, seed=seed)
    mapper = rm.ReadMapper(reference, cfg)
    jax.block_until_ready(mapper.index)
    log(f"mapper: reference {ref_len} bases, index "
        f"{mapper.index.hashes.shape[0]} minimizers on "
        f"{mapper.index.hashes.devices()} in "
        f"{time.perf_counter() - t0:.3f} s")

    reads, truths, names = [], [], []
    for i, name in enumerate(PROFILES):
        for read, start in genomics.sample_reads(
                reference, genomics.PROFILE_BY_NAME[name], reads_per_profile,
                seed=seed + 1 + i):
            reads.append(read)
            truths.append(start)
            names.append(name)
    log(f"mapper: {len(reads)} reads, lengths "
        f"{sorted(len(r) for r in reads)}")

    # the stages' programs hold compiled kernels
    qp, rp, vp = rm.chain_payload(np.arange(600, dtype=np.int32),
                                  np.arange(600, dtype=np.int32), cfg)
    assert_kernel("chain", jax.jit(rm.chain_stage(cfg.mode, cfg.band_T))
                  .lower(qp, rp, vp).as_text())
    t = cfg.sw_tile
    f32 = jnp.zeros((t,), jnp.float32)
    assert_kernel("dp tile (sw)", jax.jit(ops.make_sw_tile_fn()).lower(
        f32, f32, jnp.float32(0), f32, f32).as_text())
    padded, n = rm.seed_payload(reads[0], cfg)
    seed_fn = rm._seed_fn(cfg.k, cfg.w, cfg.max_occ, cfg.num_workers)
    assert_kernel("radix rank (seed sort)", jax.jit(seed_fn).lower(
        mapper.index.hashes, mapper.index.positions, padded, n).as_text())

    t0 = time.perf_counter()
    got = mapper.map_reads(reads[:1])
    log(f"mapper: first read (compiles) {time.perf_counter() - t0:.3f} s")
    t0 = time.perf_counter()
    got = mapper.map_reads(reads)
    t_map = time.perf_counter() - t0
    log(f"mapper: ReadMapper {len(reads)} reads in {t_map:.3f} s")

    svc = KernelService(ServiceConfig(mapper=cfg), reference=reference)
    t0 = time.perf_counter()
    batched = svc.submit([Request("map", {"read": r}) for r in reads])
    log(f"mapper: KernelService {len(reads)} reads in "
        f"{time.perf_counter() - t0:.3f} s (compiles included)")

    t0 = time.perf_counter()
    ref_map = _reference_mapper(reference, mapper.index, cfg)
    want = [ref_map(r) for r in reads]
    log(f"mapper: jnp reference in {time.perf_counter() - t0:.3f} s")

    for i, (a, b, w) in enumerate(zip(got, batched, want)):
        mine = (a.pos, a.sw_score, a.chain_score, a.n_anchors)
        if mine != w or (b.pos, b.sw_score, b.chain_score,
                         b.n_anchors) != w:
            raise AssertionError(
                f"read {i} ({names[i]}): ReadMapper {mine}, service "
                f"{(b.pos, b.sw_score, b.chain_score, b.n_anchors)}, "
                f"jnp reference {w}")
    log("mapper: positions, SW and chain scores equal the jnp reference "
        f"for all {len(reads)} reads (ReadMapper and KernelService)")
    for name in PROFILES:
        idx = [i for i, nm in enumerate(names) if nm == name]
        acc = rm.mapping_accuracy([got[i] for i in idx],
                                  [truths[i] for i in idx])
        log(f"mapper: {name} mapping accuracy {acc}")
    log(f"mapper: peak_bytes_in_use {peak_bytes()}")


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------

def init_params(cfg, seed: int):
    """Random weights from ``seed``, stored in bf16."""
    import jax
    import jax.numpy as jnp

    from repro.models import transformer as T

    def init(key):
        params = T.init_model(key, cfg)
        return jax.tree_util.tree_map(
            lambda x: x.astype(jnp.bfloat16)
            if jnp.issubdtype(x.dtype, jnp.floating) else x, params)

    params = jax.jit(init)(jax.random.PRNGKey(seed))
    return jax.block_until_ready(params)


def make_prompts(cfg, seed: int, n: int = N_REQUESTS,
                 lens=PROMPT_LENS):
    import numpy as np
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, int(rng.integers(lens[0],
                                                        lens[1] + 1)))
            .astype(np.int32) for _ in range(n)]


def run_scheduler(cfg, params, prompts, new_tokens: int, num_slots: int,
                  mesh_shards=None, mesh=None):
    from repro.serve import Scheduler, SchedulerConfig

    max_len = -(-(max(len(p) for p in prompts) + new_tokens) // 16) * 16
    sched = Scheduler(cfg, params, SchedulerConfig(
        num_slots=num_slots, max_len=max_len, allocator="paged",
        max_new_tokens=new_tokens, cache_requests=False,
        mesh_shards=mesh_shards), mesh=mesh)
    t0 = time.perf_counter()
    rids = sched.submit(prompts)
    sched.drain()
    wall = time.perf_counter() - t0
    return [sched.results[r].tokens for r in rids], wall, sched


def serving_phase(cfg, seed: int, n: int = N_REQUESTS, lens=PROMPT_LENS,
                  new_tokens: int = NEW_TOKENS):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import transformer as T
    from repro.serve import generate

    t0 = time.perf_counter()
    params = init_params(cfg, seed)
    nbytes = sum(x.nbytes for x in jax.tree_util.tree_leaves(params))
    log(f"serving: {cfg.name} {cfg.num_layers}L d_model {cfg.d_model} "
        f"vocab {cfg.vocab}, {nbytes} bytes of bf16 weights in "
        f"{time.perf_counter() - t0:.3f} s")
    prompts = make_prompts(cfg, seed, n, lens)
    log(f"serving: prompt lengths {[len(p) for p in prompts]}, "
        f"{new_tokens} greedy new tokens each")

    streams, wall, sched = run_scheduler(cfg, params, prompts, new_tokens, n)
    log(f"serving: Scheduler(paged, {n} slots) drained {n} requests in "
        f"{wall:.3f} s (compiles included), {sched.counters['steps']} steps")
    streams2, wall2, _ = run_scheduler(cfg, params, prompts, new_tokens, n)
    log(f"serving: Scheduler(paged) warm rerun {wall2:.3f} s, "
        f"{n * new_tokens / wall2:.1f} generated tok/s")

    # per request, at the Scheduler's batch shape (serve.generate: each
    # batch size compiles to its own rounding on the TPU)
    t0 = time.perf_counter()
    oracle = [generate(params, cfg, p, new_tokens, batch=n)[0]
              for p in prompts]
    log(f"serving: per-request generate(batch={n}) in "
        f"{time.perf_counter() - t0:.3f} s")
    for i, (s, s2, o) in enumerate(zip(streams, streams2, oracle)):
        if not (np.array_equal(s, o) and np.array_equal(s2, o)):
            raise AssertionError(f"request {i}: Scheduler {s.tolist()} / "
                                 f"{s2.tolist()} != generate {o.tolist()}")
    log(f"serving: all {n} Scheduler streams token-identical to generate")

    # logits vs the float32 forward at "highest" matmul precision
    tokens = jnp.asarray(prompts[0])[None]
    fwd = jax.jit(lambda p, c, t: T.apply_model(p, c, tokens=t)[0],
                  static_argnums=1)
    lo = np.asarray(fwd(params, cfg, tokens), np.float32)
    cfg32 = dataclasses.replace(cfg, dtype=jnp.float32)
    params32 = jax.tree_util.tree_map(
        lambda x: x.astype(jnp.float32)
        if jnp.issubdtype(x.dtype, jnp.floating) else x, params)
    with jax.default_matmul_precision("highest"):
        hi = np.asarray(fwd(params32, cfg32, tokens), np.float32)
    del params32
    if not (np.isfinite(lo).all() and np.isfinite(hi).all()):
        raise AssertionError("non-finite logits")
    diff = (lo - hi)[0]
    std = float(hi.std())
    rms = np.sqrt(np.mean(diff * diff, axis=-1)) / std
    err = float(np.median(rms))
    log(f"serving: prompt of {tokens.shape[1]} tokens, logits {lo.shape}, "
        f"std(f32) {std!r}: max |bf16 - f32| / std "
        f"{float(np.abs(diff).max()) / std!r}; RMS(bf16 - f32) / std by "
        f"position: median {err!r} (band {LOGIT_BAND}), max "
        f"{float(rms.max())!r} at position {int(rms.argmax())}, positions "
        f"0..3 {rms[:4].tolist()}")
    if not err <= LOGIT_BAND:
        raise AssertionError(f"logit error {err} above band {LOGIT_BAND}")
    log(f"serving: peak_bytes_in_use {peak_bytes()}")


def sharded_phase(cfg, seed: int, chips: int, n: int = N_REQUESTS,
                  lens=PROMPT_LENS, new_tokens: int = NEW_TOKENS):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_worker_mesh

    mesh = make_worker_mesh(chips, axis="slots")
    params = jax.device_put(init_params(cfg, seed),
                            NamedSharding(mesh, P()))
    prompts = make_prompts(cfg, seed, n, lens)
    want, wall1, _ = run_scheduler(cfg, params, prompts, new_tokens, n,
                                   mesh_shards=1)
    log(f"sharded: mesh_shards=1 ({n} slots) drained {n} requests in "
        f"{wall1:.3f} s")
    # each device holds as many slots as the one-device pool, so the
    # per-device programs have its batch shape (and its rounding)
    got, wall4, sched = run_scheduler(cfg, params, prompts, new_tokens,
                                      n * chips, mesh_shards=chips, mesh=mesh)
    log(f"sharded: mesh_shards={chips} ({n} slots per device) drained {n} "
        f"requests in {wall4:.3f} s (compiles included), placed "
        f"{[sched._shard_placed[s] for s in range(chips)]}")
    for i, (g, w) in enumerate(zip(got, want)):
        if not np.array_equal(g, w):
            raise AssertionError(f"request {i}: mesh_shards={chips} "
                                 f"{g.tolist()} != mesh_shards=1 "
                                 f"{w.tolist()}")
    log(f"sharded: all {n} streams token-identical to mesh_shards=1")
    backing = sched.slots.backing
    leaves = jax.tree_util.tree_leaves((backing.dense, backing.paged))
    spans = {len(x.sharding.device_set) for x in leaves}
    if spans != {chips}:
        raise AssertionError(f"cache leaves span {spans} devices, "
                             f"not {chips}")
    log(f"sharded: {len(leaves)} cache leaves (the fused steps' outputs) "
        f"each span {chips} devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the sharded serving path on 4 chips")
    args = ap.parse_args(argv)

    from repro.launch import compile_cache
    log(f"compile cache: {compile_cache.configure()}")
    import jax

    from repro import configs

    dev = require_tpu()
    devices = jax.devices()
    log(f"device: {dev.platform} {dev.device_kind} x{len(devices)}")
    if len(devices) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, found {len(devices)}")
    cfg = configs.get_config(ARCH)
    t0 = time.perf_counter()
    if args.chips == 1:
        mapper_phase(args.seed)
        log(f"mapper phase {time.perf_counter() - t0:.3f} s")
        t0 = time.perf_counter()
        serving_phase(cfg, args.seed)
        log(f"serving phase {time.perf_counter() - t0:.3f} s")
    else:
        sharded_phase(cfg, args.seed, args.chips)
        log(f"sharded phase {time.perf_counter() - t0:.3f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
