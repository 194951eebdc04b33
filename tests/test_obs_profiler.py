"""Spans on the profiler's clock: the Tracer's spans reach a JAX profiler
trace (with their args) while it records and cost one check otherwise;
the mapper's stages, the pipeline's fences and the scheduler's phases
land there nested as the code runs them; instrumented jit calls are
named; and the serving steps' named scopes reach the compiled programs'
op metadata without changing a single output bit."""

import contextlib
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro import configs
from repro.apps.read_mapper import MapperConfig
from repro.data import genomics
from repro.models import transformer as T
from repro.obs import Tracer, instrumented_jit
from repro.obs import trace as obs_trace
from repro.runtime import KernelService, Request, ServiceConfig
from repro.serve import Scheduler, SchedulerConfig
from repro.serve import engine


def _record(logdir, fn):
    """Run ``fn`` under the profiler; return (fn's result, the host
    events named ``repro.*`` as (name, start_ns, end_ns, stats))."""
    jax.profiler.start_trace(str(logdir))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(logdir), "**", "*.xplane.pb"),
                      recursive=True)
    data = jax.profiler.ProfileData.from_file(path)
    spans = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, int(e.start_ns),
                     int(e.start_ns + e.duration_ns), dict(e.stats))
                    for e in line.events if e.name.startswith("repro."))
    return out, sorted(spans, key=lambda s: s[1])


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def _inside(inner, outer) -> bool:
    return outer[1] <= inner[1] and inner[2] <= outer[2]


# --------------------------------------------------------------------------
# the tracer
# --------------------------------------------------------------------------

def test_span_reaches_the_profiler_with_its_args_and_the_ring_stays_empty(
        tmp_path):
    tr = Tracer(enabled=False)

    def work():
        with tr.span("align.walk", "map", tiles=12, batch=3):
            jnp.ones(4).block_until_ready()

    _, spans = _record(tmp_path, work)
    walk, = _named(spans, "repro.map.align.walk")
    assert walk[3] == {"tiles": 12, "batch": 3}
    assert walk[2] > walk[1]
    assert len(tr.events) == 0


def test_span_is_the_shared_noop_while_the_profiler_is_off():
    assert not obs_trace._profiling()
    tr = Tracer(enabled=False)
    assert tr.span("seed", "map") is obs_trace._NOOP
    assert tr.span("decode-tick", "scheduler", live=3) is obs_trace._NOOP


def test_enabled_ring_and_profiler_both_record_a_span(tmp_path):
    tr = Tracer(enabled=True)

    def work():
        with tr.span("decode-tick", "scheduler", live=2):
            pass
        # caller-stamped spans stay ring-only
        tr.complete("jit-compile", "dispatcher", 0.0, 1.0, fn="f")

    _, spans = _record(tmp_path, work)
    assert [s[0] for s in spans] == ["repro.scheduler.decode-tick"]
    assert spans[0][3] == {"live": 2}
    assert [(e.name, e.ph) for e in tr.events] == [("decode-tick", "X"),
                                                  ("jit-compile", "X")]
    # and with the profiler off again, only the ring records
    with tr.span("decode-tick", "scheduler"):
        pass
    assert len(tr.events) == 3


def test_instrumented_jit_names_each_call_while_profiling(tmp_path):
    f = instrumented_jit(jax.jit(lambda x: x * 3), name="obs_prof_fn",
                         prefix="test.obsprof")
    f(np.float32(1.0))                               # compile outside

    out, spans = _record(tmp_path, lambda: [f(np.float32(2.0)),
                                            f(np.float32(4.0))])
    assert [float(x) for x in out] == [6.0, 12.0]
    assert len(_named(spans, "repro.jit.obs_prof_fn")) == 2


# --------------------------------------------------------------------------
# the mapper's stages
# --------------------------------------------------------------------------

def test_mapper_stages_nest_and_count_their_tiles(tmp_path):
    ref = genomics.make_reference(12_000, seed=0)
    prof = genomics.ReadProfile("TEST", 400, 80, 0.93)
    reads = [r for r, _ in genomics.sample_reads(ref, prof, 3, seed=1)]
    mcfg = MapperConfig(mode="squire", sw_tile=64)
    svc = KernelService(ServiceConfig(mapper=mcfg), reference=ref)
    svc.index                                        # built outside
    reqs = [Request("map", {"read": r}) for r in reads]
    warm = svc.submit(reqs)                          # compiles outside
    before = dict(svc.metrics())

    got, spans = _record(tmp_path, lambda: svc.submit(reqs))
    assert [g.pos for g in got] == [w.pos for w in warm]
    assert all(g.pos >= 0 for g in got)

    seed, = _named(spans, "repro.map.seed")
    chain, = _named(spans, "repro.map.chain")
    align, = _named(spans, "repro.map.align")
    assert seed[2] <= chain[1] and chain[2] <= align[1]
    walks = _named(spans, "repro.map.align.walk")
    fetches = _named(spans, "repro.map.align.fetch")
    assert walks and len(fetches) == len(walks)
    assert all(_inside(s, align) for s in walks + fetches)
    fences = _named(spans, "repro.runtime.fence")
    assert fences and all(_inside(f, seed) for f in fences)

    # align_tiles: nr * nc tile calls per walk, the walks' own args
    after = svc.metrics()
    assert after["align_walks"] - before["align_walks"] == len(walks)
    tiles = after["align_tiles"] - before["align_tiles"]
    assert tiles == sum(w[3]["tiles"] for w in walks) > 0
    assert sum(w[3]["batch"] for w in walks) == len(reads)


@pytest.mark.parametrize("na,nb,tile,want", [
    (256, 512, 64, 4 * 8), (300, 500, 64, 5 * 8), (64, 64, 64, 1),
    (256, 768, 32, 8 * 24)])
def test_align_tiles_are_rows_times_columns_of_tiles(na, nb, tile, want):
    ref = genomics.make_reference(2_000, seed=0)
    svc = KernelService(ServiceConfig(mapper=MapperConfig(sw_tile=tile)),
                        reference=ref)
    adapter = svc._adapters["map"]
    assert adapter._walk_tiles(na, nb) == want
    base = KernelService(ServiceConfig(
        mapper=MapperConfig(mode="baseline")), reference=ref)
    assert base._adapters["map"]._walk_tiles(na, nb) == 0   # no walk


def test_fence_histogram_is_gone():
    from repro.obs import REGISTRY
    from repro.runtime import run_pipelined

    list(run_pipelined(iter([jnp.ones(2)]), lambda x: x + 1))
    assert not any(k.startswith("runtime.pipeline.fence_ms")
                   for k in REGISTRY.snapshot())


# --------------------------------------------------------------------------
# the scheduler's phases and the serving steps' scopes
# --------------------------------------------------------------------------

@pytest.fixture(scope="module")
def rwkv():
    cfg = configs.reduced_config("rwkv6-1.6b")
    return cfg, T.init_model(jax.random.PRNGKey(0), cfg)


def _prompts(vocab, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


def _sched(cfg, params, **kw):
    return Scheduler(cfg, params, SchedulerConfig(
        num_slots=2, max_len=40, prefill_chunk=4, cache_requests=False,
        allocator="paged", block_size=8, **kw))


def test_scheduler_phases_reach_the_profiler(rwkv, tmp_path):
    cfg, params = rwkv
    prompts = _prompts(cfg.vocab, (11, 7))
    warm = _sched(cfg, params)
    for p in prompts:
        warm.submit([p], max_new_tokens=4)
    warm.drain()                                     # compiles outside
    sched = _sched(cfg, params)
    for p in prompts:
        sched.submit([p], max_new_tokens=4)

    _, spans = _record(tmp_path, sched.drain)
    steps = sched.counters["steps"]
    assert len(_named(spans, "repro.scheduler.admit")) == steps
    ticks = _named(spans, "repro.scheduler.decode-tick")
    chunks = _named(spans, "repro.scheduler.prefill-chunk")
    assert len(ticks) == sched.counters["decode_steps"]
    assert len(chunks) == sched.counters["chunk_steps"] > 0
    name = f"[{cfg.name}]"
    chunk_jit = _named(spans, f"repro.jit.paged_chunk_step{name}")
    tick_jit = _named(spans, f"repro.jit.paged_decode_step{name}")
    assert len(chunk_jit) == len(chunks) and len(tick_jit) == len(ticks)
    assert all(_inside(j, c) for j, c in zip(chunk_jit, chunks))
    assert all(_inside(j, t) for j, t in zip(tick_jit, ticks))
    assert {c[3]["chunk"] for c in chunks} == {4}


def _spy_chunk_step(monkeypatch):
    """Record the abstract arguments of the first paged chunk step."""
    seen = []
    real = engine.jit_paged_chunk_step

    def spy(cfg):
        fn = real(cfg)

        def call(*args):
            if not seen:
                seen.append((fn, jax.tree_util.tree_map(
                    lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)
                    if hasattr(x, "shape") else x, args)))
            return fn(*args)
        return call

    monkeypatch.setattr(engine, "jit_paged_chunk_step", spy)
    return seen


def test_chunk_step_ops_carry_the_named_scopes(rwkv, monkeypatch):
    cfg, params = rwkv
    seen = _spy_chunk_step(monkeypatch)
    sched = _sched(cfg, params)
    sched.submit(_prompts(cfg.vocab, (11,)), max_new_tokens=2)
    sched.drain()
    fn, args = seen[0]
    hlo = fn.__wrapped__.lower(*args).compile().as_text()
    for scope in ("state_gather", "state_scatter", "wkv", "lm_head"):
        assert f"/{scope}/" in hlo, scope


def test_named_scopes_change_no_output_bit(rwkv, monkeypatch):
    """Scores (every chunk position's logits) and greedy streams with the
    scopes equal those of the same programs traced without them."""
    cfg, params = rwkv
    prompts = _prompts(cfg.vocab, (13, 9, 6), seed=3)

    def serve():
        sched = _sched(cfg, params)
        rids = sched.score(prompts)
        gen = [sched.submit([p], max_new_tokens=5)[0] for p in prompts]
        sched.drain()
        return ([sched.results[r].logprobs for r in rids],
                [sched.results[r].tokens for r in gen])

    jax.clear_caches()
    scored, tokens = serve()
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    jax.clear_caches()
    try:
        plain_scored, plain_tokens = serve()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    for a, b in zip(scored + tokens, plain_scored + plain_tokens):
        np.testing.assert_array_equal(a, b)
