"""Fig. 9 analogue: the cache-size design-space exploration.

The paper sweeps worker L1 I/D cache sizes and picks 1 KB / 8 KB from the
MPKI knee. The TPU analogue (DESIGN.md §2) is the Pallas BlockSpec tile
size: the tile determines the VMEM working set exactly like the D-cache
determined the worker's locality. We sweep the DTW/SW tile and the
ssm_scan chunk, reporting the VMEM bytes each claims (`derived`) and the
interpret-mode wall-clock — the knee (VMEM large enough to amortize the
boundary traffic, small enough to fit) mirrors the paper's 8 KB choice.
"""

from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

from benchmarks import common
from repro.core import dtw as dtw_lib
from repro.core.wavefront import dp_tile_diagonal
from repro.core.dtw import _cell

TILES = (16, 32, 64, 128)
CHUNKS = (16, 32, 64)
CHAIN_BLOCKS = (8, 16, 32)
SORT_CHUNKS = (1, 2, 4)
BUCKETS = (256, 1024)       # anchor/sort shape buckets swept per-bucket


def vmem_dtw_tile(t: int) -> int:
    """fp32 bytes a (t x t) tile's working set claims in VMEM:
    tile + two diagonal buffers + boundaries + row/col inputs."""
    return 4 * (t * t + 2 * t + 2 * t + t + 1)


def vmem_ssm_chunk(c: int, d: int = 64) -> int:
    """4 (C, d) blocks + (d, d) state, fp32."""
    return 4 * (4 * c * d + d * d)


def bench_dtw_tiles(rows):
    rng = np.random.default_rng(0)
    n = 256
    s = jnp.asarray(rng.normal(size=n).astype(np.float32))
    r = jnp.asarray(rng.normal(size=n).astype(np.float32))
    tile_fn = jax.jit(lambda t, l, c, x, y: dp_tile_diagonal(
        _cell, t, l, c, x, y))
    for t in TILES:
        def fw(x, y, t=t):
            return dtw_lib.dtw_tiled(x, y, tile_r=t, tile_c=t,
                                     tile_fn=tile_fn)[1]
        us = common.time_fn(fw, s, r)
        rows.append(common.emit(f"fig9.dtw.tile{t}", us,
                                f"vmem_bytes={vmem_dtw_tile(t)}"))


def bench_ssm_chunks(rows):
    from repro.kernels import ops
    ks = jax.random.split(jax.random.PRNGKey(1), 4)
    b, t, d = 4, 512, 64
    r = jax.random.normal(ks[0], (b, t, d))
    w = jax.nn.sigmoid(jax.random.normal(ks[1], (b, t, d)) + 2)
    k = jax.random.normal(ks[2], (b, t, d))
    v = jax.random.normal(ks[3], (b, t, d))
    for c in CHUNKS:
        us = common.time_fn(ops.ssm_scan, r, w, k, v, None, c)
        rows.append(common.emit(f"fig9.ssm.chunk{c}", us,
                                f"vmem_bytes={vmem_ssm_chunk(c, d)}"))


def bench_chain_blocks(rows):
    """Chain DP block size, swept PER ANCHOR BUCKET: the best block moves
    with the bucket (short chains want small blocks), so rows carry the
    ``@b<bucket>`` suffix and land on per-bucket autotune keys."""
    from repro.apps import read_mapper as rm
    from repro.runtime.dispatch import Dispatcher
    rng = np.random.default_rng(2)
    d = Dispatcher()
    for nb in BUCKETS:
        r = np.sort(rng.integers(0, 50 * nb, (8, nb))).astype(np.int32)
        q = np.sort(rng.integers(0, 4 * nb, (8, nb))).astype(np.int32)
        vp = np.ones((8, nb), bool)
        for blk in CHAIN_BLOCKS:
            fn = rm._chain_fn(64, "blocked", blk)
            us = common.time_fn(lambda: d.run(fn, (q, r, vp)))
            rows.append(common.emit(
                f"fig9.chain.block{blk}@b{nb}", us,
                f"depth={common.depth_chain(nb, 64, blk)[1]}"))


def bench_sort_chunks(rows):
    """Radix-sort chunk count per sort bucket (Alg. 1 worker count)."""
    from repro.core import sort as rsort
    from repro.runtime.dispatch import Dispatcher
    rng = np.random.default_rng(3)
    d = Dispatcher()
    for nb in BUCKETS:
        keys = rng.integers(0, 2**32, (8, nb), dtype=np.uint32)
        vals = np.tile(np.arange(nb, dtype=np.int32), (8, 1))
        for c in SORT_CHUNKS:
            def fn(k, v, c=c):
                return rsort.radix_sort(k, v, num_chunks=c, min_parallel=0)
            us = common.time_fn(lambda: d.run(fn, (keys, vals)))
            rows.append(common.emit(
                f"fig9.sort.chunks{c}@b{nb}", us,
                f"depth={common.depth_radix(nb, max(c, 1))[1]}"))


def run(rows=None):
    rows = rows if rows is not None else []
    print("# fig9: BlockSpec/VMEM design-space sweep (cache-size analogue)")
    bench_dtw_tiles(rows)
    bench_ssm_chunks(rows)
    bench_chain_blocks(rows)
    bench_sort_chunks(rows)
    # seed the runtime autotuner: the sweep's fastest tile/chunk become
    # knobs a service opts into (ServiceConfig.tuned(Autotuner()) and
    # KernelService(tuner=Autotuner()) read them back).
    try:
        from repro.runtime.autotune import seed_from_fig9
        best = seed_from_fig9(rows)
        if best:
            print(f"# fig9: autotune cache seeded: {best}")
    except OSError as e:                      # read-only cache dir etc.
        print(f"# fig9: autotune cache not written ({e})")
    return rows


if __name__ == "__main__":
    run()
