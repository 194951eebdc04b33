"""Compile every Pallas kernel for a described TPU v5e chip, at real widths.

Nothing runs: the TPU compiler installed beside JAX compiles for a chip
that is described, not attached, and refuses what the chip would refuse
(unaligned slices, unsupported primitives, block shapes off the (8, 128)
tiling, VMEM overflows) — the faults interpret mode cannot see. Each test
asserts the kernel reached Mosaic (``tpu_custom_call`` in the compiled
HLO).

The topology is described inside a module fixture, never at import: only
one process may hold the TPU library, and test workers import every test
file.
"""

import functools

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels import mode, ops


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture()
def chip(topo, monkeypatch):
    """Shape factory on one described chip, with the kernels in compiled
    mode (the platform here is the CPU, so the mode is steered) and the
    persistent compile cache off (a TPU entry cannot be read back here)."""
    monkeypatch.setenv("TPU_LOG_DIR", "disabled")
    monkeypatch.setattr(mode, "interpret", lambda: False)
    monkeypatch.setattr(mode, "use_kernels", lambda: True)
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    jax.clear_caches()
    one = SingleDeviceSharding(topo.devices[0])
    yield lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one)
    jax.clear_caches()
    jax.config.update("jax_enable_compilation_cache", prev)


def _assert_kernel(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text


def test_chain_scan_compiles(chip):
    # Table III: ~53k anchors per input; band T=64 padded to 128 lanes
    n = 53_248
    _assert_kernel(ops.chain_scan, chip((n, 128)), chip((n,)))


def test_chain_stage_batched_compiles(chip):
    # the mapper/service chain stage: dense fission scores + the kernel,
    # vmapped over a batch of anchor sets
    from repro.apps import read_mapper as rm
    fn = jax.vmap(rm._chain_fn_pallas(64))
    _assert_kernel(fn, chip((4, 4096), jnp.int32), chip((4, 4096), jnp.int32),
                   chip((4, 4096), jnp.bool_))


@pytest.mark.parametrize("kind", ["dtw", "sw"])
def test_dp_tile_compiles(chip, kind):
    t = 128
    fn = functools.partial(ops.dp_tile, kind=kind)
    _assert_kernel(fn, chip((t,)), chip((t,)), chip(()), chip((t,)),
                   chip((t,)))


def test_dp_tile_batched_odd_width_compiles(chip):
    # the service's batched SW tile (vmap) at the mapper's 64-wide tile
    t = 64
    fn = jax.vmap(ops.make_sw_tile_fn())
    _assert_kernel(fn, chip((8, t)), chip((8, t)), chip((8,)),
                   chip((8, t)), chip((8, t)))


def test_radix_rank_compiles(chip):
    from repro.kernels.radix_rank import radix_rank_pallas
    fn = functools.partial(radix_rank_pallas, shift=8)
    _assert_kernel(fn, chip((64, 4096), jnp.uint32))


def test_radix_sort_compiles(chip):
    # core.sort takes the rank kernel on the chip (the seed stage's sort)
    from repro.core import sort as rsort
    fn = functools.partial(rsort.radix_sort, num_chunks=8, min_parallel=0)
    _assert_kernel(fn, chip((16_200,), jnp.uint32), chip((16_200,), jnp.int32))


def test_ssm_scan_compiles(chip):
    # rwkv6-1.6b: 32 heads of 64, one 512-token prompt
    shp = (32, 512, 64)
    _assert_kernel(functools.partial(ops.ssm_scan, chunk=64), chip(shp),
                   chip(shp), chip(shp), chip(shp), chip((64,)))


def test_flash_attention_compiles(chip):
    from repro.kernels.flash_attention import flash_attention_pallas
    q = chip((1, 8, 1024, 128), jnp.bfloat16)
    kv = chip((1, 2, 1024, 128), jnp.bfloat16)
    _assert_kernel(flash_attention_pallas, q, kv, kv)
