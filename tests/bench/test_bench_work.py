"""Work counts and the peaks table."""

import json
from pathlib import Path

import jax
import pytest

from bench import work

ROOT = Path(__file__).resolve().parents[2]
RWKV = json.loads((ROOT / "bench/configs/rwkv6-1.6b.json").read_text())


@pytest.fixture(scope="module")
def program_params():
    from repro import configs
    from repro.models import transformer as T

    cfg = configs.get_config("rwkv6-1.6b")
    return jax.eval_shape(lambda k: T.init_model(k, cfg),
                          jax.random.PRNGKey(0))


def test_rwkv_flops_per_token_tie_to_the_parameter_count(program_params):
    leaves = jax.tree_util.tree_leaves_with_path(program_params)
    total = sum(x.size for _, x in leaves)
    embed = program_params["embed"]["table"].size
    vectors = sum(x.size for _, x in leaves if x.ndim <= 2
                  and x.shape[-1] == RWKV["d_model"] and x.size != embed
                  and x.shape != program_params["unembed"]["table"].shape)
    vectors += sum(x.size for p, x in leaves if "'u'" in str(p))
    assert work.rwkv_matmul_params(RWKV) + work.rwkv_vector_params(RWKV) \
        == total - embed
    assert work.rwkv_matmul_params(RWKV) == total - embed - vectors
    wkv = RWKV["num_layers"] * work.WKV_OPS_PER_ELEMENT * \
        RWKV["d_model"] * RWKV["head_size"]
    assert work.rwkv_flops_per_token(RWKV) == \
        2 * (total - embed - vectors) + wkv
    # twice the 1.45e9 multiplied weights (1.6e9 with the embedding)
    assert 2.8e9 < work.rwkv_flops_per_token(RWKV) < 3.0e9


def test_decode_tick_bytes_hold_the_weights_and_every_live_state():
    state = work.rwkv_state_bytes(RWKV)
    heads = RWKV["d_model"] // RWKV["head_size"]
    assert state == RWKV["num_layers"] * 4 * (
        heads * RWKV["head_size"] ** 2 + 2 * RWKV["d_model"])
    _, one = work.rwkv_step_work(RWKV, 1, 1)
    _, many = work.rwkv_step_work(RWKV, 128, 1)
    assert one > work.rwkv_weight_bytes(RWKV) > 2.8e9
    assert many - one == 127 * (2 * state + 2 * RWKV["d_model"])
    flops, _ = work.rwkv_step_work(RWKV, 128, 32)
    assert flops == 128 * 32 * work.rwkv_flops_per_token(RWKV)


def test_sw_ops_are_cells_times_ops_per_cell():
    ops, nbytes = work.sw_work(1000, 1100)
    assert ops == 1000 * 1100 * work.SW_OPS_PER_CELL
    assert nbytes == 1000 + 1100 + 4


def test_chain_ops_are_anchors_times_band():
    ops, nbytes = work.chain_work(300, 64)
    assert ops == 300 * 64
    assert nbytes == 4 * (300 * 64 + 3 * 300)


def test_peaks_by_device_kind_and_unknown_kinds_raise():
    p = work.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in p["source"]
    with pytest.raises(KeyError):
        work.peaks("cpu")
    # the roofline takes the larger of the two bounds
    assert work.roofline_s(197e12, 0, p) == pytest.approx(1.0)
    assert work.roofline_s(0, 819e9, p) == pytest.approx(1.0)
