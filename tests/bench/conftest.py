"""The benchmark's CPU tests import it as the package ``bench`` from the
checkout's root."""

import sys
from pathlib import Path

ROOT = str(Path(__file__).resolve().parents[2])
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import pytest  # noqa: E402


def tiny_cell(cell: str):
    """(config, mix) of ``cell`` cut to a size the CPU runs in seconds:
    the program's reduced RWKV preset with two documents in flight, a
    200 kb genome with 500-base reads. Limits are the cells' own except
    the logit gap: the tiny model's logits spread about 0.2 (full width:
    about 1), and near ties in its small vocabulary flip under bf16
    rounding. On the CPU its sound runs read 0-0.016 (14 seeds), its
    int8 control 0.004-0.026, and the faults of test_bench_faults
    0.54-0.90 (8 runs), so 0.1 lies between sound runs and faults."""
    from bench import run

    _, config, mix = run.resolve(cell)
    if config["system"] == "mapper":
        config = dict(config, genome_bases=200_000, read_mean=500,
                      read_sd=150, sw_tile=16)
    else:
        config = dict(config, preset="reduced", num_layers=2, d_model=64,
                      d_ff=128, vocab=128, head_size=16, num_slots=8,
                      check_requests=3,
                      limits=dict(config["limits"], logit_gap_max=0.1))
        mix = dict(mix, in_flight=2, waves=200,
                   prompt=dict(mix["prompt"], median=100, lo=64, hi=200),
                   output=dict(lo=12, hi=40), drain_s=60)
    return config, mix


@pytest.fixture()
def tiny():
    return tiny_cell


@pytest.fixture()
def fresh_programs():
    """Programs traced under a patched module must not outlive the test."""
    import jax

    jax.clear_caches()
    yield
    jax.clear_caches()
