"""Each cell, cut to a tiny size on the CPU, runs end to end through the
harness (everything but the look for a chip) and comes out correct."""

import json
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", [w["name"] for w in MAN["workloads"]])
def test_cell_runs_and_is_correct_at_a_tiny_size(cell, tiny):
    config, mix = tiny(cell)
    out = run.execute(cell, 2**31 + 17, 2.0, False, config=config, mix=mix)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {
        m["name"] for m in run.metric_names(cell, "end_to_end", MAN)}
    assert all(v["value"] > 0 for v in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert set(out["checks"]) == set(config["limits"])
    assert out["device"]["platform"] == "cpu"
    # warm-up covered every program the window (and a drain) ran
    assert out["programs_built_after_setup"] == 0
