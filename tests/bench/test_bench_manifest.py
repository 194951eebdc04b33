"""BENCHMARK.json: every name resolves to its files, names and units keep
to their characters, every per-layer metric's end-to-end metric is
reported where it is read, and the command refuses anything but a TPU."""

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import run

ROOT = Path(__file__).resolve().parents[2]
MAN = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in MAN["workloads"]]


def test_top_level_keys_and_paths():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["command"] == ["python3", "bench/run.py"]
    for p in MAN["paths"]:
        assert (ROOT / p).is_dir()
    assert 1 <= MAN["run_seconds"] <= 51


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_its_files_by_name(cell):
    entry, config, mix = run.resolve(cell, MAN)
    assert entry["chips"] in (1, 4)
    assert (ROOT / "bench/traffic" / f"{mix['kind']}.py").is_file()
    assert (ROOT / "bench/systems" / f"{config['system']}.py").is_file()
    for kind in ("end_to_end", "per_layer"):
        for m in run.metric_names(cell, kind, MAN):
            assert hasattr(run.reader(m["name"]), "read")
    assert config["limits"], "every configuration states its limits"


def test_names_and_units_use_only_allowed_characters():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in MAN[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append(e["name"])
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for c in MAN["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    for w in MAN["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert 1 <= len(w["why"]) <= 200
    assert len(names) == len(set(names))


def test_every_per_layer_metric_moves_a_metric_its_cells_report():
    for m in MAN["per_layer"]:
        moved = [e for e in MAN["end_to_end"] if e["name"] == m["moves"]]
        assert len(moved) == 1, m["name"]
        for cell in m["workloads"]:
            assert cell in moved[0].get("workloads", CELLS), (m, cell)


def test_every_cell_reports_setup_an_end_to_end_and_a_per_layer_metric():
    for cell in CELLS:
        e2e = [m["name"] for m in run.metric_names(cell, "end_to_end", MAN)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert run.metric_names(cell, "per_layer", MAN)


def test_bounds():
    for m in MAN["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")


def _run(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "2147483651", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_run_refuses_a_non_tpu_device():
    got = _run(ROOT)
    assert got.returncode != 0
    assert "needs a TPU" in got.stderr
    assert '"correct"' not in got.stdout


def test_run_fails_with_only_the_benchmark_files(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    for p in MAN["paths"]:
        shutil.copytree(ROOT / p, tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    got = _run(tmp_path)
    assert got.returncode != 0
    assert '"correct"' not in got.stdout
