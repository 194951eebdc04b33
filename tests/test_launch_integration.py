"""Launcher integration: dry-run machinery on a smoke mesh (subprocess —
device count locks at first jax init) and the CLI entry points."""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

SRC = str(Path(__file__).resolve().parents[1] / "src")
ENV = {**os.environ, "PYTHONPATH": SRC}


def _run(code: str, timeout=540):
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=ENV, timeout=timeout)
    assert res.returncode == 0, res.stderr[-3000:]
    return res.stdout


@pytest.mark.slow
def test_dryrun_lower_compile_smoke_mesh():
    """Lower + compile a reduced train cell and a decode cell on a forced
    8-device mesh; assert roofline terms derive from the HLO."""
    out = _run(textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, math
        import jax
        from repro import configs
        from repro.configs.base import ShapeConfig
        from repro.launch import mesh as mesh_lib, roofline, specs
        from repro.launch.dryrun import build_cell
        from repro.sharding import configure

        mesh = mesh_lib.make_smoke_mesh()
        configure(mesh)
        cfg = configs.reduced_config("gemma-2b")
        shape = ShapeConfig("smoke_train", "train", seq_len=64,
                            global_batch=8)
        jfn, args, tokens, kind = build_cell(cfg, shape, mesh)
        with mesh:
            lowered = jfn.lower(*args)
            compiled = lowered.compile()
        hlo = compiled.as_text()
        summary = roofline.summarize(hlo, 1_000_000, tokens, "train")
        assert summary["hlo_flops_per_device"] > 0
        assert summary["dominant"] in ("compute", "memory", "collective")

        # decode cell too (cache machinery under shardings)
        shape_d = ShapeConfig("smoke_decode", "decode", seq_len=128,
                              global_batch=8)
        jfn2, args2, _, _ = build_cell(cfg, shape_d, mesh)
        with mesh:
            jfn2.lower(*args2).compile()
        configure(None)
        print("SMOKE_OK")
    """))
    assert "SMOKE_OK" in out


@pytest.mark.slow
def test_train_cli(tmp_path):
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--arch", "gemma-2b",
         "--reduced", "--steps", "4", "--batch", "2", "--seq", "16",
         "--log-every", "2", "--ckpt-dir", str(tmp_path)],
        capture_output=True, text=True, env=ENV, timeout=540)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "[train] done: 4 steps" in res.stdout
    assert any(d.name.startswith("step_") for d in tmp_path.iterdir())


@pytest.mark.slow
def test_serve_cli():
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.serve", "--arch", "rwkv6-1.6b",
         "--reduced", "--batch", "2", "--prompt-len", "8", "--gen", "4"],
        capture_output=True, text=True, env=ENV, timeout=540)
    assert res.returncode == 0, res.stderr[-2000:]
    assert "[serve] decode:" in res.stdout


@pytest.mark.parametrize("placed", [True, False])
def test_compile_cache_placement(tmp_path, placed):
    """With JAX_COMPILATION_CACHE_DIR set the entry points set nothing and
    compiles land there; without it the cache is the checkout's fixed
    .jax_cache/ (CPU subprocess: the config is process state)."""
    env = {**ENV, "JAX_PLATFORMS": "cpu"}
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    want = str(Path(SRC).parent / ".jax_cache")
    if placed:
        want = str(tmp_path / "cc")
        env["JAX_COMPILATION_CACHE_DIR"] = want
    code = textwrap.dedent("""
        import jax, jax.numpy as jnp
        from repro.launch import compile_cache
        print(compile_cache.configure())
        print(jax.config.jax_compilation_cache_dir)
        if {placed}:     # write an entry (thresholds off for this probe)
            jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
            jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
            jax.jit(lambda x: x * 2 + 1)(jnp.ones(3)).block_until_ready()
    """).format(placed=placed)
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env, timeout=300)
    assert res.returncode == 0, res.stderr[-3000:]
    assert res.stdout.split() == [want, want]
    if placed:
        assert any(Path(want).iterdir())


_DRYRUN_ARTIFACTS = sorted(
    (Path(__file__).resolve().parents[1] / "experiments" / "dryrun")
    .glob("*__single.json"))


@pytest.mark.skipif(
    not _DRYRUN_ARTIFACTS,
    reason="experiments/dryrun artifacts not generated — run "
           "`python -m repro.launch.dryrun --all --mesh single` "
           "(hours of 512-device compiles; see ROADMAP)")
def test_report_tables_render():
    from repro.launch import report
    t = report.roofline_table("single")
    assert t.count("\n") > 30            # 33 OK rows + header
    assert "dominant" in t.splitlines()[0]
