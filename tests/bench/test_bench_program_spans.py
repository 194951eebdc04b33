"""The program's own profiler spans beside the harness's: the trace
loader keeps the harness's ``bench.*`` spans as they were and none of the
program's ``repro.*`` spans, so every existing reading of a trace stays
what it was; and the align-tile count per read reads the service's
counter, or nothing where the program has none."""

import glob
import importlib.util
import os
from pathlib import Path

import pytest

import jax
import jax.numpy as jnp

from bench import trace
from bench.record import Run, Window
from repro.obs import Tracer

ROOT = Path(__file__).resolve().parents[2]


def _reader(name):
    spec = importlib.util.spec_from_file_location(
        name.replace(".", "_"), ROOT / "bench/metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_load_keeps_the_harness_spans_and_drops_the_programs(tmp_path):
    """Program spans nested in the harness's, as a traced scheduler step
    opens them: the loaded trace holds the harness's spans alone."""
    tr = Tracer(enabled=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(2):
                with jax.profiler.TraceAnnotation("bench.step"):
                    with tr.span("admit", "scheduler"):
                        pass
                    with tr.span("prefill-chunk", "scheduler", slots=2):
                        jnp.ones(8).block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    raw = trace.load(path)
    assert [e[0] for e in raw["host"]] == ["bench.window", "bench.step",
                                           "bench.step"]
    assert set(raw) == {"devices", "host"}


@pytest.mark.parametrize("counters,items,want", [
    ({"align_tiles": 1680, "align_walks": 3}, 2, 840.0),
    ({"align_tiles": 0}, 2, None),
    ({"submits": 1}, 2, None),          # a program without the counter
    ({"align_tiles": 1680}, 0, None),
])
def test_align_tiles_per_read(counters, items, want):
    read = _reader("align_tiles_per_read.map").read
    r = Run(config={}, peaks={},
            window=Window(0, 1, [{}] * items, items, 0, counters))
    assert read(r) == want
