"""The reduction from a profiler trace to busy time, idle share, kernel
time and idle gaps, on a synthetic trace with hand-computed intervals."""

import pytest

from bench import readers, trace
from bench.record import Run, Window

MS = 1_000_000  # ns


def raw():
    """Window [0, 100 ms). Device ops: [10, 30) and [20, 40) overlap
    (busy 30 ms), a kernel [50, 55), one op straddling the window's end
    [95, 120) (5 ms inside). Host spans: two steps and a submit."""
    ops = [["%fusion.1 = f32[8]", 10 * MS, 20 * MS],
           ["%fusion.2 = f32[8]", 20 * MS, 20 * MS],
           ["%dp_tile_pallas.1 = custom-call", 50 * MS, 5 * MS],
           ["%copy.3 = f32[8]", 95 * MS, 25 * MS]]
    modules = [["jit_run(11)", 10 * MS, 30 * MS],
               ["jit_run(22)", 50 * MS, 5 * MS],
               ["jit_run(33)", 60 * MS, 4 * MS],
               ["jit__reset(44)", 95 * MS, 25 * MS]]
    host = [["bench.window", 0, 100 * MS],
            ["bench.step", 5 * MS, 55 * MS],
            ["bench.step", 62 * MS, 30 * MS],
            ["bench.submit", 56 * MS, 2 * MS],
            ["bench.step", 150 * MS, 10 * MS]]      # after the window
    return {"devices": {"/device:TPU:0": {"XLA Ops": ops,
                                          "XLA Modules": modules}},
            "host": host}


def test_busy_union_and_idle_share():
    t = trace.Trace(raw())
    assert t.window_s == pytest.approx(0.1)
    # [10, 40) + [50, 55) + [95, 100) = 30 + 5 + 5 ms
    assert t.busy_s() == pytest.approx(0.040)
    assert t.idle_share() == pytest.approx(0.6)


def test_union_merges_and_clips():
    assert trace.union([(5, 9), (1, 3), (2, 6)]) == [(1, 9)]
    assert trace.clip([(0, 10), (20, 30)], 5, 25) == [(5, 10), (20, 25)]


def test_kernel_time_by_name():
    t = trace.Trace(raw())
    assert t.op_seconds(r"dp_tile_pallas") == (pytest.approx(0.005), 1)
    assert t.op_seconds(r"chain_scan_pallas") == (0.0, 0)


def test_idle_gaps_by_innermost_harness_span():
    t = trace.Trace(raw())
    gaps = dict(t.idle_gaps())
    # idle: [0, 10) (the first step starts at 5: only the window covers
    # it), [40, 50) inside the first step, [55, 95) across the submit and
    # the second step (only the window covers all of it)
    assert gaps == {"bench.window": pytest.approx(0.050),
                    "bench.step": pytest.approx(0.010)}


def test_spans_and_busy_within_a_span():
    t = trace.Trace(raw())
    steps = t.spans("bench.step")
    assert [s[1] for s in steps] == [5 * MS, 62 * MS]   # the third is out
    assert t.busy_within(5 * MS, 60 * MS) == pytest.approx(0.035)
    assert t.busy_within(62 * MS, 92 * MS) == pytest.approx(0.0)


def test_step_programs_last_in_step_is_decode():
    run = Run(config={},
              window=Window(0, 1, [], 0, 0, {}), peaks={},
              trace=trace.Trace(raw()))
    decode, chunk = readers.step_programs(run)
    # step 1 holds jit_run(11) and jit_run(22): the last is the decode
    assert decode == pytest.approx(0.005)
    assert chunk == pytest.approx(0.030)
    # host time per step not covered by the device: (55 - 35) and 30 ms
    assert readers.host_ms_per_tick(run) == pytest.approx(25.0)


def test_a_trace_without_device_work_is_refused():
    r = raw()
    r["devices"] = {"/device:TPU:0": {"XLA Ops": [], "XLA Modules": []}}
    with pytest.raises(ValueError):
        trace.Trace(r)


def test_chunk_roofline_counts_state_per_chunk_of_the_scheduler():
    """The chunk steps' state bytes follow the chunk size the window
    recorded, not a copy of the program's default."""
    import importlib.util
    import json
    from pathlib import Path

    from bench import work

    root = Path(__file__).resolve().parents[2]
    spec = importlib.util.spec_from_file_location(
        "chunk_roofline", root / "bench/metrics/chunk_roofline.docs.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    config = json.loads((root / "bench/configs/rwkv6-1.6b.json").read_text())
    counters = {"prefill_tokens": 256, "chunk_steps": 2}   # bytes-bound
    got = {}
    for chunk in (32, 64):
        run = Run(config=config, peaks=work.peaks("TPU v5 lite"),
                  trace=trace.Trace(raw()),
                  window=Window(0, 1, [], 0, 0, counters,
                                info={"prefill_chunk": chunk}))
        flops, nbytes = readers.chunk_work(run, chunk)
        _, seconds = readers.step_programs(run)
        got[chunk] = mod.read(run)
        assert got[chunk] == pytest.approx(
            100 * work.roofline_s(flops, nbytes, run.peaks) / seconds)
    assert got[64] < got[32]        # half the state round trips


def test_busy_within_matches_a_count_by_nanosecond():
    """The bisected sum over the merged intervals equals busy time
    counted one nanosecond at a time, on random overlapping ops."""
    import random

    g = random.Random(3)
    ops = []
    for i in range(300):
        s = g.randrange(0, 2000)
        ops.append([f"%op.{i}", s, g.randrange(1, 40)])
    r = {"devices": {"/device:TPU:0": {"XLA Ops": ops}},
         "host": [["bench.window", 0, 2000]]}
    t = trace.Trace(r)
    busy = [False] * 2000
    for _, s, dur in ops:
        for x in range(s, min(s + dur, 2000)):
            busy[x] = True
    for _ in range(200):
        a = g.randrange(0, 2000)
        b = g.randrange(a, 2001)
        assert t.busy_within(a, b) * 1e9 == pytest.approx(sum(busy[a:b]))
