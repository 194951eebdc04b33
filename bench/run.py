"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of BENCHMARK.json's ``workloads``) names a
configuration, ``bench/configs/<config>.json``, and a traffic mix,
``bench/workloads/<traffic>.json``; the mix's ``kind`` names its driver,
``bench/traffic/<kind>.py``. Every metric is read by
``bench/metrics/<metric>.py``. Nothing here names a cell.

A run: refuses anything but a TPU with the chips the cell asks for; sets
up the system and warms every shape its traffic uses (``setup_s`` runs
from process start to the window's start); measures for ``--seconds``;
reads the device's peak memory; frees the program's state; checks what
the window produced against the plain reference (its seconds are the
line's ``check_s``, a field and not a metric); prints each compared
number beside its limit on standard error, and last on standard output
one JSON line. With ``--trace 1`` the window is traced by the JAX
profiler and the line carries the cell's per-layer metrics instead of
its end-to-end ones.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse
import importlib
import importlib.util
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def log(msg: str):
    print(f"[bench] {msg}", flush=True)


def manifest() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def resolve(cell: str, man: dict = None):
    """(workload entry, configuration, mix) of ``cell``, by name."""
    man = man or manifest()
    entries = [w for w in man["workloads"] if w["name"] == cell]
    if len(entries) != 1:
        raise SystemExit(f"no workload {cell!r} in BENCHMARK.json")
    entry = entries[0]
    cfg_entry = [c for c in man["configs"] if c["name"] == entry["config"]]
    if len(cfg_entry) != 1:
        raise SystemExit(f"no configuration {entry['config']!r}")
    config = json.loads((ROOT / cfg_entry[0]["file"]).read_text())
    mix = json.loads((BENCH / "workloads" / f"{entry['traffic']}.json")
                     .read_text())
    return entry, config, mix


def metric_names(cell: str, kind: str, man: dict) -> list:
    """The cell's metrics of ``kind`` ('end_to_end' or 'per_layer')."""
    return [m for m in man[kind]
            if cell in m.get("workloads", [cell])]


def reader(name: str):
    """The reader module of metric ``name`` (file names carry dots)."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def require_chips(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"bench: needs a TPU, found {devs[0].platform!r} "
                         f"({devs[0].device_kind}); no result")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, found "
                         f"{len(devs)}; no result")
    return devs[:chips]


PROGRAMS = {"built": 0, "seconds": 0.0, "cache_hits": 0}
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


def count_programs():
    """Count, from here on, every program JAX compiles or loads from its
    persistent cache (one backend-compile event each), the seconds that
    took, and how many came from the cache."""
    if "listening" in PROGRAMS:
        return
    import jax

    def on_duration(event, duration, **_):
        if event == BACKEND_COMPILE_EVENT:
            PROGRAMS["built"] += 1
            PROGRAMS["seconds"] += duration

    def on_event(event, **_):
        if event == CACHE_HIT_EVENT:
            PROGRAMS["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    PROGRAMS["listening"] = True


def peak_bytes(devices) -> int:
    return max(int(d.memory_stats()["peak_bytes_in_use"]) for d in devices)


def execute(cell: str, seed: int, seconds: float, trace: bool, *,
            devices=None, man: dict = None, config: dict = None,
            mix: dict = None) -> dict:
    """One run of ``cell``; returns the result line as a dict. ``config``
    and ``mix`` replace the files' (tests run tiny sizes on the CPU)."""
    import jax

    from bench import trace as trace_lib
    from bench import work
    from bench.record import Run

    man = man or manifest()
    entry, file_config, file_mix = resolve(cell, man)
    config = config or file_config
    mix = mix or file_mix
    devices = devices or jax.devices()[:entry["chips"]]
    dev = devices[0]
    peaks = work.peaks(dev.device_kind) if dev.platform == "tpu" else {}

    count_programs()
    driver_mod = importlib.import_module(f"bench.traffic.{mix['kind']}")
    driver = driver_mod.Driver(config, mix, seed, seconds)
    driver.warm()
    built_setup = PROGRAMS["built"]
    logdir = None
    if trace:
        logdir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.host_tracer_level = 1
        opts.python_tracer_level = 0
        jax.profiler.start_trace(logdir, profiler_options=opts)
    t_window = time.perf_counter()
    setup_s = t_window - T_START
    window = driver.run()
    # the window, and an LM cell's drain after it
    window.info["programs_built_after_setup"] = (PROGRAMS["built"]
                                                 - built_setup)
    tr = None
    if trace:
        jax.profiler.stop_trace()
        tr = trace_lib.from_logdir(logdir)
        shutil.rmtree(logdir, ignore_errors=True)
    memory = peak_bytes(devices) if dev.platform == "tpu" else 0
    log(f"set-up {setup_s!r} s; this process built or loaded "
        f"{built_setup} programs in {PROGRAMS['seconds']!r} s, "
        f"{PROGRAMS['cache_hits']} of them from the persistent cache")
    log(f"window {window.seconds!r} s; {len(window.items)} done of "
        f"{window.attempted} attempted, {window.failed} missing; "
        f"{window.info}; counters {window.counters}; peak memory "
        f"{memory} bytes")

    run = Run(config=config, window=window, peaks=peaks, trace=tr,
              setup_s=setup_s)
    kind = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in metric_names(cell, kind, man):
        value = reader(m["name"]).read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    t, built_before_check = time.perf_counter(), PROGRAMS["built"]
    numbers = driver.check(window)
    limits = config["limits"]
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    correct = (window.failed == 0
               and all(c["value"] <= c["limit"] for c in checks.values()))
    check_s = time.perf_counter() - t
    log(f"reference check {check_s!r} s over {len(window.items)} finished, "
        f"{PROGRAMS['built'] - built_before_check} programs built or loaded")

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory}
    out = {"correct": bool(correct), "attempted": int(window.attempted),
           "failed": int(window.failed), "metrics": metrics,
           "device": device, "programs_built_after_setup":
           window.info["programs_built_after_setup"], "check_s": check_s}
    if tr is not None:
        device["busy_s"] = tr.busy_s()
        device["window_s"] = tr.window_s
        out["breakdown"] = {
            "device_ops": [[n[:160], s] for n, s in tr.top_ops(10)],
            "idle_gaps": tr.idle_gaps(10)}
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    man = manifest()
    entry, _, _ = resolve(args.workload, man)
    from repro.launch import compile_cache

    log(f"compile cache: {compile_cache.configure()}")
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devices = require_chips(entry["chips"])
    log(f"device: {devices[0].platform} {devices[0].device_kind} "
        f"x{len(devices)}")
    out = execute(args.workload, args.seed, args.seconds, bool(args.trace),
                  devices=devices, man=man)
    for name, c in out["checks"].items():
        print(f"[bench] check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
