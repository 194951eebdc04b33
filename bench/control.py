"""Readings that the correctness limits are set from, on the chip.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 20

For each seed, one process sets the cell up at its own size, runs its
traffic for ``--seconds`` at the cell's own load, and then reads, over
what that window finished:

  program  the numbers a benchmark run compares (sound readings; their
           largest over a dozen seeds is a limit's lower reading);
  control  the same numbers with the plain reference put in the
           program's place at the nearest precision below the one the
           configuration states: int8 matrix products (weights per
           channel, inputs per token) for the bf16 model (the gap of the
           token the int8 reference puts first), bfloat16 DP
           scores for the float32 mapper (its answers compared with the
           float32 reference). The smallest over the seeds is a limit's
           upper reading.

One JSON line per seed, then a summary line with the largest program and
the smallest control reading of each number.
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def lm_control(driver, window) -> dict:
    """Gap numbers of the int8-weight reference over the window's sample."""
    import numpy as np

    system = driver.system
    ref = system.ref
    sample = system.sample(window.items, driver.seed)
    qparams = ref.quantize(system.params)
    worst = 0.0
    for it in sample:
        prompt, served = it["prompt"], it["tokens"]
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        rows = np.arange(len(prompt) - 1, len(seq))
        exact = ref.logits(system.params, system.config, seq, rows)
        low = ref.logits(qparams, system.config, seq, rows, int8=True)
        worst = max(worst, float(ref.gaps(exact, low.argmax(-1)).max()))
    return {"logit_gap_max": worst}


def mapper_control(driver, window) -> dict:
    from bench.references import mapper as ref_mapper

    system = driver.system
    exact = ref_mapper.reference_for(system.genome, system.config)
    low = ref_mapper.reference_for(system.genome, system.config, "bfloat16")
    reads = [it["read"] for it in window.items]
    return ref_mapper.compare(low.map_all(reads), exact.map_all(reads),
                              [it["start"] for it in window.items],
                              system.config["accuracy_tolerance"])


def readings(cell: str, seeds, seconds: float, config=None, mix=None):
    """[(seed, program numbers, control numbers)] for ``cell``."""
    from bench import run

    _, file_config, file_mix = run.resolve(cell)
    config, mix = config or file_config, mix or file_mix
    control = {"lm": lm_control, "mapper": mapper_control}[config["system"]]
    driver_mod = importlib.import_module(f"bench.traffic.{mix['kind']}")
    out = []
    for seed in seeds:
        t = time.perf_counter()
        driver = driver_mod.Driver(config, mix, seed, seconds)
        driver.warm()
        window = driver.run()
        driver.system.free()
        got = driver.system.check(window.items, seed)
        low = control(driver, window)
        line = {"seed": seed, "finished": len(window.items) - window.failed,
                "program": got, "control": low,
                "seconds": time.perf_counter() - t}
        print(json.dumps(line), flush=True)
        out.append((seed, got, low))
        del driver
    return out


def summary(rows) -> dict:
    keys = rows[0][2].keys()
    return {k: {"lower": max(float(r[1][k]) for r in rows),
                "upper": min(float(r[2][k]) for r in rows)} for k in keys}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    from repro.launch import compile_cache

    compile_cache.configure()
    import jax

    from bench import run

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    run.require_chips(1)
    rows = readings(args.workload, [int(s) for s in args.seeds.split(",")],
                    args.seconds)
    print(json.dumps({"summary": summary(rows)}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
