"""What the mapper's reference check costs, on the chip, at any number of
reads of a mapper cell's lengths.

    python3 bench/check_cost.py --workload map-ont --reads 2000 --seed 7

One process makes the cell's genome and ``--reads`` reads of its length
cycle, as its traffic does, and times the plain reference: its index,
seed and chain of every read on the host (``Reference.locate``), and the
batched Smith-Waterman sweep of all of them on the device
(``Reference.sw_all``), counting the programs that sweep compiled or
loaded. It then maps a sample of ``--sample`` reads drawn from the seed,
the longest among them, one at a time on the host (``Reference.map``):
the per-read cost the batched check replaced, and a witness that the
device's scores, exact and under the bfloat16 control, equal the
per-read sweep's. The last line is one JSON object.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT), str(ROOT / "src")):
    if p not in sys.path:
        sys.path.insert(0, p)


def cell_reads(config: dict, mix: dict, genome, n: int, seed: int):
    """``n`` reads, the cell's length cycle repeated, as its traffic makes
    them."""
    from bench import gen
    from bench.systems.mapper import sequence_read
    from bench.traffic.read_batches import length_cycle

    cycle = length_cycle(config, mix, seed).ravel()
    g = gen.rng(seed, "cost")
    return [sequence_read(genome, int(cycle[k % len(cycle)]),
                          config["read_accuracy"], config["error_mix"], g)[0]
            for k in range(n)]


def measure(cell: str, n_reads: int, seed: int, sample: int,
            config: dict = None, mix: dict = None) -> dict:
    from bench import gen, run
    from bench.references import mapper as ref_mapper
    from bench.systems.mapper import make_genome

    _, file_config, file_mix = run.resolve(cell)
    config, mix = config or file_config, mix or file_mix
    run.count_programs()
    genome = make_genome(config["genome_bases"], seed)
    reads = cell_reads(config, mix, genome, n_reads, seed)
    out = {"reads": n_reads, "bases": int(sum(map(len, reads)))}

    t = time.perf_counter()
    ref = ref_mapper.reference_for(genome, config)
    out["index_s"] = time.perf_counter() - t
    t = time.perf_counter()
    located = [ref.locate(r) for r in reads]
    out["seed_chain_s"] = time.perf_counter() - t
    todo = [k for k, (_, w) in enumerate(located) if w is not None]
    pairs = [(reads[k], genome[slice(*located[k][1])]) for k in todo]
    before = dict(run.PROGRAMS)
    t = time.perf_counter()
    scores = ref.sw_all(pairs)
    out["sw_s"] = time.perf_counter() - t
    out["sw_programs"] = run.PROGRAMS["built"] - before["built"]
    out["sw_program_s"] = run.PROGRAMS["seconds"] - before["seconds"]
    out["sw_cache_hits"] = (run.PROGRAMS["cache_hits"]
                            - before["cache_hits"])
    out["aligned"] = len(pairs)
    out["check_s"] = out["index_s"] + out["seed_chain_s"] + out["sw_s"]

    # the per-read oracle on a sample, the longest pair among it
    longest = max(range(len(pairs)), key=lambda k: len(pairs[k][0]))
    picks = gen.rng(seed, "sample").permutation(len(pairs))[:sample - 1]
    picks = sorted({longest, *map(int, picks)})
    t = time.perf_counter()
    per_read = [ref.map(reads[todo[k]]) for k in picks]
    out["per_read_map_s"] = (time.perf_counter() - t) / len(picks)
    out["sample_sw_gap"] = max(abs(m.sw_score - scores[k])
                               for m, k in zip(per_read, picks))
    low = ref_mapper.reference_for(genome, config, "bfloat16")
    low_batched = low.sw_all([pairs[k] for k in picks])
    out["sample_sw_gap_bfloat16"] = max(
        abs(low.sw(*pairs[k]) - s) for k, s in zip(picks, low_batched))
    out["sample"] = len(picks)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", default="map-ont")
    ap.add_argument("--reads", type=int, default=2000)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sample", type=int, default=8)
    args = ap.parse_args(argv)
    from repro.launch import compile_cache

    compile_cache.configure()
    import jax

    from bench import run

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    dev = run.require_chips(1)[0]
    out = measure(args.workload, args.reads, args.seed, args.sample)
    out["device"] = {"platform": dev.platform, "kind": dev.device_kind}
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
