"""End-to-end read mapping (paper §VI-C, Fig. 8) — seed -> chain -> align.

Builds a synthetic reference, samples reads with the paper's five input
profiles (Table IV statistics), and maps them with the baseline (1-worker)
and Squire (chunk-parallel) pipelines, reporting accuracy and wall-clock.
Both pipelines are exact transformations of each other, so accuracies
match; the wall-clock ratio on CPU is a *proxy* for the paper's Fig. 8
(gem5 cycle numbers need silicon).

    PYTHONPATH=src python examples/read_mapper.py [--reads 4] [--ref 20000]
"""

import argparse
import time

import numpy as np

from repro.apps.read_mapper import MapperConfig, ReadMapper, mapping_accuracy
from repro.data import genomics


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--ref", type=int, default=20_000)
    ap.add_argument("--reads", type=int, default=4)
    ap.add_argument("--profiles", nargs="*",
                    default=["ONT", "PBHF1"])
    ap.add_argument("--scale", type=float, default=0.25,
                    help="read-length scale vs Table IV/10 (CPU wall-clock)")
    args = ap.parse_args()

    ref = genomics.make_reference(args.ref, seed=0)

    for prof_name in args.profiles:
        base = genomics.PROFILE_BY_NAME[prof_name]
        prof = genomics.ReadProfile(
            base.name, max(300, int(base.mean_len * args.scale)),
            max(80, int(base.std_len * args.scale)), base.accuracy)
        pairs = genomics.sample_reads(ref, prof, args.reads, seed=1)
        reads = [r for r, _ in pairs]
        truths = [t for _, t in pairs]

        print(f"\n=== profile {prof.name} (len~{prof.mean_len}, "
              f"acc {prof.accuracy:.4f}) ===")
        rows = {}
        for mode in ("baseline", "squire"):
            mapper = ReadMapper(ref, MapperConfig(mode=mode))
            mapper.map_read(reads[0])          # warm the shape buckets
            t0 = time.time()
            res = mapper.map_reads(reads)
            dt = time.time() - t0
            acc = mapping_accuracy(res, truths)
            cells = sum(r.align_cells for r in res)
            rows[mode] = (dt, acc, res)
            print(f"  {mode:9s}: {dt:6.2f}s  accuracy={acc:.2f}  "
                  f"align_cells={cells/1e6:.2f}M")
        sp = rows["baseline"][0] / max(rows["squire"][0], 1e-9)
        same = all(a.pos == b.pos and abs(a.sw_score - b.sw_score) < 1e-3
                   for a, b in zip(rows["baseline"][2], rows["squire"][2]))
        print(f"  squire speedup (CPU proxy): {sp:.2f}x; "
              f"outputs identical: {same}")


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.configure()
    main()
