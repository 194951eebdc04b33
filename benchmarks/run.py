"""Benchmark driver — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only fig6,fig_band]

Prints ``name,us_per_call,derived`` CSV rows (see benchmarks.common for
the two-column semantics: measured CPU wall-clock + the hardware-
independent depth-model / claim-specific derived quantity).

The dry-run / roofline numbers (EXPERIMENTS.md §Dry-run/§Roofline) come
from ``python -m repro.launch.dryrun``, not from this driver — they need
the 512-device XLA flag that must not leak into benchmark processes.
"""

from __future__ import annotations

import argparse
import sys
import time


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: fig6,fig7,fig8,fig9,fig_band,"
                         "fig_runtime,fig_serve")
    args = ap.parse_args(argv)

    from benchmarks import (fig6_kernels, fig7_sync, fig8_end2end,
                            fig9_blocksize, fig_band, fig_runtime,
                            fig_serve)
    suites = {
        "fig6": fig6_kernels.run,
        "fig7": fig7_sync.run,
        "fig8": fig8_end2end.run,
        "fig9": fig9_blocksize.run,
        "fig_band": fig_band.run,
        "fig_runtime": fig_runtime.run,
        # full sweep includes the paged-allocator occupancy comparison
        # (CI smoke reaches it via `fig_serve --smoke --paged`)
        "fig_serve": lambda rows: fig_serve.run(rows, paged=True),
    }
    want = args.only.split(",") if args.only else list(suites)

    rows = []
    t0 = time.time()
    print("name,us_per_call,derived")
    for name in want:
        if name not in suites:
            print(f"unknown suite {name!r}; have {sorted(suites)}",
                  file=sys.stderr)
            return 2
        suites[name](rows)
    print(f"# total: {len(rows)} rows in {time.time()-t0:.0f}s")
    return 0


if __name__ == "__main__":
    from repro.launch import compile_cache
    compile_cache.configure()
    raise SystemExit(main())
