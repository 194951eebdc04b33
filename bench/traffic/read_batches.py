"""Closed-loop read batches, as a mapping job streaming a FASTQ.

Each batch holds one read per quartile of the profile's length
distribution: the lengths of a cycle of ``per_quartile`` batches
(``gen.quartile_cycle``), repeated, with fresh reads from random genome
positions and the profile's sequencing errors in every batch. The next
batch is submitted when the last returns; the window ends when the batch
in flight at the deadline completes, and the rate is all bases completed
over all of that time.

Warm-up maps one batch of each length mix of the cycle, then probe reads
into every aligned shape that a batch can reach but need not: a read's
alignment window covers its genome span, which its indels make differ
from its length, so a window can fall into either of two of the
program's padding buckets (``System.window_buckets``). No shape the
window can send is then new to it.
"""

from __future__ import annotations

import collections
import itertools
import time
from typing import List

import jax

from bench import gen
from bench.record import Window, delta
from bench.systems.mapper import System, sequence_read, shifted_read


# A probe only has to reach its shapes: at 95% accuracy every length
# chains for sure, with fewer anchors than one padding bucket of them.
PROBE_ACCURACY = 0.95


def length_cycle(config: dict, mix: dict, seed: int):
    """(per_quartile, 4) read lengths of the batches of one cycle."""
    mean, sd = config["read_mean"], config["read_sd"]
    return gen.quartile_cycle(
        mean, sd, mix["clip_lo"], mean + mix["clip_hi_sd"] * sd,
        mix["per_quartile"], seed, "lengths")


class Driver:
    def __init__(self, config: dict, mix: dict, seed: int, seconds: float):
        self.mix, self.seed, self.seconds = mix, seed, seconds
        self.cycle = length_cycle(config, mix, seed)
        self.system = System(config, seed)
        self.accuracy = config["read_accuracy"]
        self.error_mix = config["error_mix"]

    def batch(self, i: int, name: str):
        g = gen.rng(self.seed, name)
        return [sequence_read(self.system.genome, int(n), self.accuracy,
                              self.error_mix, g)
                for n in self.cycle[i % len(self.cycle)]]

    def probe_batches(self) -> list:
        """Batches of (length, shift) probe reads, one batch for each
        aligned shape that a batch of the cycle can reach but need not:
        (padded read, padded window, reads stacked in it). A length whose
        window can fall into two buckets can also join or leave a stack
        of reads with its padded shape."""
        shapes, system = {}, self.system
        for row in self.cycle:
            options = [[(int(n), shift, padded)
                        for shift, padded in system.window_buckets(int(n))]
                       for n in row]
            picks = []
            for pick in itertools.product(*options):
                stacks = collections.defaultdict(list)
                for n, shift, padded in pick:
                    stacks[(system.padded(n), padded)].append((n, shift))
                picks.append({(key, len(v)): v for key, v in stacks.items()})
            forced = set.intersection(*(set(p) for p in picks))
            for p in picks:
                for shape, reads in sorted(p.items()):
                    if shape not in forced:
                        shapes.setdefault(shape, reads)
        return [shapes[k] for k in sorted(shapes)]

    def warm(self):
        phases = self.system.phases
        phases["warm_batches_s"] = []
        for i in range(len(self.cycle)):
            t = time.perf_counter()
            self.system.map([r for r, _ in self.batch(i, f"warm{i}")])
            phases["warm_batches_s"].append(time.perf_counter() - t)
        t = time.perf_counter()
        g = gen.rng(self.seed, "probes")
        probes = self.probe_batches()
        for reads in probes:
            self.system.map([shifted_read(self.system.genome, n, shift,
                                          PROBE_ACCURACY, g)
                             for n, shift in reads])
        phases["warm_probes_s"] = time.perf_counter() - t
        phases["probe_reads"] = sum(map(len, probes))

    def run(self) -> Window:
        items: List[dict] = []
        steps = []
        before = self.system.counters()
        batches = 0
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                reads = self.batch(batches, f"batch{batches}")
                s = time.perf_counter()
                with jax.profiler.TraceAnnotation("bench.batch"):
                    results = self.system.map([r for r, _ in reads])
                e = time.perf_counter()
                steps.append((s, e))
                for (read, start), res in zip(reads, results):
                    items.append({"read": read, "start": start,
                                  "result": res, "bases": len(read)})
                batches += 1
                if e - t0 >= self.seconds:
                    break
        t1 = time.perf_counter()
        return Window(t0=t0, t1=t1, items=items, attempted=len(items),
                      failed=0,
                      counters=delta(before, self.system.counters()),
                      steps=steps,
                      info={"batches": batches,
                            "read_lengths": self.cycle.tolist(),
                            "setup_phases": self.system.phases})

    def check(self, window: Window):
        self.system.free()
        return self.system.check(window.items, self.seed)
