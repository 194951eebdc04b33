"""The read mapper under test: ``runtime.KernelService`` serving ``map``
requests against a genome the benchmark generates from the seed.

Set-up makes the genome on the host and builds the program's minimizer
index on the device. ``map`` is the timed entry: one bulk submit of a
batch of reads. ``check`` maps every read the window completed with the
plain reference (``bench/references/mapper.py``), its alignments batched
on the device once the program's state is freed, and compares.
"""

from __future__ import annotations

import gc
import math
import time
from typing import List

import numpy as np

from bench import gen
from bench.references import mapper as ref_mapper


def make_genome(n: int, seed: int) -> np.ndarray:
    return gen.rng(seed, "genome").integers(0, 4, n).astype(np.int8)


def sequence_read(genome: np.ndarray, length: int, accuracy: float, mix,
                  g: np.random.Generator):
    """A read of exactly ``length`` bases from a random start, with
    sequencing errors: each base is wrong with probability 1 - accuracy,
    the error a substitution, an insertion or a deletion by ``mix``.
    Returns (read, true start)."""
    span = length + length // 4 + 64     # room for the net deletions
    start = int(g.integers(0, len(genome) - span))
    clean = genome[start:start + span].astype(np.int16)
    err = g.random(span) > accuracy
    kind = g.choice(3, size=span, p=list(mix))
    sub = (clean + g.integers(1, 4, span)) % 4
    base = np.where(err & (kind == 0), sub, clean)
    keep = ~(err & (kind == 2))
    extra = err & (kind == 1)
    reps = keep.astype(np.int64) + extra
    out = np.repeat(base, reps)
    # an insertion emits the base, then a random one after it
    ins_at = np.cumsum(reps)[extra] - 1
    out[ins_at] = g.integers(0, 4, len(ins_at))
    return out[:length].astype(np.int8), start


def shifted_read(genome: np.ndarray, length: int, shift: int,
                 accuracy: float, g: np.random.Generator) -> np.ndarray:
    """A read of exactly ``length`` bases that covers ``length + shift``
    genome bases: one block of ``shift`` bases deleted from its middle
    (or ``-shift`` random bases inserted there), and substitutions at
    1 - ``accuracy``. With its chain across the block,
    its alignment window is the read's length plus ``shift`` plus the
    window's slack, to the base."""
    half, gap = length // 2, abs(shift)
    start = int(g.integers(length, len(genome) - 2 * length - gap))
    head = genome[start:start + half]
    if shift >= 0:
        tail = genome[start + half + shift:start + length + shift]
        seq = np.concatenate([head, tail])
    else:
        tail = genome[start + half:start + length - gap]
        seq = np.concatenate([head, g.integers(0, 4, gap), tail])
    seq = seq.astype(np.int16)
    err = g.random(length) > accuracy
    seq = np.where(err, (seq + g.integers(1, 4, length)) % 4, seq)
    return seq.astype(np.int8)


class System:
    def __init__(self, config: dict, seed: int):
        from repro.apps import read_mapper as rm
        from repro.runtime import KernelService, ServiceConfig
        import jax

        self.config = config
        t = time.perf_counter()
        self.genome = make_genome(config["genome_bases"], seed)
        self.phases = {"genome_s": time.perf_counter() - t}
        t = time.perf_counter()
        self.mapper_cfg = rm.MapperConfig(
            k=config["k"], w=config["w"], max_occ=config["max_occ"],
            band_T=config["band"], min_chain_score=config["min_chain_score"],
            sw_window_pad=config["sw_window_pad"], mode=config["mode"],
            **({"sw_tile": config["sw_tile"]} if "sw_tile" in config else {}))
        self.service = KernelService(ServiceConfig(mapper=self.mapper_cfg),
                                     reference=self.genome)
        jax.block_until_ready(self.service.index)   # built on the device
        self.phases["index_s"] = time.perf_counter() - t

    def padded(self, n: int) -> int:
        """``n`` bases padded to the program's read bucket."""
        bucket = self.mapper_cfg.read_bucket
        return -(-n // bucket) * bucket

    def window_buckets(self, length: int) -> list:
        """[(shift, padded window)]: one per padding bucket of the program
        that the alignment window of a ``length``-base read reaches. The
        window covers the read's genome span, which differs from its
        length by the net of its indels (here within four standard
        deviations, and 8 bases for where its end anchors sit), plus the
        slack on both sides."""
        cfg = self.config
        indel = (1 - cfg["read_accuracy"]) * sum(cfg["error_mix"][1:])
        reach = int(4 * math.sqrt(indel * length)) + 8
        mid = length + 2 * cfg["sw_window_pad"]
        out = {}
        for w in range(mid - reach, mid + reach + 1):
            out.setdefault(self.padded(w), []).append(w - mid)
        return [(shifts[len(shifts) // 2], padded)
                for padded, shifts in sorted(out.items())]

    def map(self, reads: List[np.ndarray]):
        from repro.runtime import Request

        return self.service.submit([Request("map", {"read": r})
                                    for r in reads])

    def counters(self) -> dict:
        return dict(self.service.metrics())

    def free(self):
        self.service = None
        gc.collect()

    def check(self, items: List[dict], seed: int, control: str = "float32"
              ) -> dict:
        """Compare every completed read with the reference, which aligns
        them all together on the device (``Reference.map_all``)."""
        ref = ref_mapper.reference_for(self.genome, self.config, control)
        want = ref.map_all([it["read"] for it in items])
        got = [it["result"] for it in items]
        return ref_mapper.compare(got, want, [it["start"] for it in items],
                                  self.config["accuracy_tolerance"])
