"""Closed-loop LM jobs: a fixed number of documents in flight, as a batch
ingestion or summarization job. The window opens with that many
documents submitted at once; each one that finishes is replaced by the
next at once. The rate counts every token the model consumed or
produced in the window.

Documents come in waves of ``in_flight``: every wave holds the same
stratified prompt and output lengths in an order drawn from the seed, so
the window's opening wave, which most of the window prefills, is the
same work under every seed.
"""

from __future__ import annotations

import time

import jax

from bench import gen
from bench.lmload import LMLoad
from bench.systems.lm import System


def document_lengths(mix: dict, seed: int):
    """(prompt lengths, output lengths) of ``mix['waves']`` waves."""
    p, o, n = mix["prompt"], mix["output"], mix["in_flight"]
    plens, outs = [], []
    for w in range(mix["waves"]):
        plens += list(gen.lognormal_ints(n, p["median"], p["sigma"], p["lo"],
                                         p["hi"], seed, f"prompt_lens{w}"))
        outs += list(gen.uniform_ints(n, o["lo"], o["hi"], seed,
                                      f"output_lens{w}"))
    return plens, outs


class Driver(LMLoad):
    def __init__(self, config: dict, mix: dict, seed: int, seconds: float):
        super().__init__()
        self.mix, self.seed, self.seconds = mix, seed, seconds
        p, o = mix["prompt"], mix["output"]
        plens, self.outs = document_lengths(mix, seed)
        self.prompts = gen.random_tokens(plens, config["vocab"], seed,
                                         "prompts")
        max_len = -(-(p["hi"] + o["hi"]) // 16) * 16
        self.system = System(config, seed, max_len)

    def run(self):
        steps = []
        before = self.system.counters()
        nxt = 0
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(self.mix["in_flight"]):
                self.send(self.prompts[nxt], int(self.outs[nxt]))
                nxt += 1
            while time.perf_counter() - t0 < self.seconds:
                for _ in self.step(steps):
                    if nxt == len(self.prompts):
                        raise RuntimeError("the mix ran out of documents; "
                                           "raise 'waves'")
                    self.send(self.prompts[nxt], int(self.outs[nxt]))
                    nxt += 1
        t1 = time.perf_counter()
        after = self.system.counters()
        drain_s = self.drain(self.mix["drain_s"])
        return self.window(t0, t1, before, after, steps,
                           {"documents_sent": nxt, "drain_s": drain_s})
