"""A language model under test: ``serve.Scheduler`` over weights that the
benchmark makes from the seed.

The configuration file names the program's architecture (``arch``) and
states every size; set-up refuses a program whose configuration differs.
The weights come from the reference module (``bench/references``), made
on the device in the served dtype, and the Scheduler serves them with the
pool the file states. ``warm`` compiles every program the window can
call: the fused decode tick and one chunk step per power-of-two row
count up to the pool's width. ``check`` runs the reference over a sample
of finished requests and compares logits.
"""

from __future__ import annotations

import gc
import importlib
from typing import List

import numpy as np

from bench import gen

SIZE_KEYS = {"num_layers": "num_layers", "d_model": "d_model",
             "d_ff": "d_ff", "vocab": "vocab", "head_size": "rwkv_head_dim"}


def reference(config: dict):
    return importlib.import_module(f"bench.references.{config['reference']}")


class System:
    def __init__(self, config: dict, seed: int, max_len: int):
        from repro import configs
        from repro.serve import Scheduler, SchedulerConfig

        self.config = config
        # "preset": "reduced" takes the program's tiny preset (CPU tests)
        cfg = (configs.reduced_config(config["arch"])
               if config.get("preset") == "reduced"
               else configs.get_config(config["arch"]))
        for key, attr in SIZE_KEYS.items():
            if getattr(cfg, attr) != config[key]:
                raise ValueError(f"program config {cfg.name}: {attr} = "
                                 f"{getattr(cfg, attr)}, file states "
                                 f"{key} = {config[key]}")
        self.cfg = cfg
        self.ref = reference(config)
        self.params = self.ref.init_params(config, gen.key32(seed, "weights"))
        self.max_len = max_len
        self.sched = Scheduler(cfg, self.params, SchedulerConfig(
            num_slots=config["num_slots"], max_len=max_len,
            allocator=config["allocator"],
            cache_requests=config["cache_requests"]))

    # -- the timed entry -------------------------------------------------

    def submit(self, prompt: np.ndarray, max_new: int) -> int:
        return self.sched.submit([prompt], max_new_tokens=max_new)[0]

    def step(self):
        return self.sched.step()

    @property
    def prefill_chunk(self) -> int:
        """Prompt tokens per row of one chunk step, as the Scheduler runs
        it."""
        return self.sched.sched.prefill_chunk

    @property
    def busy(self) -> bool:
        return bool(self.sched.live or self.sched.pending)

    def counters(self) -> dict:
        return dict(self.sched.counters)

    def warm(self, seed: int):
        """Compile the decode tick and the chunk step at every row count
        (powers of two, 2 .. num_slots) with prompts of one chunk plus
        the decode ramp's token."""
        chunk = self.prefill_chunk
        rows = 2
        while rows <= self.config["num_slots"]:
            prompts = gen.random_tokens([chunk + 1] * rows,
                                        self.config["vocab"], seed,
                                        f"warm{rows}")
            self.sched.submit(prompts, max_new_tokens=1)
            self.sched.drain()
            rows *= 2
        self.sched.results.clear()

    def free(self):
        self.sched = None
        gc.collect()

    # -- correctness -----------------------------------------------------

    def gaps(self, prompt: np.ndarray, served: np.ndarray, params=None
             ) -> np.ndarray:
        """Per served token: the reference's best logit minus the
        reference's logit of the served token (0 where they agree), at
        each position that produced a served token."""
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        rows = np.arange(len(prompt) - 1, len(seq))
        logits = self.ref.logits(self.params if params is None else params,
                                 self.config, seq, rows)
        return self.ref.gaps(logits, np.asarray(served))

    def sample(self, done: List[dict], seed: int) -> List[dict]:
        """``check_requests`` finished requests: the one with the most
        served tokens, then others in an order drawn from the seed."""
        done = [d for d in done if not d.get("missing")]
        if not done:
            return []
        order = sorted(range(len(done)), key=lambda i: -len(done[i]["tokens"]))
        rest = [int(i) for i in gen.rng(seed, "check").permutation(order[1:])]
        return [done[i] for i in
                ([order[0]] + rest)[:self.config["check_requests"]]]

    def check(self, items: List[dict], seed: int) -> dict:
        sample = self.sample(items, seed)
        worst = 0.0
        for it in sample:
            worst = max(worst, float(self.gaps(it["prompt"],
                                               it["tokens"]).max()))
        short = sum(len(it["tokens"]) != it["max_new"] for it in items
                    if not it.get("missing"))
        return {"logit_gap_max": worst, "length_mismatches": short,
                "checked_requests": len(sample),
                "checked_tokens": sum(len(it["tokens"]) for it in sample)}
