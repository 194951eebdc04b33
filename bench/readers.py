"""Arithmetic the metric readers share. Each reader in ``metrics/`` maps
one run to one number, or to None where its run holds nothing to read."""

from __future__ import annotations

import re
from typing import Optional, Tuple

from bench import work

STEP = "bench.step"
# The serving steps' programs; the trace names them jit_run(<fingerprint>).
STEP_PROGRAM = re.compile(r"^jit_run\(")


def share(part: float, whole: float) -> Optional[float]:
    """100 * part / whole, or None where there is nothing to divide."""
    return 100.0 * part / whole if whole > 0 else None


def idle_pct(run) -> Optional[float]:
    return None if run.trace is None else 100.0 * run.trace.idle_share()


def host_ms_per_tick(run) -> Optional[float]:
    """Mean, over the window's scheduler steps, of the step's host wall
    time not covered by device-busy time, in ms."""
    if run.trace is None:
        return None
    steps = run.trace.spans(STEP)
    if not steps:
        return None
    idle = sum(dur / 1e9 - run.trace.busy_within(s, s + dur)
               for _, s, dur in steps)
    return 1e3 * idle / len(steps)


def step_programs(run) -> Tuple[float, float]:
    """(decode seconds, chunk seconds) of the serving step programs in the
    window. A scheduler step dispatches its chunk steps, then one decode
    tick that the host waits for, so all of a step's programs run inside
    its span and the last is the decode tick."""
    decode = chunk = 0.0
    mods = [m for m in run.trace.modules() if STEP_PROGRAM.search(m[0])]
    for _, s, dur in run.trace.spans(STEP):
        inside = [m for m in mods if s <= m[1] and m[1] + m[2] <= s + dur]
        if inside:
            decode += inside[-1][2] / 1e9
            chunk += sum(m[2] for m in inside[:-1]) / 1e9
    return decode, chunk


def model_flops(run) -> float:
    """Model FLOPs of every token the model consumed or produced in the
    window: chunk-prefill tokens and one token per live slot per tick."""
    c = run.window.counters
    tokens = c["prefill_tokens"] + c["live_decode_slots"]
    return tokens * work.rwkv_flops_per_token(run.config)


def chunk_work(run, chunk: int) -> Tuple[float, float]:
    c, cfg = run.window.counters, run.config
    tokens = c["prefill_tokens"]
    flops = tokens * work.rwkv_flops_per_token(cfg)
    nbytes = (c["chunk_steps"] * work.rwkv_weight_bytes(cfg)
              + 2 * (tokens // chunk) * work.rwkv_state_bytes(cfg)
              + tokens * work.BF16 * cfg["d_model"])
    return flops, nbytes


def kernel_roofline(run, pattern: str, ops: float, nbytes: float
                    ) -> Optional[float]:
    """Share of the roofline of the kernel ops matching ``pattern``."""
    if run.trace is None:
        return None
    seconds, n = run.trace.op_seconds(pattern)
    if n == 0:
        return None
    return share(work.roofline_s(ops, nbytes, run.peaks), seconds)
