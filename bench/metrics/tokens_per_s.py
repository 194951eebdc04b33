"""Tokens the model consumed or produced in the window (chunk-prefill
tokens plus one per live slot per decode tick, from the Scheduler's
counters before and after), over the window."""


def read(run):
    c = run.window.counters
    return (c["prefill_tokens"] + c["live_decode_slots"]) / run.window.seconds
