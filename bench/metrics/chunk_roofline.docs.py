"""Share of the roofline of the chunk-prefill steps: the least time their
work allows (weights once per step, each row's state read and written
once per chunk of the Scheduler's own size, the chunk tokens' FLOPs),
over the chunk programs' device time."""

from bench import readers, work


def read(run):
    if run.trace is None or not run.window.counters.get("chunk_steps"):
        return None
    _, seconds = readers.step_programs(run)
    flops, nbytes = readers.chunk_work(run, run.window.info["prefill_chunk"])
    return readers.share(work.roofline_s(flops, nbytes, run.peaks), seconds)
