"""Model FLOPs of every token the model processed in the window, over the
window times the chip's bf16 peak."""

from bench import readers


def read(run):
    if not run.peaks:
        return None
    return readers.share(readers.model_flops(run),
                         run.window.seconds * run.peaks["bf16_flops_per_s"])
