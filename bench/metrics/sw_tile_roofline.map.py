"""Share of the roofline of the Smith-Waterman dp-tile kernel: the least
time the window's alignments (read x window cell updates) allow, over the
kernel's device time. The cell updates run on the vector unit, for which
no peak is published, so the compute bound is the bf16 peak."""

from bench import readers, work

KERNEL = r"dp_tile_pallas"


def read(run):
    ops = nbytes = 0
    for it in run.window.items:
        res, n = it["result"], len(it["read"])
        if res.align_cells:
            o, b = work.sw_work(n, res.align_cells // n)
            ops, nbytes = ops + o, nbytes + b
    return readers.kernel_roofline(run, KERNEL, ops, nbytes)
