"""Share of the roofline of the chain-scan kernel: the least time the
window's chains (anchors x band) allow, over the kernel's device time
(against the bf16 peak, as the scan runs on the vector unit)."""

from bench import readers, work

KERNEL = r"chain_scan_pallas"


def read(run):
    ops = nbytes = 0
    for it in run.window.items:
        o, b = work.chain_work(it["result"].n_anchors, run.config["band"])
        ops, nbytes = ops + o, nbytes + b
    return readers.kernel_roofline(run, KERNEL, ops, nbytes)
