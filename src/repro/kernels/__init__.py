"""Pallas TPU kernels for the paper's compute hot-spots.

chain_scan       — banded max-plus chain recurrence (paper Alg. 3 serial part)
dtw_wavefront    — 2-D DP wavefront tile (DTW / Smith-Waterman, Alg. 4)
ssm_scan         — chunked diagonal-linear scan (RWKV6/Mamba; DESIGN.md §3.1)
flash_attention  — fused blockwise attention w/ GQA + sliding window (the
                   production fix for the fp32 score traffic §Perf exposed)
radix_rank       — radix counting-sort rank/histogram pass (Alg. 1 hot-spot)

ops.py: jit'd wrappers (padding, layout, wavefront/sort integration).
ref.py: pure-jnp oracles; tests assert allclose across shape/dtype sweeps.
mode.py: the kernel mode from the platform — compiled through Mosaic on a
TPU, the Pallas interpreter on the CPU; no wrapper takes it as an option.
"""

from repro.kernels import ops, ref  # noqa: F401
