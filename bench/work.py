"""Work counts from shapes: the algorithm's own operations and bytes.

Every count here is what the computation needs, never what an
implementation materializes: padded rows, junk slots of a fused pool,
full-vocabulary logits nobody reads, and eager copies are left out. A
kernel's roofline share divides the least time these counts allow on the
chip (``roofline_s``) by the kernel's measured device time.
"""

from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).with_name("peaks.json")

# Smith-Waterman cell update H = max(0, diag + s(a, b), up - g, left - g):
# compare and select for s (2), three adds, three maxes.
SW_OPS_PER_CELL = 8
# chain band entry: one max-plus update, f(j) + S[i, t] folded into a max.
CHAIN_OPS_PER_ENTRY = 1
# WKV6 per (head, dk, dv) element and token: decay multiply, k v outer
# product multiply, add (state update); multiply-add of the readout.
WKV_OPS_PER_ELEMENT = 5
BF16, F32 = 2, 4


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``; unknown kinds raise."""
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def roofline_s(ops: float, nbytes: float, peak: dict) -> float:
    """Least time the chip could take: the larger of the compute bound
    (against the bf16 peak) and the memory bound."""
    return max(ops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])


# --------------------------------------------------------------------------
# RWKV-6 (config: the JSON file's keys)
# --------------------------------------------------------------------------

def rwkv_matmul_params(cfg: dict) -> int:
    """Weights that every token multiplies: per layer the time mix's five
    d x d projections and decay LoRA, the channel mix's three matrices;
    then the unembedding. The embedding is a row lookup, not a product."""
    d, ff, lora = cfg["d_model"], cfg["d_ff"], cfg["decay_lora"]
    per_layer = 5 * d * d + 2 * d * lora + 2 * d * ff + d * d
    return cfg["num_layers"] * per_layer + cfg["vocab"] * d


def rwkv_vector_params(cfg: dict) -> int:
    """Per-channel parameters (mixing, norms, decay base, bonus)."""
    d = cfg["d_model"]
    per_layer = 12 * d + d  # 5 + 2 mixes, w0, ln_x scale+bias, 2 norms; u
    return cfg["num_layers"] * per_layer + d


def rwkv_flops_per_token(cfg: dict) -> float:
    """Model FLOPs of one token: two per multiplied weight, plus the WKV
    recurrence over every head's dk x dv state."""
    heads = cfg["d_model"] // cfg["head_size"]
    wkv = WKV_OPS_PER_ELEMENT * heads * cfg["head_size"] ** 2
    return 2.0 * rwkv_matmul_params(cfg) + cfg["num_layers"] * wkv


def rwkv_weight_bytes(cfg: dict) -> int:
    """Bytes of the weights a step reads once: every multiplied weight and
    per-channel vector in bf16 (the embedding table is read by rows)."""
    return BF16 * (rwkv_matmul_params(cfg) + rwkv_vector_params(cfg))


def rwkv_state_bytes(cfg: dict) -> int:
    """One sequence's recurrent state: per layer the fp32 WKV state of
    every head and the two token-shift vectors."""
    d, hs = cfg["d_model"], cfg["head_size"]
    return cfg["num_layers"] * F32 * ((d // hs) * hs * hs + 2 * d)


def rwkv_step_work(cfg: dict, rows: int, tokens_per_row: int):
    """(flops, bytes) of one fused step over ``rows`` live sequences of
    ``tokens_per_row`` tokens: weights once, each row's state read and
    written, each token's embedding row read."""
    tokens = rows * tokens_per_row
    flops = tokens * rwkv_flops_per_token(cfg)
    nbytes = (rwkv_weight_bytes(cfg) + 2 * rows * rwkv_state_bytes(cfg)
              + tokens * BF16 * cfg["d_model"])
    return flops, nbytes


# --------------------------------------------------------------------------
# read mapper kernels
# --------------------------------------------------------------------------

def sw_work(read_len: int, window_len: int):
    """(ops, bytes) of one Smith-Waterman alignment: read x window cell
    updates; the recurrence reads both sequences (a byte per base) and
    writes one score."""
    return (SW_OPS_PER_CELL * read_len * window_len,
            read_len + window_len + F32)


def chain_work(anchors: int, band: int):
    """(ops, bytes) of the chain scan over ``anchors`` anchors in a band
    of ``band`` predecessors: it reads the (anchors, band) fp32 match-up
    scores and anchor weights and writes each anchor's score and
    predecessor."""
    return (CHAIN_OPS_PER_ENTRY * anchors * band,
            F32 * (anchors * band + 3 * anchors))
