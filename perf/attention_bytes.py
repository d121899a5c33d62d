"""Bytes of K and V that decode attention has to read, from shapes,
beside `flops.py` and `moe_flops.py` and under their rules: what the
algorithm needs, not what a compiler or a gather emitted.
"""
from __future__ import annotations

SLIDING = "sliding_attention"


def kv_row_bytes(n_kv_heads: int, head_dim: int, elem_bytes: int = 2) -> int:
    """One position's K (or V) on one layer: the K/V heads side by side
    (1024 B at 4 heads of 128 in bf16)."""
    return n_kv_heads * head_dim * elem_bytes


def kv_read_bytes(rows_full: float, rows_window: float, layer_types,
                  n_kv_heads: int, head_dim: int,
                  elem_bytes: int = 2) -> float:
    """K and V a step's attention must read at least once.  A slot whose
    cursor is c attends over c + 1 positions on a full layer and over
    min(c + 1, window) on a sliding one; `rows_full` and `rows_window`
    are those counts summed over the slots (and over the ticks counted).
    Every layer of a kind reads its rows of K and of V.  What a gather
    through a whole block table reads beyond that (rows past the
    cursor, the copy it writes and reads back) is the implementation's,
    so a roofline share from these bytes errs low, never above what the
    chip did."""
    n_window = sum(1 for kind in layer_types if kind == SLIDING)
    n_full = len(layer_types) - n_window
    return float(2 * kv_row_bytes(n_kv_heads, head_dim, elem_bytes)
                 * (n_full * rows_full + n_window * rows_window))
