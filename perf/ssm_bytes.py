"""Bytes of recurrent state that a Mamba-2 decode step has to move,
from shapes, beside `flops.py`, `moe_flops.py` and `attention_bytes.py`
and under their rules: what the algorithm needs, not what a compiler
emitted.
"""
from __future__ import annotations

MAMBA = "mamba"


def lane_state_bytes(heads: int, d_head: int, d_state: int,
                     elem_bytes: int = 4) -> int:
    """One lane's SSM state on one Mamba layer, [heads, d_head,
    d_state] (4.19 MB at 128 x 64 x 128 in float32)."""
    return heads * d_head * d_state * elem_bytes


def scan_bytes(lane_ticks: float, layer_types, heads: int, d_head: int,
               d_state: int, elem_bytes: int = 4) -> float:
    """State a step's recurrence must read AND write: every Mamba
    layer reads h and writes it back once for each lane that runs a
    position (`lane_ticks`: such lanes, summed over the ticks
    counted).  The step's operations (a handful a state element) are a
    hundredth of these bytes' time on a v5e: the recurrence is bound
    by memory.  x, B, C, dt and y (kilobytes a lane) are left out, and
    so is what a step moves for lanes that run nothing, so a roofline
    share from these bytes errs low, never above what the chip did."""
    n_mamba = sum(1 for kind in layer_types if kind == MAMBA)
    return float(2 * lane_ticks * n_mamba
                 * lane_state_bytes(heads, d_head, d_state, elem_bytes))
