"""Bytes that the gated delta rule of a decode step has to move, from
shapes, beside `flops.py`, `moe_flops.py`, `attention_bytes.py`,
`ssm_bytes.py` and `short_conv_bytes.py` and under their rules: what the
algorithm needs, not what a compiler emitted.
"""
from __future__ import annotations

DELTA = "delta_rule"


def lane_state_bytes(heads: int, d_head: int, elem_bytes: int = 4) -> int:
    """One lane's matrix state on one delta-rule layer, [heads, d_head
    keys, d_head values] (4.19 MB at 64 heads of 128 in float32)."""
    return heads * d_head * d_head * elem_bytes


def lane_tail_bytes(heads: int, d_head: int, taps: int,
                    elem_bytes: int = 4) -> int:
    """One lane's tail on one delta-rule layer: the last `taps - 1`
    rows of q | k | v before the convolution (295 KB at 64 heads of 128
    and 4 taps in float32)."""
    return (taps - 1) * 3 * heads * d_head * elem_bytes


def lane_row_bytes(heads: int, d_head: int, elem_bytes: int = 4) -> int:
    """The float32 rows between the projections, a lane a layer: q | k
    | v in (3 H K), the log decay and the output gate (H K each) and
    the gated result out (H K): 6 H K elements (197 KB at 64 x 128)."""
    return 6 * heads * d_head * elem_bytes


def rule_bytes(lane_ticks: float, layer_types, heads: int, d_head: int,
               taps: int, elem_bytes: int = 4) -> float:
    """What the recurrence of a step must move between its projections:
    every delta-rule layer reads S and writes it back ONCE for each
    lane that runs a position (`lane_ticks`: such lanes, summed over
    the ticks counted), reads and writes that lane's tail, and moves
    its rows (`lane_row_bytes`).  The step's operations (a handful a
    state element) are a hundredth of these bytes' time on a v5e: the
    recurrence is bound by memory.  The small gate matrices (W_fa,
    W_fb, W_ga, W_gb, W_b: 6.8 MB a layer a tick) and what a step
    moves for lanes that run nothing are left out, so a roofline share
    from these bytes errs low, never above what the chip did."""
    n_delta = sum(1 for kind in layer_types if kind == DELTA)
    per_lane = (2 * lane_state_bytes(heads, d_head, elem_bytes)
                + 2 * lane_tail_bytes(heads, d_head, taps, elem_bytes)
                + lane_row_bytes(heads, d_head, elem_bytes))
    return float(lane_ticks * n_delta * per_lane)
