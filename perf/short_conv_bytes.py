"""Bytes that the gated short convolutions of a decode step have to
move, from shapes, beside `flops.py`, `moe_flops.py`,
`attention_bytes.py` and `ssm_bytes.py` and under their rules: what the
algorithm needs, not what a compiler emitted.
"""
from __future__ import annotations

CONV = "conv"


def mixer_weight_bytes(d_model: int, taps: int, elem_bytes: int = 2) -> int:
    """One conv mixer's matrices: the in-projection [d, 3d] to the
    gates B, C and u, the out-projection [d, d] and the depthwise taps
    [taps, d] (33.6 MB at d 2048 and 3 taps in bf16)."""
    return (4 * d_model * d_model + taps * d_model) * elem_bytes


def lane_tail_bytes(d_model: int, taps: int, elem_bytes: int = 4) -> int:
    """One lane's tail on one conv layer: the last `taps - 1` rows of
    the product B * u (16 KB at d 2048 and 3 taps in float32)."""
    return (taps - 1) * d_model * elem_bytes


def mixer_bytes(ticks: float, lane_ticks: float, layer_types,
                d_model: int, taps: int, weight_bytes: int = 2) -> float:
    """What the conv mixers of a step must move: every conv layer reads
    its matrices once a tick whatever the lanes (`ticks`: the ticks
    counted), and for each lane that runs a position (`lane_ticks`:
    such lanes, summed over those ticks) reads its tail and writes it
    back and moves the three float32 rows the in-projection hands the
    gate (B, C and u).  The step's operations (8 d^2 multiply-adds a
    lane a layer) take half these bytes' time at 128 lanes on a
    v5e: the mixer is bound by the matrices' bytes.  The normed input,
    the gated row and the residual (kilobytes a lane) are left out, and
    so is what a step moves for lanes that run nothing, so a roofline
    share from these bytes errs low, never above what the chip did."""
    n_conv = sum(1 for kind in layer_types if kind == CONV)
    per_lane = 2 * lane_tail_bytes(d_model, taps) + 3 * d_model * 4
    return float(n_conv * (
        ticks * mixer_weight_bytes(d_model, taps, weight_bytes)
        + lane_ticks * per_lane))
