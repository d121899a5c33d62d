"""Operations and bytes of a dropless top-k expert layer from shapes,
beside `flops.py` and under its rules: what the algorithm needs, not
what a compiler emitted; a multiply-add counts as two.
"""
from __future__ import annotations


def expert_params(d_model: int, width: int) -> int:
    """Weights of ONE SwiGLU expert: gate and up [d_model, width], down
    [width, d_model]."""
    return 3 * d_model * width


def expert_bytes(d_model: int, width: int, weight_bytes: int = 2) -> int:
    """Bytes of one expert's three matrices (12.58 MB at 2048 x 1024 in
    bf16): what a grouped matmul has to read once for every expert that
    got at least one row."""
    return expert_params(d_model, width) * weight_bytes


def moe_layer_params(d_model: int, width: int, experts: int) -> int:
    """Router and experts of one layer (norm scales left out)."""
    return d_model * experts + experts * expert_params(d_model, width)


def expected_experts_hit(experts: int, per_token: int, tokens: int) -> float:
    """Distinct experts touched by `tokens` tokens routed uniformly,
    `per_token` distinct experts each: E (1 - (1 - k/E)^T)."""
    return experts * (1.0 - (1.0 - per_token / experts) ** tokens)


def moe_experts_call(d_model: int, width: int, assignments: int,
                     experts_hit: float, weight_bytes: int = 2) -> dict:
    """The three grouped matmuls and the gate over `assignments` rows
    (tokens x experts per token) that touch `experts_hit` experts: the
    operations, and the bytes that must cross HBM at least once.  The
    bytes are the touched experts' matrices alone: the rows in and out
    (under 1% of them at 256 rows) are left out, so a roofline share
    from these bytes errs low, never above what the chip did."""
    return {"flops": float(2 * assignments * expert_params(d_model, width)),
            "bytes": float(experts_hit * expert_bytes(d_model, width,
                                                      weight_bytes))}
