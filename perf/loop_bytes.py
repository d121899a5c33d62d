"""Bytes that a looped decoder's tick has to move, from shapes, beside
`flops.py`, `moe_flops.py`, `attention_bytes.py` and `ssm_bytes.py` and
under their rules: what the algorithm needs, not what a compiler
emitted.  The configuration is its file's dict, under the source's own
keys.
"""
from __future__ import annotations


def layer_matrix_params(m: dict) -> int:
    """One layer's seven matrices: q, k, v, o over the heads, and the
    SwiGLU FFN's gate, up and down (16.78 M + 34.60 M at hidden 2048,
    16 heads of 128, FFN 5632)."""
    d = int(m["hidden_size"])
    dq = int(m["num_attention_heads"]) * int(m["head_dim"])
    dkv = int(m["num_key_value_heads"]) * int(m["head_dim"])
    return 2 * d * dq + 2 * d * dkv + 3 * d * int(m["intermediate_size"])


def stack_weight_bytes(m: dict, elem_bytes: int = 2) -> int:
    """ONE pass over the stack: every layer's matrices and its four
    norm scales, read once (4.93 GB at 48 layers in bfloat16).  A tick
    of 12 rows cannot keep a stack resident, so every pass reads it
    again; embedding rows, the final norm and the gate (kilobytes) and
    the head (read once a tick, not once a pass) are left out, so a
    roofline share from these bytes errs low."""
    per_layer = layer_matrix_params(m) + 4 * int(m["hidden_size"])
    return int(m["num_hidden_layers"]) * per_layer * elem_bytes


def page_bytes(m: dict, block_size: int, elem_bytes: int = 2) -> int:
    """K AND V of one page: `block_size` positions of one plane, the
    K/V heads side by side (2 x 64 KB at 16 x 2048 in bfloat16)."""
    return 2 * int(block_size) * int(m["num_key_value_heads"]) * int(
        m["head_dim"]) * elem_bytes
