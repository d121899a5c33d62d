"""Operations and bytes of decode attention over a LATENT cache (one
compressed row a position a layer, shared by all heads), from shapes,
beside `attention_bytes.py` and under its rule: what the algorithm
needs, not what a kernel emitted; a multiply-add counts as two.

In the absorbed form a query head meets the cached row itself: its
score is a product over the whole row (latent and rotated key part) and
its context a weighted sum of the row's latent columns.  The pad that
puts a stored row on the lane grid, rows a kernel multiplies past a
slot's length and a page read for a lane with no sequence are the
implementation's, so a roofline share from these numbers errs low,
never above what the chip did, and reads the same work whatever
implements the kernel.
"""
from __future__ import annotations


def row_ops(n_heads: int, kv_lora_rank: int, qk_rope_head_dim: int) -> int:
    """One row attended by every head: the score over the row and the
    context over its latent (278 528 at 128 heads, 512 + 64)."""
    return 2 * n_heads * ((kv_lora_rank + qk_rope_head_dim) + kv_lora_rank)


def row_bytes(kv_lora_rank: int, qk_rope_head_dim: int,
              elem_bytes: int = 2) -> int:
    """One position's row on one layer, read ONCE for both products
    (1152 B at 512 + 64 in bf16)."""
    return (kv_lora_rank + qk_rope_head_dim) * elem_bytes


def attention_call(rows: float, n_heads: int, kv_lora_rank: int,
                   qk_rope_head_dim: int, elem_bytes: int = 2) -> dict:
    """`rows` rows attended (a slot whose cursor is c attends over
    c + 1, summed over slots, layers and the ticks counted): the
    operations, and the bytes that must cross HBM at least once.  The
    queries in and the contexts out (33 MB a layer at 64 slots of 128
    heads, whatever the lengths) are left out, like `moe_flops.py`'s
    rows."""
    return {"flops": float(rows * row_ops(n_heads, kv_lora_rank,
                                          qk_rope_head_dim)),
            "bytes": float(rows * row_bytes(kv_lora_rank,
                                            qk_rope_head_dim, elem_bytes))}
