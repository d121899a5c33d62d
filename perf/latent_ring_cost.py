"""Operations and bytes of decode attention over a LATENT RING (a sliding
layer of a latent block keeps one compressed row a position, shared by
all its heads, in a ring of blocks a lane), from shapes, beside
`latent_attention_cost.py` and `sparse_attention_cost.py` and under their
rule: what the algorithm needs, not what a kernel emitted; a
multiply-add counts as two.

The need is the ring rows a lane's cursor has written (the ring's rows
at most), each read ONCE at the width the ring stores it, and every
head's score over the whole row and context over its latent columns.
The page read for a lane with no sequence, the rows of a last page past
the cursor and the rows a ring LONGER than its window holds past the
window's edge (they are read and masked) beyond `rows` are the
implementation's, so a roofline share from these numbers errs low, never
above what the chip did.
"""
from __future__ import annotations


def stored_row_bytes(kv_lora_rank: int, qk_rope_head_dim: int,
                     elem_bytes: int = 2, lanes: int = 128) -> int:
    """One position's row of one sliding layer AS STORED: the latent and
    the rotated key part on the 128-lane grid (2304 B at 1024 + 64 in
    bf16)."""
    width = -(-(kv_lora_rank + qk_rope_head_dim) // lanes) * lanes
    return width * elem_bytes


def row_ops(n_heads: int, kv_lora_rank: int, qk_rope_head_dim: int) -> int:
    """One row attended by every head: the score over the row and the
    context over its latent (270 336 at 64 heads, 1024 + 64)."""
    return 2 * n_heads * ((kv_lora_rank + qk_rope_head_dim) + kv_lora_rank)


def ring_call(rows: float, kv_lora_rank: int, qk_rope_head_dim: int,
              elem_bytes: int = 2, n_heads: int = 0) -> dict:
    """`rows` ring rows read (min(cursor + 1, the ring's rows) a lane a
    sliding layer, summed over the ticks counted): the bytes that must
    cross HBM at least once and, with `n_heads`, the operations."""
    return {"bytes": float(rows * stored_row_bytes(
                kv_lora_rank, qk_rope_head_dim, elem_bytes)),
            "flops": float(rows * row_ops(n_heads, kv_lora_rank,
                                          qk_rope_head_dim))}
