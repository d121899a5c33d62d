"""Operations and bytes of SELECTED latent attention (a lightning indexer
scores every cached position of a lane against the query's position and
attention runs over the `index_topk` positions it selects), from shapes,
beside `latent_attention_cost.py` and under its rule: what the algorithm
needs, not what a kernel emitted; a multiply-add counts as two.

The attention's need is the SELECTED rows, each read once at the width
the cache stores it (a row nobody selected need not cross HBM), whatever
implements the read: a kernel that copies whole pages under a mask moves
more and reads the lower for it.  The indexer's need is one index key a
row under the cursor (`index_head_dim` columns as stored) and a product
of every index head with it.
"""
from __future__ import annotations


def stored_row_bytes(kv_lora_rank: int, qk_rope_head_dim: int,
                     elem_bytes: int = 2, lanes: int = 128) -> int:
    """One position's latent row on one layer AS STORED: the latent and
    the rotated key part on the 128-lane grid (1280 B at 512 + 64 in
    bf16)."""
    width = -(-(kv_lora_rank + qk_rope_head_dim) // lanes) * lanes
    return width * elem_bytes


def attention_call(rows_selected: float, kv_lora_rank: int,
                   qk_rope_head_dim: int, elem_bytes: int = 2) -> dict:
    """`rows_selected` rows attended (min(cursor + 1, index_topk) a lane
    a layer, summed over the ticks counted): the bytes that must cross
    HBM at least once."""
    return {"bytes": float(rows_selected * stored_row_bytes(
        kv_lora_rank, qk_rope_head_dim, elem_bytes))}


def indexer_call(rows_indexed: float, index_n_heads: int,
                 index_head_dim: int, elem_bytes: int = 2) -> dict:
    """`rows_indexed` rows scored (cursor + 1 a lane a selecting layer):
    the index keys' bytes (256 B a row at 128 columns in bf16) and the
    scores' operations (2 x 32 x 128 = 8192 a row)."""
    return {"bytes": float(rows_indexed * index_head_dim * elem_bytes),
            "flops": float(rows_indexed * 2 * index_n_heads
                           * index_head_dim)}
