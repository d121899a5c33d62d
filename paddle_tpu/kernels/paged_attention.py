"""Paged-attention decode as a Pallas TPU kernel (vLLM-style).

The paged decoder (models/transformer.build_lm_paged_decoder) is the
serving hot path and the top entry on the static analyzer's
memory-bound worklist: its XLA lowering gathers K/V through the block
table into a logical-order [S, ctx, d] copy (in the pool's dtype)
every tick, whole tables, unowned entries included, and contracts
over all of d_model for every head.  This kernel reads K/V blocks
DIRECTLY through the block
table — the table rides the scalar-prefetch lane, so each grid step's
BlockSpec index map addresses one physical pool block and Pallas
streams exactly the blocks a slot owns into VMEM, dequantizing in-lane
(bf16 cast / int8 per-(layer, block) scale) on the way.  No
logical-order copy of the pool ever exists in HBM.

Grid = (slots, max_blocks_per_seq), block index innermost so one
slot's K/V blocks accumulate into a VMEM scratch of the logical
context; the last block step runs the attention math for that slot:
the oracle's QK^T, -inf mask, jax.nn.softmax and att@V with f32
accumulation, one head at a time as 2-D contractions over lane-aligned
column bands (the forms Mosaic lowers).  Under Pallas interpret mode on
CPU greedy decode through it is token-identical to the XLA paged path
for fp32/bf16/int8 (tests/test_paged_attention.py); on a TPU the MXU's
f32 passes differ from XLA's default-precision einsum, so the on-chip
gate is a logit tolerance (chip_smoke.py serve_lm).

`window > 1` is the teacher-forced multi-position variant: the same
kernel body scores a [W, ctx] tile per slot (causal within the window
via the position offsets), so speculative-decoding verification and
chunked prefill ride the same kernel as single-token decode.  The
window is padded to whole 8-row sublane tiles, so W=1 decode and a
draft window share one code path.

`select_paged_attention` is the one entry point: from the decoder's
geometry and the platform it is built for it returns the kernel, or
None and the reason the XLA gather path runs instead.
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention_supports", "select_paged_attention"]

# VMEM budget for the per-slot K+V logical-context scratch: past this
# the context must be tiled with an online softmax, which trades away
# the oracle's exact math, so such a context is refused instead
_SCRATCH_BUDGET_BYTES = 8 * 1024 * 1024

_KV_DTYPES = ("fp32", "bf16", "int8")

# the q/out window is padded to whole f32 sublane tiles so every
# matmul operand and the output store are (8, 128)-aligned
_WINDOW_ALIGN = 8


def paged_attention_supports(*, d_model: int, n_heads: int,
                             block_size: int, max_blocks_per_seq: int,
                             kv_dtype: str, platform: str,
                             interpret: bool = False,
                             kv_width: Optional[int] = None,
                             d_head: Optional[int] = None,
                             ringed: bool = False) -> Optional[str]:
    """None when `select_paged_attention` would return the kernel for
    this geometry on `platform`, else the short reason it is refused
    (what `decoder.kernels` reports after "xla:").  Off a TPU there is
    no Mosaic compiler: refused unless `interpret` (tests) asks for the
    Pallas interpreter, a correctness harness and never a fast path.

    `kv_width` (a pool row: K/V heads x head size), `d_head` and
    `ringed` (some layers keep a ring of blocks instead of the table)
    are the K/V geometry; left out they are multi-head attention over
    `d_model`, the one geometry the kernel computes."""
    if platform != "tpu" and not interpret:
        return "not_tpu"
    if kv_dtype not in _KV_DTYPES:
        return "kv_dtype"
    if (ringed or kv_width not in (None, d_model)
            or (d_head is not None and d_head * n_heads != d_model)):
        # grouped-query heads, a head size that is not d_model /
        # n_heads, or a second kind of cache: the kernel is multi-head
        # attention over d_model-wide rows of ONE table
        return "kv_geometry"
    if d_model % n_heads:
        return "head_split"
    ctx = max_blocks_per_seq * block_size
    if 2 * ctx * d_model * 4 > _SCRATCH_BUDGET_BYTES:
        return "vmem_scratch"
    if platform == "tpu":
        # Mosaic tiling: last dim on the 128-lane grid, K/V block rows
        # on the 8-sublane grid; the per-head slice must stay
        # lane-aligned
        if d_model % 128:
            return "lane_misaligned"
        if (d_model // n_heads) % 128:
            return "head_dim_misaligned"
        if block_size % 8:
            return "sublane_misaligned"
    return None


def _decode_kernel(tables_ref, pos_ref, q_ref, *refs, nb, bs, n_heads,
                   d_head, scale, kv_dtype):
    """Grid step (s, i): dequantize-copy pool block `tables[s, i]` into
    the logical-context scratch; at the slot's last block, run the
    oracle's attention math on the assembled [ctx, d] tiles.

    `refs` is (k block, v block[, k scales, v scales], out, k scratch,
    v scratch): int8 pools bring their per-block f32 scales in whole
    through SMEM and index them with the prefetched table entry."""
    s, i = pl.program_id(0), pl.program_id(1)
    ctx_len = nb * bs
    rows = pl.ds(pl.multiple_of(i * bs, bs), bs)

    if kv_dtype == "int8":
        k_ref, v_ref, ks_ref, vs_ref, o_ref, k_s, v_s = refs
        blk = tables_ref[s, i]
        k_s[rows, :] = k_ref[0, 0].astype(jnp.float32) * ks_ref[blk]
        v_s[rows, :] = v_ref[0, 0].astype(jnp.float32) * vs_ref[blk]
    else:
        k_ref, v_ref, o_ref, k_s, v_s = refs
        k_s[rows, :] = k_ref[0, 0].astype(jnp.float32)
        v_s[rows, :] = v_ref[0, 0].astype(jnp.float32)

    @pl.when(i == nb - 1)
    def _attend():
        # per-head 2-D contractions over lane-aligned column bands —
        # the oracle's QK^T / -inf mask / softmax / att@V, one head at
        # a time (Mosaic has no batched dot without a free lhs dim, and
        # no in-kernel [ctx, d] -> [ctx, h, d_head] relayout)
        w_p = q_ref.shape[1]
        q = q_ref[0].astype(jnp.float32)
        # absolute position of window row w is pos[s] + w; row w
        # attends to logical positions <= it (row 0 is step()'s mask,
        # the rest step_window's teacher-forced causal mask)
        cols = jax.lax.broadcasted_iota(jnp.int32, (w_p, ctx_len), 1)
        row_i = jax.lax.broadcasted_iota(jnp.int32, (w_p, ctx_len), 0)
        keep = cols <= pos_ref[s] + row_i
        for h in range(n_heads):
            band = slice(h * d_head, (h + 1) * d_head)
            sc = jax.lax.dot_general(
                q[:, band], k_s[:, band], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            sc = jnp.where(keep, sc, -jnp.inf)
            w_att = jax.nn.softmax(sc, axis=-1)
            o_ref[0, :, band] = jax.lax.dot_general(
                w_att, v_s[:, band], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)


def select_paged_attention(
        *, d_model: int, n_heads: int, block_size: int,
        max_blocks_per_seq: int, kv_dtype: str, platform: str,
        interpret: bool = False, kv_width: Optional[int] = None,
        d_head: Optional[int] = None, ringed: bool = False,
) -> Tuple[Optional[Callable], Optional[str]]:
    """-> (attend, None), or (None, reason) where
    `paged_attention_supports` refuses: the caller then keeps its XLA
    gather path.  A function of the geometry and the platform alone.

    attend(q, pool_k, pool_v, tables, positions, layer): q is
    [S, W, d_model] f32 (the window W is taken from q's shape at trace
    time: the single-token step passes W=1, speculative verify its
    draft window), pools are the paged decoder's layer-major pool
    pytrees, and the result is the pre-output-projection context
    [S, W, d_model] f32, a drop-in for the gather/einsum/softmax
    block."""
    reason = paged_attention_supports(
        d_model=d_model, n_heads=n_heads, block_size=block_size,
        max_blocks_per_seq=max_blocks_per_seq, kv_dtype=kv_dtype,
        platform=platform, interpret=interpret, kv_width=kv_width,
        d_head=d_head, ringed=ringed)
    if reason is not None:
        return None, reason
    nb, bs = int(max_blocks_per_seq), int(block_size)
    d_head = d_model // n_heads
    scale = 1.0 / math.sqrt(d_head)

    kern = functools.partial(
        _decode_kernel, nb=nb, bs=bs, n_heads=n_heads, d_head=d_head,
        scale=scale, kv_dtype=kv_dtype)

    def attend(q, pool_k, pool_v, tables, positions, layer):
        s_n, w_n = q.shape[0], q.shape[1]
        w_p = -(-w_n // _WINDOW_ALIGN) * _WINDOW_ALIGN
        q = jnp.pad(q, ((0, 0), (0, w_p - w_n), (0, 0)))

        # one physical pool block per grid step, addressed THROUGH the
        # prefetched table — the kernel never sees a logical-order copy
        def blk(s, i, tab, pos):
            return (layer, tab[s, i], 0, 0)

        def slot(s, i, tab, pos):
            return (s, 0, 0)

        pool_spec = pl.BlockSpec((1, 1, bs, d_model), blk)
        if kv_dtype == "int8":
            (pool_k, k_scale), (pool_v, v_scale) = pool_k, pool_v
            scales = (k_scale[layer], v_scale[layer])
            scale_specs = [pl.BlockSpec(memory_space=pltpu.SMEM)] * 2
        else:
            scales, scale_specs = (), []
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(s_n, nb),
            in_specs=[pl.BlockSpec((1, w_p, d_model), slot),
                      pool_spec, pool_spec] + scale_specs,
            out_specs=pl.BlockSpec((1, w_p, d_model), slot),
            scratch_shapes=[
                pltpu.VMEM((nb * bs, d_model), jnp.float32),
                pltpu.VMEM((nb * bs, d_model), jnp.float32),
            ],
        )
        out = pl.pallas_call(
            kern, grid_spec=grid_spec,
            out_shape=jax.ShapeDtypeStruct((s_n, w_p, d_model),
                                           jnp.float32),
            interpret=interpret,
        )(tables, positions, q, pool_k, pool_v, *scales)
        return out[:, :w_n]

    return attend, None
