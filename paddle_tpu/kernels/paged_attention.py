"""Decode attention over a paged K/V pool as a streaming Pallas TPU
kernel: a slot reads only the pages its cursor has reached.

The resident decode step (models/transformer.build_lm_paged_decoder:
`step`, `step_logits`, `step_routing`, one position a slot) attends,
for every slot, over the K/V its sequence has written so far.  The XLA
lowering gathers `pool[layer, tables]` over ALL `max_blocks_per_seq`
blocks of every slot whatever the cursor, writes a logical-order copy
and reads it back: seven to twenty times the bytes the cursors need
(PERF.md section 6, PR 35).  This kernel takes the block tables and a
LENGTH a slot on the scalar-prefetch lane and copies, from the pool
left in HBM, the `ceil(length / block_size)` pages of a slot and no
other: ragged by scalars, one compiled shape.

A table (full layer) and a ring (sliding layer) are the same kernel
given another table and another length: on a table a slot's length is
`cursor + 1`, on a ring `min(cursor + 1, window)`, and ring order needs
no reordering (RoPE is in the keys before they are written, and a
softmax does not care in what order its rows come).

The grid is over SLOTS, not pages (a page a grid step is some 0.35 us
of step overhead for 0.08 us of copying).  Inside a step the slot's
pages arrive in chunks of `pages` pages through manual async copies
into a double-buffered VMEM scratch of two chunks, K and V, whatever
the context; an online softmax (running max and sum, float32) joins
the chunks.  While a slot's last chunk is computed the NEXT slot's
first chunk is already in flight (the scratch and the buffer cursor
outlive a grid step), so DMA latency is paid once a call, not once a
slot.

The arithmetic is `_attention`'s: the query arrives block-diagonal by
K/V head (`q_bd` [S, H, Dkv], built by the decoder, in the pool's
dtype: what the MXU rounds it to on the XLA path too), scores are
`q_bd . page^T` over the whole pool row and the context `p . page`
over the whole row, of which each head keeps its own columns outside.
The kernel therefore knows nothing of head size or grouping.  Scores,
mask, softmax and both sums are float32; `scale` is an argument.

The call sits behind one module-level `jax.jit` (`paged_attention`),
the layer a TRACED scalar: the body is traced once a process for a set
of shapes and lowered once a program however many layers call it (an
inline `pallas_call` is traced and lowered to Mosaic again at every
call site: PERF.md section 6, PR 32).

`select_paged_attention` is the one entry point: from the pool's
geometry, its dtype and the platform it returns the kernel, or None
and the reason the XLA gather path runs instead.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_attention", "paged_attention_supports",
           "select_paged_attention"]

# K (or V) bytes a chunk: the copies of one chunk are in flight while
# the one before it is computed, so a chunk is long enough to hide a
# DMA's latency; only the pages a slot has are copied, so a slot with
# a page or two pays for the chunk's rows in the two products alone.
# On the v5e 1 MiB reads 3 to 6% faster than 512 KiB and 10 to 15%
# faster than 256 KiB, at pages of 64 KB and of 16 KB alike (PERF.md
# section 6, PR 35).  Two chunks of K and two of V are the whole
# scratch: 4 MiB, whatever the context.
_CHUNK_BYTES = 1024 * 1024
_KV_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


def paged_attention_supports(*, d_model: int, n_heads: int,
                             block_size: int, max_blocks_per_seq: int,
                             kv_dtype: str, platform: str,
                             interpret: bool = False,
                             kv_width: Optional[int] = None,
                             d_head: Optional[int] = None,
                             ringed: bool = False) -> Optional[str]:
    """None when `select_paged_attention` would return the kernel for
    this pool on `platform`, else the short reason it is refused (what
    `decoder.kernels` reports after "xla:").  Off a TPU there is no
    Mosaic compiler: refused unless `interpret` (tests) asks for the
    Pallas TPU interpreter, a correctness harness and never a fast
    path.

    The kernel sees a pool ROW (`kv_width`: the K/V heads side by side,
    `d_model` under plain multi-head attention), a page of `block_size`
    rows and the pool's dtype.  `n_heads`, `d_head`, `ringed` and
    `max_blocks_per_seq` change nothing it refuses: the query comes
    block-diagonal by K/V head, a ring is a table, and the scratch is
    two chunks whatever the context."""
    del n_heads, d_head, ringed, max_blocks_per_seq
    if platform != "tpu" and not interpret:
        return "not_tpu"
    if kv_dtype not in _KV_DTYPES:
        # an int8 pool's per-block scales would ride the scores and the
        # weights; no cell serves one
        return "kv_dtype"
    if platform == "tpu":
        # Mosaic tiling: a pool row on the 128-lane grid, a page a whole
        # number of the dtype's sublane tiles (8 rows of float32, 16 of
        # bfloat16), so a page lands in the scratch as whole tiles
        if (kv_width or d_model) % 128:
            return "lane_misaligned"
        if block_size % (32 // jnp.dtype(_KV_DTYPES[kv_dtype]).itemsize):
            return "sublane_misaligned"
    return None


def _kernel(tables_ref, lengths_ref, layer_ref, q_ref, k_hbm, v_hbm,
            o_ref, k_buf, v_buf, sems, cursor_ref, *, bs, nb, pages,
            scale):
    """Grid step s: slot s's attention over its first
    `ceil(lengths[s] / bs)` pages of layer `layer[0]`, a chunk of
    `pages` pages at a time.  `cursor_ref[0]` is the buffer (0 or 1)
    that holds this slot's first chunk, started by the step before."""
    s, n_slots = pl.program_id(0), pl.num_programs(0)
    layer = layer_ref[0]
    rows = pages * bs

    def n_pages(slot):
        return (lengths_ref[slot] + bs - 1) // bs

    def each_page(slot, chunk, buf, do):
        """`do` each page copy (K, then V) of `slot`'s chunk `chunk`
        into buffer `buf`: the pages the slot's length reaches, so a
        table entry past it is never read."""
        first = chunk * pages

        def page(i, _):
            blk = tables_ref[slot * nb + first + i]
            dst = pl.ds(pl.multiple_of(i * bs, bs), bs)
            do(pltpu.make_async_copy(k_hbm.at[layer, blk],
                                     k_buf.at[buf, dst], sems.at[0, buf]))
            do(pltpu.make_async_copy(v_hbm.at[layer, blk],
                                     v_buf.at[buf, dst], sems.at[1, buf]))
            return 0

        jax.lax.fori_loop(0, jnp.minimum(pages, n_pages(slot) - first),
                          page, 0)

    def start(slot, chunk, buf):
        each_page(slot, chunk, buf, lambda copy: copy.start())

    @pl.when(s == 0)
    def _first_slot():
        cursor_ref[0] = 0
        # rows of a chunk no page was copied into weigh 0 in `p . V`:
        # they must be finite, which VMEM as it comes is not
        v_buf[...] = jnp.zeros_like(v_buf)
        start(0, 0, 0)

    first_buf = cursor_ref[0]
    length = lengths_ref[s]
    n_chunks = (n_pages(s) + pages - 1) // pages
    q = q_ref[0]                                            # [H, Dkv]
    o_ref[0] = jnp.zeros_like(o_ref[0])

    def chunk(c, carry):
        m, l = carry
        buf = (first_buf + c) % 2

        @pl.when(c + 1 < n_chunks)
        def _next_chunk():
            start(s, c + 1, 1 - buf)

        @pl.when((c + 1 == n_chunks) & (s + 1 < n_slots))
        def _next_slot():
            start(s + 1, 0, 1 - buf)

        each_page(s, c, buf, lambda copy: copy.wait())
        sc = jax.lax.dot_general(
            q, k_buf[buf], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale     # [H, rows]
        row = c * rows + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(row < length, sc, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new)
        v = v_buf[buf]
        o_ref[0] = alpha * o_ref[0] + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        return m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True)

    h = q.shape[0]
    _, l = jax.lax.fori_loop(
        0, n_chunks, chunk, (jnp.full((h, 1), -jnp.inf, jnp.float32),
                             jnp.zeros((h, 1), jnp.float32)))
    o_ref[0] = o_ref[0] / l
    cursor_ref[0] = (first_buf + n_chunks) % 2


@functools.partial(jax.jit, static_argnames=("scale", "pages",
                                             "interpret"))
def paged_attention(q_bd, pool_k, pool_v, tables, lengths, layer, *,
                    scale: float, pages: int, interpret: bool = False):
    """Attention of one query position a slot over a paged pool.

    q_bd [S, H, Dkv] in the pools' dtype (a query head's columns in
    its K/V head's columns of a pool row, zero outside them), pools
    [layers, blocks, block_size, Dkv], tables [S, NB] int32 block ids,
    lengths [S] int32 (rows of its table, in table order, that slot s
    attends over: at least 1, and no page past `ceil(length /
    block_size)` is read), layer an int32 scalar, traced.  Returns
    [S, H, Dkv] float32: `softmax(scale * q_bd . K^T) . V` over all
    Dkv columns, of which a head keeps its own outside."""
    s_n, h, d_kv = q_bd.shape
    bs, nb = pool_k.shape[2], tables.shape[1]

    def slot(s, *_):
        return (s, 0, 0)

    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, nb=nb, pages=pages,
                          scale=scale),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(s_n,),
            in_specs=[pl.BlockSpec((1, h, d_kv), slot),
                      pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, h, d_kv), slot),
            scratch_shapes=[
                pltpu.VMEM((2, pages * bs, d_kv), pool_k.dtype),
                pltpu.VMEM((2, pages * bs, d_kv), pool_v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((s_n, h, d_kv), jnp.float32),
        # a slot's first chunk is started by the slot before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_attention",
    )(tables.reshape(-1).astype(jnp.int32),
      jnp.maximum(lengths.astype(jnp.int32), 1),
      jnp.asarray(layer, jnp.int32).reshape(1), q_bd, pool_k, pool_v)


def select_paged_attention(
        *, d_model: int, n_heads: int, block_size: int,
        max_blocks_per_seq: int, kv_dtype: str, platform: str,
        interpret: bool = False, kv_width: Optional[int] = None,
        d_head: Optional[int] = None, ringed: bool = False,
) -> Tuple[Optional[Callable], Optional[str]]:
    """-> (attend, None), or (None, reason) where
    `paged_attention_supports` refuses: the caller then keeps its XLA
    gather path.  A function of the pool's geometry, its dtype and the
    platform alone; it touches no array and runs nothing.

    attend(q_bd, pool_k, pool_v, tables, lengths, layer, scale):
    `paged_attention` with the chunk chosen from a page's bytes."""
    reason = paged_attention_supports(
        d_model=d_model, n_heads=n_heads, block_size=block_size,
        max_blocks_per_seq=max_blocks_per_seq, kv_dtype=kv_dtype,
        platform=platform, interpret=interpret, kv_width=kv_width,
        d_head=d_head, ringed=ringed)
    if reason is not None:
        return None, reason
    page_bytes = (int(block_size) * int(kv_width or d_model)
                  * jnp.dtype(_KV_DTYPES[kv_dtype]).itemsize)
    pages = max(1, _CHUNK_BYTES // page_bytes)

    def attend(q_bd, pool_k, pool_v, tables, lengths, layer, scale):
        # a chunk holds no more pages than the table (the ring) has
        return paged_attention(
            q_bd, pool_k, pool_v, tables, lengths, layer,
            scale=float(scale), pages=min(pages, tables.shape[1]),
            interpret=interpret)

    return attend, None
