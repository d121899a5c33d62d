"""A lightning indexer's scores over a paged index-key pool as a
streaming Pallas TPU kernel: a lane reads only the pages of the plane
its cursor has reached.

A selecting layer of a SELECTED latent block (models/transformer.
build_lm_paged_decoder: `_indexer`, one query position a lane) scores
every row under a lane's cursor: I(r) = sum_j w_j relu(q_j . k_r) over
the index heads j, k_r the ONE index key position r wrote.  The XLA
lowering gathers `pool[plane, tables]` over ALL `max_blocks_per_seq`
blocks of every lane whatever the cursor, writes a logical-order copy,
reads it back into a product that writes [S, heads, rows] float32, and
reads that again: seven times the bytes the cursors need (PERF.md
section 6, PR 54).  This kernel is the plain sibling of the paged
attention kernel and runs that kernel's own page stream
(`paged_attention.stream_chunks` under `stream_scalars`' cut, two
measured constants apart): tables and a LENGTH a lane on the
scalar-prefetch lane, the pool left in HBM, the
`ceil(length / block_size)` pages of a lane and no other through two
VMEM buffers a chunk at a time, the next lane's first chunk in flight
under this lane's last.  Nothing is written to the
pool (it is read only and not aliased: the key's write is the caller's
scatter, before the call, by data dependence) and no softmax joins the
chunks: a chunk's scores go to the chunk's columns of the lane's
output and that is all.

A chunk's work: the index queries `q` [heads, width] (cast to the
pool's dtype: what the MXU rounds them to on the XLA path too) times
the chunk's keys transposed, float32 accumulation; relu; times `w`
[heads, 1] float32; summed over the heads in float32, over the
smallest row window (128 rows doubled up to the buffer, or a stride)
that holds the pages copied into it.  [S, rows of the table] float32 leaves the
kernel, and of it ONLY the rows under a lane's length are defined: a
window's rows past the copied pages hold what the buffer held, a chunk
the length never reached what VMEM held.  The caller's mask makes them
minus infinity (`_indexer`: `jnp.where(valid, scores, -inf)`).

The call sits behind one module-level `jax.jit` (`paged_index_scores`),
the plane a TRACED scalar: the selecting layers of a program share one
lowered Mosaic call (PERF.md section 6, PR 32).

`select_index_scores` is the one entry point: from the plane's
geometry, the pool's dtype and the platform it returns the kernel, or
None and the reason the XLA gather runs instead.
"""
from __future__ import annotations

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .paged_attention import (_KV_DTYPES, _TILE_ROWS, _windows, chunk_cap,
                              paged_attention_supports, stream_chunks,
                              stream_scalars)

__all__ = ["paged_index_scores", "select_index_scores"]

# Key bytes a chunk.  An index key's page is a fifth of a latent one
# (4 KiB at 16 rows of 128 bfloat16), so this kernel is bound by the
# DMA engine's DESCRIPTORS (some 18 ns a page copy whatever its issue
# loop: PERF.md section 6, PR 54) with the products hidden under them,
# and every chunk's edge is a place where the engine can run dry: on
# the v5e the GLM cell's two planes take 1.16, 0.99, 0.90, 0.84 and
# 0.79 ms at chunks of 128 KiB to 2 MiB.  2 MiB is the cell's whole
# table (432 pages) in one chunk; two such buffers are the whole
# scratch.  A longer table's lanes would be cut in equal chunks under
# it (`paged_attention.chunk_cut`).
_CHUNK_BYTES = 2 * 1024 * 1024
# Table entries the issue loop takes a loop iteration, and the group
# that goes as ONE copy where they are a run (`start_pages`).  With
# nothing but descriptors in its way the loop's own counter, test and
# branch show: a page a copy, the cell's planes take 0.787 ms at 8 to
# an iteration and 0.752 at 16, and 32 gave nothing over 16 (PERF.md
# section 6, PR 54).
_ISSUE_UNROLL = 16


def _kernel(tables_ref, order_ref, lengths_ref, stride_ref, count_ref,
            plane_ref, q_ref, w_ref, hbm, o_ref, buf_ref, sems, cursor_ref, *,
            bs, nb, pages, windows):
    """Grid step s: lane s's scores over its first
    `ceil(lengths[s] / bs)` pages of plane `plane[0]`, which
    `paged_attention.stream_chunks` brings a chunk of the lane's stride
    at a time (`pages` at most), and multiplied over the smallest of
    `windows` (pages, static) that the copied pages fill: the product
    reads `buf_ref`'s pages as a chunk's rows.  `o_ref` [1, chunks, 1,
    rows a chunk at most]: a chunk's scores a tile of their own, so
    that the chunk indexes an untiled axis (a store that starts at a
    lane the kernel computes is what Mosaic's compiler does not
    survive)."""
    def over(lane, chunk, buf, n_rows):
        """The scores of the first `n_rows` rows (static) of the
        lane's chunk c."""
        q, w = lane                                 # [H, D], [H, 1]
        c = chunk[0]

        def multiply(carry):
            keys = buf_ref[buf, :n_rows // bs].reshape(n_rows, -1)
            dots = jax.lax.dot_general(
                q, keys, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)   # [H, n_rows]
            o_ref[0, c, :, :n_rows] = jnp.sum(
                jnp.maximum(dots, 0.0) * w, axis=0, keepdims=True)
            # the scores went to `o_ref`: the loop carries nothing
            return carry
        return multiply

    stream_chunks(
        tables_ref, order_ref, lengths_ref, stride_ref, count_ref,
        lambda: (hbm.at[plane_ref[0]],), (buf_ref,), sems, cursor_ref,
        bs=bs, nb=nb, pages=pages, windows=windows, unroll=_ISSUE_UNROLL,
        before_chunks=lambda _: ((q_ref[0], w_ref[0]), 0), over=over)


@functools.partial(jax.jit, static_argnames=("pages", "tile", "interpret"))
def paged_index_scores(q, w, pool, tables, lengths, plane, *, pages: int,
                       tile: int, interpret: bool = False):
    """Index scores of one query position a lane over a paged pool of
    index keys.

    q [S, H, D] (the index queries; cast to the pool's dtype), w [S, H]
    (the heads' weights; float32), pool [planes, blocks, block_size, D],
    tables [S, NB] int32 block ids, lengths [S] int32 (rows of its
    table, in table order, that lane s scores: at least 1, and no page
    past `ceil(length / block_size)` is read), plane an int32 scalar,
    traced.  A chunk is `pages` pages at most (`paged_attention.
    chunk_cut` cuts a lane's pages under it), its smallest row window
    `tile` of them.  Returns [S, NB * block_size] float32: row r of lane s
    reads sum_j w[s, j] relu(q[s, j] . key r of its table) where
    r < lengths[s], and is NOT DEFINED past it (whatever the buffers
    held, no number among them)."""
    s_n, h, d = q.shape
    bs, nb = pool.shape[2], tables.shape[1]
    scalars, n_chunks = stream_scalars(tables, lengths, bs=bs, pages=pages,
                                       tile=tile, unroll=_ISSUE_UNROLL)
    scalars.append(jnp.asarray(plane, jnp.int32).reshape(1))
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, nb=nb, pages=pages,
                          windows=_windows(pages, tile, _ISSUE_UNROLL)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(s_n,),
            in_specs=[pl.BlockSpec((1, h, d), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec((1, h, 1), lambda s, *_: (s, 0, 0)),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=pl.BlockSpec((1, n_chunks, 1, pages * bs),
                                   lambda s, *_: (s, 0, 0, 0)),
            scratch_shapes=[pltpu.VMEM((2, pages, bs, d), pool.dtype),
                            pltpu.SemaphoreType.DMA((1, 2)),
                            pltpu.SMEM((1,), jnp.int32)]),
        out_shape=jax.ShapeDtypeStruct((s_n, n_chunks, 1, pages * bs),
                                       jnp.float32),
        # a lane's first chunk is started by the lane before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_index_scores",
    )(*scalars, q.astype(pool.dtype),
      w.astype(jnp.float32).reshape(s_n, h, 1), pool)
    out = out.reshape(s_n, n_chunks * pages * bs)
    if n_chunks > 1:
        # a lane's chunks lie a stride apart in its table and a whole
        # buffer apart here: each row from its chunk's tile (a table
        # one buffer does not hold: no cell has one)
        stride = scalars[3][:, None] * bs
        row = jnp.arange(nb * bs, dtype=jnp.int32)[None, :]
        out = jnp.take_along_axis(
            out, row // stride * (pages * bs) + row % stride, axis=1)
    # the last chunk's rows past the table are nobody's
    return out[:, :nb * bs]


def select_index_scores(
        *, index_head_dim: int, block_size: int, kv_dtype: str,
        platform: str, interpret: bool = False,
) -> Tuple[Optional[Callable], Optional[str]]:
    """-> (scores, None), or (None, reason) where the plane is refused
    as `paged_attention_supports` refuses a pool of such rows (what
    `decoder.kernels` reports after "xla:"): off a TPU `not_tpu`
    (unless `interpret`: the tests' Pallas interpreter), a pool that is
    neither float32 nor bfloat16 `kv_dtype`, a key not on the 128-lane
    grid `lane_misaligned`, a page that is no whole number of the
    dtype's sublane tiles `sublane_misaligned`.  The caller then keeps
    its XLA gather.  A function of the plane's geometry, the pool's
    dtype and the platform alone; it touches no array and runs nothing.

    scores(q, w, pool, tables, lengths, plane): `paged_index_scores`
    with the chunk and the row tile chosen from a page's bytes and the
    table's pages: `scores.tiling(table_pages)` says which, and
    `scores.unroll` the table entries its issue loop takes at once."""
    reason = paged_attention_supports(
        d_model=index_head_dim, block_size=block_size, kv_dtype=kv_dtype,
        platform=platform, interpret=interpret)
    if reason is not None:
        return None, reason
    page_bytes = (int(block_size) * int(index_head_dim)
                  * jnp.dtype(_KV_DTYPES[kv_dtype]).itemsize)
    chunk = _CHUNK_BYTES // page_bytes
    row_tile = max(1, _TILE_ROWS // int(block_size))

    def tiling(table_pages):
        """(pages a chunk at most, pages a row tile) over lanes that
        hold `table_pages` pages: the table itself where `_CHUNK_BYTES`
        hold it (every lane is then ONE chunk: every cell's), else the
        whole groups they hold, under which `paged_attention.chunk_cut`
        cuts a lane in equal chunks; a tile no longer than the
        chunk."""
        pages = chunk_cap(int(table_pages), chunk, row_tile, _ISSUE_UNROLL)
        return pages, min(row_tile, pages)

    def scores(q, w, pool, tables, lengths, plane):
        pages, tile = tiling(tables.shape[1])
        return paged_index_scores(q, w, pool, tables, lengths, plane,
                                  pages=pages, tile=tile,
                                  interpret=interpret)

    scores.tiling = tiling
    # the issue loop's group: what `paged_attention.starts_saved` and
    # `dma_ops` count this kernel's starts by
    scores.unroll = _ISSUE_UNROLL
    return scores, None
