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
pages arrive in chunks through manual async copies into a
double-buffered VMEM scratch of two buffers of `pages` pages, K and V,
whatever the context; an online softmax (running max and sum, float32)
joins them a chunk at a time: the chunk is the unit of copying, and its
two products run over the smallest row window (128 rows doubled up to
the buffer, or a stride) that holds the pages copied into it.  While a
slot's last chunk is computed the NEXT slot's first chunk is already in
flight (the scratch and the buffer cursor outlive a grid step), so DMA
latency is paid once a call, not once a slot.  That stream is ONE function,
`stream_chunks`, which the index-score kernel (`paged_index_scores.py`)
runs too: a kernel keeps its operand and what it does with a chunk.

HOW a slot's pages are cut into chunks decides what the copies cost,
because a copy can hide only under the products of the chunk BEFORE it
in the stream (chunk c + 1, or the next slot's chunk 0, is started at
the head of chunk c's iteration).  A slot cut `[full, short]` hides its
short copy under its full products and the next slot's FULL copy under
its own SHORT products: it costs `P_full + max(P_short, C_full)` where
`max(P, C)` would do.  So (`chunk_cut`) a slot whose pages the buffer
holds is ONE chunk, and a longer slot is cut in EQUAL chunks of whole
issue groups, the stride its own scalar, made once a call beside the
issue order (`stream_scalars`) and read on the scalar-prefetch lane: no
division enters a grid step.  The buffer is `_CHUNK_BYTES` of pages at
most (`chunk_cap`), or the table where that is shorter: dots3's ring of
33 pages is one chunk where 28 + 5 left a 28-page copy under five
pages' products (PERF.md section 6, PR 66).

A chunk's DMA bookkeeping is per CHUNK, not per page.  A table names
any block, so a copy is a page; but every copy is a descriptor the
scalar core makes and the DMA engine takes (some 17.5 ns a start
whatever its size; a 20 KB latent page is 25 ns of bytes, a 4 KiB
index key page 5), in the one instruction stream the products are in,
and tables mostly name RUNS: a request's blocks are taken at one
admission, side by side and ascending where the free supply allows
(`serving/kv_cache.py`), and a ring is consecutive blocks by
construction.  So the issue loop (`start_pages`) takes a chunk's table
entries in groups of `_ISSUE_UNROLL` and starts ONE copy for a group
whose entries are consecutive ascending block ids (a slice of so many
blocks of the pool into as many pages of the scratch, which is
declared by pages for it), a copy a page for the others as before.
Which groups are runs is the table's own word, computed from the
tables by the same jitted call that hands them to the kernel
(`issue_order`, on the scalar-prefetch lane): never the layer's kind
or a promise of the allocator's.  A chunk starts on a group of the
slot's table, so the groups are the table's own whatever the cut.  It
arrives SORTED, a slot's run groups first (a chunk's are a slice of
either list), by counting and never by a sort, so that the loop over
runs and the loop over the others
are each branch-free: a test inside one loop (a flag a group, or eight
loads and compares) cost a table with no run 7 to 17% of a call, the
sorted lists 1 to 2% (PERF.md section 6, PR 56).  A table in any
order, an idle lane's ring of block 0 and a shared prefix followed by
fresh blocks give the same bytes in the same places, and only the
count of descriptors differs (`starts_saved` and `dma_ops` count
them).  The copies are WAITED FOR on their summed bytes, one wait for
each set bit of the pages copied: `stream_chunks` has the invariant on
the semaphores that makes it safe; the written row's way back to the
pool has semaphores of its own (`wsems`).

The arithmetic is `_attention`'s.  The query arrives as the projection
made it (`q` [S, H*dh], cast to the pool's dtype: what the MXU rounds
it to on the XLA path too) and the kernel lays it out block-diagonal by
K/V head in VMEM, once a grid step: query head i's d_head columns in
ITS K/V head's columns of a pool row and exact zeros outside them
([H, Dkv]; under plain multi-head attention the row broadcast over the
heads under an iota mask, under grouped heads the [H, dh] block tiled
along the lanes under the same mask).  Scores are `q_bd . K^T` over
the whole pool row and the context `p . V` over the whole row into a
float32 accumulator [H, Dkv] in VMEM, of which each head keeps its own
columns when the last chunk is in: [S, H*dh] float32 leaves the kernel,
at a head's own width as the query came.  Scores, mask, softmax and
both sums are float32; `scale`, the heads and their size are static
arguments.

The kernel also WRITES this position's K and V (`write=`): the row
goes into its page as the page lies in VMEM, before the products read
it, and the sublane tile of 8 rows that holds it goes back to the pool
under them (the pools are aliased outputs: the caller's buffers where
it donates them).  The step's two XLA scatters a layer, 7.6 us each on
the v5e and most of that launch, are gone; a row alone cannot go back,
because a DMA moves whole sublane tiles (PERF.md section 6, PR 41).

A LATENT pool is the same kernel over ONE array (`pool_v` None,
`d_value` columns): a row is a compressed latent and one rotated key
part that ALL heads share, and the value is the row's own first
`d_value` columns.  One copy a chunk serves both products: the scores
are `q . row^T` over the whole row with a DENSE query [H, row] (one
"K/V head" for every query head: the block-diagonal operand's
degenerate case, so nothing is masked), the context `p . row[:,
:d_value]` from the same VMEM buffer, [S, H*d_value] float32 out; the
position's one row is written inside as K and V are.  At 128 heads a
row's two products are 240 operations a byte: the one kernel here that
the MXU can pace (PERF.md section 6, PR 45).

A SELECTION (`select=`, a latent pool's: the rows a lightning indexer
chose, `lm_block.select_rows`) rides the same kernel as a row mask a
slot, [S, rows of the table], brought into VMEM a slot a grid step and
read a chunk at a time, from the chunk's first row on, beside the
cursor's own mask: the pages are
those the cursor has reached, the softmax is over the selected rows
alone.  A list of rows cannot be copied row by row: a DMA moves whole
sublane tiles (16 rows of bfloat16: a page of the cells' tables), so
the page is the smallest unit a selection can leave unread, and while
a cursor is a few times the rows selected nearly every page holds one.
A chunk in which nothing is selected leaves the running maximum at
minus infinity, so under a selection the exponentials are taken against
a finite stand-in there.

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
import math
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["chunk_cap", "chunk_cut", "cut_group", "dma_ops", "group_runs",
           "issue_order", "paged_attention", "paged_attention_supports",
           "rows_multiplied", "select_paged_attention", "start_pages",
           "starts_saved", "stream_chunks", "stream_counts",
           "stream_scalars"]

# K (or V) bytes a chunk AT MOST: the cap under which a slot's pages are
# cut (`chunk_cut`), and a buffer of the scratch.  The copies of one
# chunk are in flight while the chunk BEFORE it in the stream is
# multiplied, and under nothing else: so a slot the cap holds is ONE
# chunk (the next slot's whole copy under this slot's whole products),
# a longer one equal chunks, and only the pages a slot has are copied.
# On the v5e 1 MiB read 3 to 6% faster than 512 KiB and 10 to 15%
# faster than 256 KiB, at pages of 64 KB and of 16 KB alike (PERF.md
# section 6, PR 35); 1.25 MiB is the least that holds dots3's ring (33
# pages of 36 KB: 28 + 5 under 1 MiB, the 28-page copy waiting under
# five pages' products) and 64 pages of a latent row stored 640 wide,
# which is eight whole issue groups (PERF.md section 6, PR 66).  Two
# such buffers of K and two of V are the whole scratch: 5 MiB at most,
# whatever the context.
_CHUNK_BYTES = 1280 * 1024
# The fewest rows of a chunk the two products run over: the chunk is
# the unit of COPYING, and the products take the smallest row window
# (this many rows doubled up to the cap, or a stride: `_windows`) that
# holds the pages copied into it, so a slot with a page or two pays
# for 128 rows and not for the chunk's.  One product a window, chosen
# by a switch: a LOOP over
# row tiles costs some 0.2 us an iteration in latency (Mellum 2's
# 1024-row chunks in tiles of 128: 3.6 ms a tick where the switch
# takes 2.5; PERF.md section 6, PR 41).  128 rows fill the MXU's
# columns once; fewer would save no pass of it.
_TILE_ROWS = 128
# Table entries the issue loop takes a loop iteration: a page's table
# read, descriptor and start are scalar work in the one instruction
# stream the products are in, and a loop's counter, test and branch a
# page were a part of it (PERF.md section 6, PR 46).  It is also the
# GROUP that goes as one copy where its entries are a run (PR 56), and
# what a slot's chunks are whole numbers of (`cut_group`): every chunk
# but a slot's LAST starts and ends on a group of the slot's table, so
# only the `n % 8` pages at a slot's end go one at a time.
_ISSUE_UNROLL = 8
_KV_DTYPES = {"fp32": jnp.float32, "bf16": jnp.bfloat16}


def paged_attention_supports(*, d_model: int, block_size: int,
                             kv_dtype: str, platform: str,
                             interpret: bool = False,
                             kv_width: Optional[int] = None,
                             value_width: Optional[int] = None
                             ) -> Optional[str]:
    """None when `select_paged_attention` would return the kernel for
    this pool on `platform`, else the short reason it is refused (what
    `decoder.kernels` reports after "xla:").  Off a TPU there is no
    Mosaic compiler: refused unless `interpret` (tests) asks for the
    Pallas TPU interpreter, a correctness harness and never a fast
    path.

    The kernel sees a pool ROW (`kv_width`: the K/V heads side by side,
    `d_model` under plain multi-head attention), a page of `block_size`
    rows and the pool's dtype.  The heads, a ring and the table's
    length are no parameters because they change nothing it refuses:
    the kernel lays the query out block-diagonal by K/V head whatever
    the heads, a ring is a table, and the scratch is two chunks
    whatever the context.  `value_width`: a LATENT pool, one array
    whose row (`kv_width`, as stored) every head reads whole and whose
    first `value_width` columns are the value."""
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
        if (kv_width or d_model) % 128 or (value_width or 0) % 128:
            return "lane_misaligned"
        if block_size % (32 // jnp.dtype(_KV_DTYPES[kv_dtype]).itemsize):
            return "sublane_misaligned"
    return None


def cut_group(pages: int, tile: int, unroll: int = _ISSUE_UNROLL) -> int:
    """What `chunk_cut` rounds a stride up to at a cap of `pages`
    pages: whole issue groups (`unroll` table entries, the cap where it
    is shorter) that are whole row tiles too, so that a chunk starts on
    a group of the LANE's table and on a row window's edge (whole
    issue groups alone where a tile is longer than the cap); and so
    many of those that a cap holds at most eight, since every stride
    wants a row window of its own (`_windows`)."""
    unroll = min(unroll, pages)
    group = math.lcm(unroll, tile)
    if group > pages:
        return unroll
    return group * -(-pages // (8 * group))


@functools.lru_cache(maxsize=None)
def _windows(pages: int, tile: int,
             unroll: int = _ISSUE_UNROLL) -> Tuple[int, ...]:
    """The row windows (in pages) a chunk of up to `pages` pages is
    multiplied over: the tile doubled up to the cap, the strides a
    lane longer than the cap is cut at (`chunk_cut`: the multiples of
    `cut_group` over half the cap), the cap last.  So a chunk that is
    not a lane's last is multiplied over its own pages and no more.  A
    window is a branch of the products' switch, a whole products body:
    the three that a cap of 64 pages adds to the doubled four cost a
    kernel 0.4 to 0.9 s of compile, 12 KB of lowered text and, all
    lanes at 64 pages, 5% of a call (PERF.md section 6, PR 66)."""
    sizes, group = set(), cut_group(pages, tile, unroll)
    while tile < pages:
        sizes.add(tile)
        tile *= 2
    sizes.update(range(pages // 2 // group * group + group, pages, group))
    return tuple(sorted(sizes)) + (pages,)


def chunk_cap(table_pages: int, pages: int, tile: int,
              unroll: int = _ISSUE_UNROLL) -> int:
    """The pages a chunk has at most over a table of `table_pages`
    pages where a chunk's bytes hold `pages`: the table itself where
    they hold it (every lane is then ONE chunk), else the whole groups
    (`cut_group`) among them, so that equal chunks of whole groups
    reach the cap (one page where a page is more bytes than a
    chunk)."""
    pages = max(1, pages)
    if table_pages <= pages:
        return table_pages
    group = cut_group(pages, tile, unroll)
    return pages // group * group


def chunk_cut(n_pages, cap: int, group: int):
    """How a lane of `n_pages` pages (an integer, or an integer array of
    numpy's or jax's) is cut into chunks of at most `cap` pages ->
    (stride, count): chunk c covers table entries `[c * stride,
    min((c + 1) * stride, n_pages))`.  A lane the cap holds is ONE
    chunk; a longer one is cut in EQUAL chunks, as few as the cap's
    whole groups allow, the stride rounded up to `group`
    (`cut_group`): only a lane's last chunk is short, and never by more
    than the rounding.  A copy hides under the products of the chunk
    BEFORE it in the stream (`stream_chunks`), so chunks of one length
    hide each other's copies where `[full, short]` left a full copy
    under a short chunk's products (PERF.md section 6, PR 66).  The ONE
    cut: the kernels' prologue (`stream_scalars`) and the host's
    account (`rows_multiplied`, `dma_ops`, `stream_counts`) call it."""
    xp = jnp if isinstance(n_pages, jax.Array) else np
    n_pages = xp.maximum(n_pages, 1)
    count = xp.where(n_pages <= cap, 1,
                     -(-n_pages // (cap // group * group)))
    stride = xp.minimum(-(-(-(-n_pages // count)) // group) * group, cap)
    return stride, count


def _window_of(copied, pages: int, tile: int, unroll: int):
    """The smallest of `_windows(pages, tile, unroll)` that holds
    `copied` pages (an integer or an integer array under the cap)."""
    windows = np.asarray(_windows(pages, tile, unroll))
    return windows[np.searchsorted(windows, copied)]


def _cut_counts(n_pages, pages: int, tile: int, unroll: int):
    """Of lanes of `n_pages` pages (an integer or an integer array)
    under a cap of `pages`, by `chunk_cut` -> (the pages of a lane's
    first chunk, the pages its products run over, the waits its chunks
    take: one for each set bit of the pages copied into a chunk, the
    pages of its last chunk's row window)."""
    stride, count = chunk_cut(n_pages, pages, cut_group(pages, tile, unroll))
    last = n_pages - (count - 1) * stride
    last_window = _window_of(last, pages, tile, unroll)
    return (np.minimum(n_pages, stride),
            (count - 1) * _window_of(stride, pages, tile, unroll)
            + last_window,
            (count - 1) * np.bitwise_count(stride) + np.bitwise_count(last),
            last_window)


def rows_multiplied(n_pages, pages: int, tile: int, block_size: int,
                    unroll: int = _ISSUE_UNROLL):
    """Rows of K (and of V) the kernel's two products run over for a
    slot of `n_pages` pages (an integer or an integer array) under a
    cap of `pages` pages a chunk: for each chunk of its cut
    (`chunk_cut`) the smallest window of `_windows(pages, tile,
    unroll)` that holds the pages copied into it."""
    return _cut_counts(n_pages, pages, tile, unroll)[1] * block_size


def group_runs(tables, unroll: int = _ISSUE_UNROLL):
    """Which of the issue loop's groups are RUNS: `tables` [lanes,
    table pages] (a jax array, traced or not, or host integers), a
    lane's first `table pages // unroll * unroll` entries in groups of
    `unroll` -> bool [lanes, groups], True where a group's entries are
    consecutive ascending block ids (`start_pages` then starts ONE copy
    for it).  The table's own word, whatever made it: what
    `issue_order` sorts for the kernels and `starts_saved` counts.  A
    chunk starts on a group (`cut_group`), so a chunk's groups are
    these, however a lane's length cuts it."""
    xp = jnp if isinstance(tables, jax.Array) else np
    lanes, nb = tables.shape
    groups = tables[:, :nb // unroll * unroll].reshape(lanes, -1, unroll)
    return (groups == groups[..., :1] + xp.arange(unroll)).all(axis=2)


def issue_order(tables, unroll: int = _ISSUE_UNROLL):
    """What `start_pages` reads of `tables` [lanes, table pages] (a jax
    array) beside the tables themselves: int32 [lanes, 2 * G + 1] over
    a lane's G = `table pages // unroll` groups.  A row's first G + 1
    words: the run groups among the lane's first k, k = 0 to G
    (`group_runs`); its last G: the lane's groups in the order they
    are issued, the runs first, each kind ascending.  A chunk takes
    groups g0 to g0 + k - 1 of its lane, wherever the lane's length
    cuts it, and those are a SLICE of either list (both ascend): the
    runs from the count ahead of g0 on, the others from g0 less that
    count on.  Two loops, no test inside, and nothing here follows the
    cut.  Sorted by counting, never a `sort`."""
    runs = group_runs(tables, unroll).astype(jnp.int32)
    group = jnp.arange(runs.shape[1])
    ahead = jnp.cumsum(runs, axis=1)
    before = ahead - runs
    # a group's place: among the runs, or after all of them among the rest
    place = jnp.where(runs > 0, before, ahead[:, -1:] + group - before)
    order = jnp.sum(group[:, None] * (place[..., None] == group), axis=1)
    return jnp.concatenate(
        [jnp.zeros_like(runs[:, :1]), ahead, order], axis=1)


def stream_scalars(tables, lengths, *, bs: int, pages: int, tile: int,
                   unroll: int = _ISSUE_UNROLL):
    """The scalar-prefetch operands of a paged kernel's stream
    (`stream_chunks`), made ONCE a call from `tables` [lanes, table
    pages] and `lengths` [lanes] -> ([tables, `issue_order` of them,
    lengths (at least 1), the lanes' cut: their strides, their counts
    of chunks (`chunk_cut`)], each flat int32; the chunks a lane has at
    most).  Every division of the cut is here: a
    grid step multiplies."""
    tables = jnp.asarray(tables, jnp.int32)
    nb = tables.shape[1]
    lengths = jnp.maximum(lengths.astype(jnp.int32), 1)
    group = cut_group(pages, tile, unroll)
    strides, counts = chunk_cut(-(-lengths // bs), pages, group)
    return [tables.reshape(-1),
            issue_order(tables, min(unroll, pages)).reshape(-1),
            lengths, strides.astype(jnp.int32), counts.astype(jnp.int32)
            ], int(chunk_cut(nb, pages, group)[1])


def starts_saved(tables, pages: int, unroll: int = _ISSUE_UNROLL):
    """What `start_pages` saves of a start a page over `tables` [lanes,
    table pages] (host integers) under a cap of `pages` pages a chunk,
    as prefix sums a lane over its groups (`group_runs`): [lanes,
    groups + 1], entry k the starts saved by the lane's first k groups,
    `unroll - 1` for each that is a run.  A chunk starts on a group of
    the lane's table wherever its length cuts it (`cut_group`), so the
    groups a lane of n pages issues whole are its first `n // unroll`.
    A table does not change while a request holds it: computed once,
    `dma_ops` looks a lane's count up whatever its cursor."""
    unroll = min(unroll, pages)
    runs = group_runs(np.asarray(tables), unroll)
    saved = np.zeros((runs.shape[0], runs.shape[1] + 1), np.int64)
    np.cumsum(runs * (unroll - 1), axis=1, out=saved[:, 1:])
    return saved


def dma_ops(n_pages, pages: int, saved=None, unroll: int = _ISSUE_UNROLL,
            tile: int = 1):
    """DMA starts and waits a POOL the kernel performs for a slot of
    `n_pages` pages (an integer or an integer array) under a cap of
    `pages` pages a chunk and row tiles of `tile`: a start a page, less
    what the groups that are runs save (`saved`: `starts_saved` of the
    lanes' tables at this `pages` and `unroll`, a row for each of
    `n_pages`; None: no group is a run), and for each chunk of the
    lane's cut (`chunk_cut`) a wait for each set bit of the pages
    copied into it."""
    ops = n_pages + _cut_counts(n_pages, pages, tile, unroll)[2]
    if saved is None:
        return ops
    return ops - saved[range(len(saved)), n_pages // min(unroll, pages)]


def stream_counts(rows, idle: int, pages: int, tile: int, block_size: int,
                  saved=None, unroll: int = _ISSUE_UNROLL):
    """What one call of a paged kernel's stream (`stream_chunks`) reads
    and does, a plane and a pool, over lanes with `rows` rows under
    their cursors (an integer array: the lanes that hold a sequence, in
    the stream's order) beside `idle` lanes that hold none, which read
    ONE page each (the kernels raise a length to 1) and are counted
    after them -> (pages read, rows multiplied, DMA operations, pages
    whose copy the products cover): `rows_multiplied` and `dma_ops`
    (`saved`, `unroll`: its) over `ceil(rows / block_size)` pages a
    lane, cut by `chunk_cut` under a cap of `pages` and row windows
    from `tile`.  COVERED: every chunk's copy but the call's first is
    in flight while the chunk before it in the stream is multiplied,
    and counts `min(its pages, the pages of that chunk's row window)`:
    a lane's later chunks whole (a stride is its own window), its
    first chunk against the window of the lane before's last."""
    n_pages = np.concatenate([-(-np.asarray(rows, np.int64) // block_size),
                              np.ones(idle, np.int64)])
    first, windows, waits, last_window = _cut_counts(n_pages, pages, tile,
                                                     unroll)
    read = int(n_pages.sum())
    starts = read
    if saved is not None:
        held = len(n_pages) - idle
        starts -= saved[range(held),
                        n_pages[:held] // min(unroll, pages)].sum()
    return (read, int(windows.sum()) * block_size,
            int(starts + waits.sum()),
            int(read - first.sum()
                + np.minimum(first[1:], last_window[:-1]).sum()))


def start_pages(tables_ref, order_ref, lane, first, n, planes, bufs, buf,
                sems, *, nb: int, unroll: int):
    """Start the copies of the `n` pages that entries `first` to
    `first + n - 1` of lane `lane`'s table name (`tables_ref` [lanes *
    nb], scalars in SMEM; no other entry is read; `first` on a group of
    `unroll` entries) into pages 0 to `n - 1` of buffer `buf`: for each
    pool i, from `planes[i]` ([blocks, block_size, width], HBM) into
    `bufs[i]` ([2, pages, block_size, width], VMEM), counted on
    `sems[i, buf]`.  The first `n // unroll` groups go by `order_ref`
    (`issue_order` of the same tables: the lane's row of it, of which
    these groups are a slice of either list): ONE copy of `unroll`
    pages for each that is a run, then a copy a page for the others;
    the `n % unroll` last pages a copy each.  The one issue loop of the
    paged kernels (`stream_chunks`' own)."""
    table = lane * nb

    def copy(entry, n_pages=1):
        """`n_pages` pages from the block the lane's entry `entry`
        names on, to the buffer's page `entry - first` on."""
        blk, i = tables_ref[table + entry], entry - first
        for i_pool, (plane, into) in enumerate(zip(planes, bufs)):
            if n_pages == 1:
                src, dst = plane.at[blk], into.at[buf, i]
            else:
                src = plane.at[pl.ds(blk, n_pages)]
                dst = into.at[buf, pl.ds(i, n_pages)]
            pltpu.make_async_copy(src, dst, sems.at[i_pool, buf]).start()

    def page(entry, carry=0):
        copy(entry)
        return carry

    grouped = 0
    if unroll > 1:
        lane_groups = nb // unroll
        groups = n // unroll
        grouped = groups * unroll
        row = lane * (2 * lane_groups + 1)
        ahead = row + first // unroll
        runs_ahead = order_ref[ahead]
        runs = order_ref[ahead + groups] - runs_ahead
        # the lane's run groups from this chunk's first on, and its
        # others: those past all the lane's runs in the list
        order = row + lane_groups + 1
        of_runs = order + runs_ahead
        of_others = order + order_ref[row + lane_groups] + (
            ahead - row - runs_ahead)

        def run(i, carry):
            copy(order_ref[of_runs + i] * unroll, unroll)
            return carry

        def pages(i, carry):
            entry = order_ref[of_others + i] * unroll
            for j in range(unroll):
                copy(entry + j)
            return carry

        jax.lax.fori_loop(0, runs, run, 0)
        jax.lax.fori_loop(0, groups - runs, pages, 0)
    jax.lax.fori_loop(first + grouped, first + n, page, 0)


def stream_chunks(tables_ref, order_ref, lengths_ref, stride_ref, count_ref,
                  planes, bufs, sems, cursor_ref, *, bs, nb, pages, windows, unroll,
                  before_chunks, over, before_first_start=lambda: None,
                  before_count=lambda: None,
                  around_products=lambda lane, chunk, buf, products:
                  products(),
                  after_chunks=lambda lane, carry: None):
    """Grid step s of a paged kernel: lane s's first
    `ceil(lengths[s] / bs)` pages through two buffers of `pages` pages,
    a chunk of the lane's own stride at a time (`chunk_cut`: the lane
    whole where the buffer holds it, else equal chunks).  The one chunk
    loop of the paged kernels: a kernel hands in its operand and what
    it does with a chunk's rows.

    `tables_ref` [lanes * nb], `order_ref`, `lengths_ref` [lanes],
    `stride_ref` and `count_ref` [lanes] (a lane's stride, its count of
    chunks): scalars in SMEM, `stream_scalars` of the call.  `planes()` -> a
    plane [blocks, bs, width] in HBM a
    pool (asked at every start: a kernel may read its plane's scalar
    there); `bufs`: [2, pages, bs, width] in VMEM a pool; `sems`
    [pools, 2]; `cursor_ref[0]`: the buffer that holds this lane's
    first chunk, started by the step before.  `windows`: `_windows`;
    `unroll`: `start_pages`' group.

    Chunk c + 1 (or the NEXT lane's first) is started at the head of
    chunk c's iteration and chunk c is waited for before its products:
    a copy hides under the products of the chunk before it in the
    stream and under nothing else, which is why a lane's chunks are of
    one length and a lane the buffer holds is one chunk.

    The copies are WAITED FOR on their summed bytes: a DMA semaphore
    counts bytes, so a wait need not name the copy it waits for, only
    as many bytes, and a chunk of `copied` pages is one wait a pool for
    each set bit of `copied`, on a descriptor of 2^b pages.  The
    invariant that makes it safe: `sems[pool, buf]` never has more
    than ONE chunk's copies outstanding.  Buffers alternate; chunk
    c + 1 (or the next lane's first) is started into `1 - buf` while
    chunk c, in `buf`, is still to be waited for, and `1 - buf`'s last
    chunk was waited for whole before its products ran; whatever else
    a kernel copies has semaphores of its own.  So the bytes a wait
    takes off a semaphore are that chunk's and no other's (PERF.md
    section 6, PR 46).

    The kernel's side, in the order it is called (the order the
    compiled kernels were measured in: tests/test_kernels_lower_tpu.py
    pins their text): `before_first_start()`, in the first grid step
    alone, the cursor set; `before_count()` -> anything, the cursor read
    and the lane's chunks not yet counted; `before_chunks(that)` ->
    (lane, carry), the kernel's operand, handed back in every later
    call, and the chunk loop's carry; `over(lane, chunk, buf, n_rows)`
    -> carry -> carry, a branch of the switch: the first `n_rows` rows
    (static) of buffer `buf`, `chunk` = (c, first, copied): the lane's
    chunk c, `copied` pages from page `first` of its table on;
    `around_products(lane, chunk, buf, products)` -> carry,
    `products()` the switch: what else a kernel does between a chunk's
    wait and the next chunk; `after_chunks(lane, carry)`, before the
    cursor is written."""
    s, n_lanes = pl.program_id(0), pl.num_programs(0)

    def n_pages(lane):
        return (lengths_ref[lane] + bs - 1) // bs

    def start(lane, chunk, buf):
        """Start the page copies (a pool each) of `lane`'s chunk
        `chunk` into buffer `buf`: the pages the lane's length reaches,
        so a table entry past it is never read."""
        stride = stride_ref[lane]
        first = chunk * stride
        start_pages(tables_ref, order_ref, lane, first,
                    jnp.minimum(stride, n_pages(lane) - first),
                    planes(), bufs, buf, sems, nb=nb,
                    unroll=min(unroll, pages))

    def wait(copied, buf):
        """Wait for the `copied` pages a `start` sent to buffer `buf`:
        of a wait's descriptor only the size and the semaphore
        matter."""
        for bit in range(pages.bit_length()):
            @pl.when(((copied >> bit) & 1) == 1)
            def _wait(size=pl.ds(0, 1 << bit)):
                for i_pool, into in enumerate(bufs):
                    pltpu.make_async_copy(into.at[buf, size],
                                          into.at[buf, size],
                                          sems.at[i_pool, buf]).wait()

    @pl.when(s == 0)
    def _first_lane():
        cursor_ref[0] = 0
        before_first_start()
        start(0, 0, 0)

    first_buf = cursor_ref[0]
    read = before_count()
    stride, n_chunks = stride_ref[s], count_ref[s]
    reached = n_pages(s)
    lane, carry = before_chunks(read)

    def chunk(c, carry):
        buf = (first_buf + c) % 2
        more = c + 1 < n_chunks

        @pl.when(more | (s + 1 < n_lanes))
        def _next():
            # this lane's next chunk, else the next lane's first
            start(jnp.where(more, s, s + 1), jnp.where(more, c + 1, 0),
                  1 - buf)

        # the pages copied into this chunk, from page `first` on
        first = c * stride
        copied = jnp.minimum(stride, reached - first)
        wait(copied, buf)

        def products():
            # over the smallest window they fill.  Mosaic lowers a
            # switch to a cascade of tests, branch 0 first: the longest
            # window, which a lane's equal chunks take, is branch 0
            # (seven windows in ascending order cost a chunk of 64
            # pages 0.15 us over the same in descending: PERF.md
            # section 6, PR 66)
            longest_first = windows[::-1]
            return jax.lax.switch(
                sum((copied <= w).astype(jnp.int32)
                    for w in longest_first[1:]),
                [over(lane, (c, first, copied), buf, w * bs)
                 for w in longest_first], carry)

        return around_products(lane, (c, first, copied), buf, products)

    after_chunks(lane, jax.lax.fori_loop(0, n_chunks, chunk, carry))
    cursor_ref[0] = (first_buf + n_chunks) % 2


def _kernel(tables_ref, order_ref, lengths_ref, stride_ref, count_ref,
            layer_ref, *refs, bs, nb, pages, windows, scale, h, dh, n_kv,
            writes, d_value=0, selects=False):
    """Grid step s: slot s's attention over its first
    `ceil(lengths[s] / bs)` pages of layer `layer[0]`, which
    `stream_chunks` brings a chunk of the slot's stride at a time
    (`pages` at most), and multiplied over the smallest of `windows`
    (pages, static) that the copied pages fill.  `d_value`: a latent
    pool, ONE array whose first `d_value` columns are the value (0: a K
    pool and a V pool).  `selects`: a row mask a slot follows the query
    ([1, rows of the table and a chunk's more] float32, 1 where the row
    is selected; a chunk reads it from its first row on, which is a
    whole number of lane tiles wherever a slot has a second chunk).
    The products read a buffer's pages as a chunk's rows."""
    n_pools = 1 if d_value else 2
    refs = iter(refs)

    def take(n):
        return tuple(next(refs) for _ in range(n))

    # as `paged_attention` orders them: the written row's scalars and
    # new rows (a pool each) only where the kernel writes
    wrow_ref = next(refs) if writes else None
    q_ref = next(refs)
    sel_ref = next(refs) if selects else None
    new_refs = take(n_pools if writes else 0)
    hbms, (o_ref,) = take(n_pools), take(1)
    outs = take(n_pools if writes else 0)
    bufs = take(n_pools)
    acc_ref, sems, cursor_ref = take(3)
    wsems = next(refs) if writes else None
    # the keys' buffer, and the values': the same one on a latent pool
    k_buf, v_buf = bufs[0], bufs[-1]
    s = pl.program_id(0)
    layer = layer_ref[0]
    d_kv = n_kv * dh
    group = h // n_kv
    # the rows a written row goes back to the pool with: a sublane
    # tile where a page is whole tiles, else the page
    group_rows = 8 if bs % 8 == 0 else bs

    def zero_values():
        # rows of a window no page was copied into weigh 0 in `p . V`:
        # they must be finite, which VMEM as it comes is not
        v_buf[...] = jnp.zeros_like(v_buf)

    def iota(shape, axis):
        return jax.lax.broadcasted_iota(jnp.int32, shape, axis)

    def operand(length):
        """The block-diagonal operand [H, Dkv]: query head i's d_head
        columns in ITS K/V head's columns of a pool row, exact zeros
        outside them (`_block_diagonal` of the decoder, built here).
        -> ((the slot's length, the operand, each head's own columns,
        its K/V head), the softmax's carry: the running max and sum)."""
        own = kv_head = None
        if d_value:
            # every head reads the whole latent row: q_ref[0] [H, row]
            # IS the operand
            q = q_ref[0]
        else:
            kv_head = iota((h, 1), 0)
            if group > 1:
                kv_head = jax.lax.div(kv_head, group)
            col = iota((h, d_kv), 1)
            own = (col >= kv_head * dh) & (col < kv_head * dh + dh)
            if group == 1:
                # q_ref[0] is the projection's row [1, H*dh]: a head's
                # columns of it ARE its columns of a pool row
                q_wide = jnp.broadcast_to(q_ref[0].astype(jnp.float32),
                                          (h, d_kv))
            else:
                # q_ref[0] is [H, dh]: a head's columns under every K/V head
                q_wide = jnp.concatenate(
                    [q_ref[0].astype(jnp.float32)] * n_kv, axis=1)
            q = jnp.where(own, q_wide, 0.0).astype(k_buf.dtype)
        acc_ref[...] = jnp.zeros_like(acc_ref)
        return (length, q, own, kv_head), (
            jnp.full((h, 1), -jnp.inf, jnp.float32),
            jnp.zeros((h, 1), jnp.float32))

    def row_copies(first, buf):
        """This position's K and V on their way back to the pool: the
        `group_rows` rows of the chunk in buffer `buf` (the table's
        pages from `first` on) that hold row `wrow_ref[s]` of the
        slot's table, to their place in its page."""
        at = wrow_ref[s] - first * bs
        tile = pl.multiple_of(at // group_rows * group_rows, group_rows)
        blk = tables_ref[s * nb + first + at // bs]
        dst = pl.ds(pl.multiple_of(tile % bs, group_rows), group_rows)
        return tuple(
            pltpu.make_async_copy(back.at[buf, at // bs, dst],
                                  out.at[layer, blk, dst], wsems.at[i])
            for i, (back, out) in enumerate(zip(bufs, outs)))

    def put_row(first, copied, buf):
        """Where the chunk of `copied` pages from page `first` on holds
        the row this tick writes (`wrow_ref[s]` of the slot's table;
        negative: a slot that writes nothing), put this position's K
        and V into its page as it lies in VMEM, before the products
        read it, and start the page's rows back to the pool.  ->
        whether it did."""
        wrow = wrow_ref[s]
        here = (wrow >= first * bs) & (wrow < (first + copied) * bs)

        @pl.when(here)
        def _put():
            at = wrow - first * bs
            page = at // bs
            mine = iota((bs, 1), 0) == at % bs
            for into, new_ref in zip(bufs, new_refs):
                into[buf, page] = jnp.where(mine, new_ref[0],
                                            into[buf, page])
            for copy in row_copies(first, buf):
                copy.start()

        return here

    def around_written_row(_, chunk, buf, products):
        if not writes:
            return products()
        _, first, copied = chunk
        written = put_row(first, copied, buf)
        carry = products()

        # the row's way back to the pool lay under the products
        @pl.when(written)
        def _row_is_back():
            for copy in row_copies(first, buf):
                copy.wait()
        return carry

    # a chunk's first row is a whole number of lane tiles wherever a
    # slot has a second chunk: it starts on an issue group
    lane_tiled = (min(_ISSUE_UNROLL, pages) * bs) % 128 == 0

    def over(lane, chunk, buf, n_rows):
        """The online softmax over the first `n_rows` rows (static) of
        the chunk of `copied` pages from page `first` of the table
        on."""
        length, q = lane[:2]
        _, first, copied = chunk
        # a window's rows past the copied pages are nobody's (under a
        # stride of whole row tiles only a slot's last chunk has any)
        base = first * bs
        seen_to = jnp.minimum(length - base, copied * bs)

        def multiply(carry):
            m, l = carry
            k = k_buf[buf, :n_rows // bs].reshape(n_rows, -1)
            sc = jax.lax.dot_general(
                q, k, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            seen = iota(sc.shape, 1) < seen_to            # [H, n_rows]
            if selects:
                at = pl.multiple_of(base, 128) if lane_tiled else base
                seen &= sel_ref[0, :, pl.ds(at, n_rows)] > 0.0
            sc = jnp.where(seen, sc, -jnp.inf)
            m_new = jnp.maximum(m, jnp.max(sc, axis=1, keepdims=True))
            # nothing selected so far: exp(-inf - -inf) is no number
            m_ref = (jnp.where(m_new == -jnp.inf, 0.0, m_new)
                     if selects else m_new)
            alpha = jnp.exp(m - m_ref)
            p = jnp.exp(sc - m_ref)
            v = (v_buf[buf, :n_rows // bs, :, :d_value] if d_value
                 else v_buf[buf, :n_rows // bs]).reshape(n_rows, -1)
            acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
                p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            return m_new, alpha * l + jnp.sum(p, axis=1, keepdims=True)
        return multiply

    def result(lane, carry):
        # of all Dkv columns a head keeps its K/V head's
        # (`_own_columns` of the decoder)
        (_, _, own, kv_head), (_, l) = lane, carry
        ctx = acc_ref[...] / l
        if d_value:
            o_ref[0] = ctx
        elif group == 1:
            o_ref[0] = jnp.sum(jnp.where(own, ctx, 0.0), axis=0,
                               keepdims=True)
        else:
            o_ref[0] = sum(
                jnp.where(kv_head == g, ctx[:, g * dh:(g + 1) * dh], 0.0)
                for g in range(n_kv))

    stream_chunks(
        tables_ref, order_ref, lengths_ref, stride_ref, count_ref,
        lambda: tuple(hbm.at[layer] for hbm in hbms), bufs, sems,
        cursor_ref, bs=bs, nb=nb, pages=pages, windows=windows,
        unroll=_ISSUE_UNROLL, before_first_start=zero_values,
        before_count=lambda: lengths_ref[s], before_chunks=operand,
        over=over, around_products=around_written_row, after_chunks=result)


@functools.partial(jax.jit, static_argnames=(
    "scale", "pages", "tile", "n_heads", "d_head", "d_value", "interpret"))
def paged_attention(q, pool_k, pool_v, tables, lengths, layer, *,
                    scale: float, pages: int, tile: int, n_heads: int,
                    d_head: int, d_value: int = 0,
                    interpret: bool = False, write=None, select=None):
    """Attention of one query position a slot over a paged pool.

    q [S, H*dh] (the projection's rows; cast to the pools' dtype: what
    the MXU rounds it to on the XLA path too), pools [layers, blocks,
    block_size, Dkv] with Dkv = n_kv * dh and H a multiple of n_kv,
    tables [S, NB] int32 block ids, lengths [S] int32 (rows of its
    table, in table order, that slot s attends over: at least 1, and
    no page past `ceil(length / block_size)` is read), layer an int32
    scalar, traced.  A chunk is `pages` pages AT MOST (the scratch is
    two of them a pool; `chunk_cut` cuts a slot's pages under it), its
    smallest row window `tile` of them.  Returns [S, H*dh] float32: head i's
    `softmax(scale * q_i . K_g^T) . V_g` over its K/V head g.

    `write` = (k [S, Dkv], v [S, Dkv], rows [S] int32): this
    position's K and V, written by the kernel itself at row `rows[s]`
    of slot s's table (under its length; negative: nothing is written)
    into the page as it lies in VMEM before the products read it, and
    from there back into the pools, which are then RETURNED beside the
    result, (out, pool_k, pool_v), the same buffers where the caller
    donates them: no scatter runs before the kernel.

    A LATENT pool: `pool_v` None and `d_value` > 0 (`d_head` is not
    read).  `pool_k`'s row is then what every head attends over, q
    [S, H*Dkv] a whole row a head, the result [S, H*d_value] float32:
    head i's `softmax(scale * q_i . rows^T) . rows[:, :d_value]`;
    `write` = (row [S, Dkv], None, rows) and (out, pool) comes back.
    `select` [S, NB * block_size] bool: the rows of its table, in table
    order, that slot s attends over of those under its length (at least
    one of them: a slot with none divides by zero)."""
    s_n, h = q.shape[0], n_heads
    bs, nb, d_kv = pool_k.shape[2], tables.shape[1], pool_k.shape[3]
    pools = (pool_k,) if d_value else (pool_k, pool_v)
    dh = d_kv if d_value else d_head
    n_kv = d_kv // dh
    # under plain multi-head attention the kernel takes a slot's query
    # and gives its result as ONE row, under grouped heads (a latent
    # pool's one row for all heads among them) a row a head
    block = (1, 1, h * dh) if n_kv == h else (1, h, dh)
    out_block = (1, h, d_value) if d_value else block

    def slot(s, *_):
        return (s, 0, 0)

    def hbm():
        return pl.BlockSpec(memory_space=pl.ANY)

    scalars, n_chunks = stream_scalars(tables, lengths, bs=bs, pages=pages,
                                       tile=tile)
    scalars.append(jnp.asarray(layer, jnp.int32).reshape(1))
    inputs = [q.astype(pool_k.dtype).reshape((s_n,) + block[1:])]
    in_specs = [pl.BlockSpec(block, slot)]
    out_specs = [pl.BlockSpec(out_block, slot)]
    out_shape = [jax.ShapeDtypeStruct((s_n,) + out_block[1:], jnp.float32)]
    scratch = [pltpu.VMEM((2, pages, bs, d_kv), pool.dtype)
               for pool in pools]
    scratch += [pltpu.VMEM(out_block[1:] if d_value else (h, d_kv),
                           jnp.float32),
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                pltpu.SMEM((1,), jnp.int32)]
    if select is not None:
        # a slot's mask in table order, and unselected rows after it
        # for the window of a last chunk that starts past row 0
        rows = (nb + (n_chunks > 1) * pages) * bs
        mask = jnp.pad(select.astype(jnp.float32),
                       ((0, 0), (0, rows - nb * bs)))
        inputs.append(mask.reshape(s_n, 1, rows))
        in_specs.append(pl.BlockSpec((1, 1, rows), slot))
    aliases = {}
    if write is not None:
        *news, rows = write
        scalars.append(rows.astype(jnp.int32))
        for new, pool in zip(news, pools):
            inputs.append(new.astype(pool.dtype).reshape(s_n, 1, d_kv))
            in_specs.append(pl.BlockSpec((1, 1, d_kv), slot))
            out_specs.append(hbm())
            out_shape.append(jax.ShapeDtypeStruct(pool.shape, pool.dtype))
        # operands are numbered with the scalars: the pools come last
        aliases = {len(scalars) + len(inputs) + i: 1 + i
                   for i in range(len(pools))}
        scratch.append(pltpu.SemaphoreType.DMA((len(pools),)))
    out = pl.pallas_call(
        functools.partial(_kernel, bs=bs, nb=nb, pages=pages,
                          windows=_windows(pages, tile), scale=scale,
                          h=h, dh=dh, n_kv=n_kv,
                          writes=write is not None, d_value=d_value,
                          selects=select is not None),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars), grid=(s_n,),
            in_specs=in_specs + [hbm() for _ in pools],
            out_specs=out_specs, scratch_shapes=scratch),
        out_shape=out_shape, input_output_aliases=aliases,
        # a slot's first chunk is started by the slot before it
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=pltpu.InterpretParams() if interpret else False,
        name="paged_attention",
    )(*scalars, *inputs, *pools)
    ctx = out[0].reshape(s_n, -1)
    return ctx if write is None else (ctx,) + tuple(out[1:])


def select_paged_attention(
        *, d_model: int, n_heads: int, block_size: int, kv_dtype: str,
        platform: str, interpret: bool = False,
        kv_width: Optional[int] = None, d_head: Optional[int] = None,
        value_width: Optional[int] = None,
) -> Tuple[Optional[Callable], Optional[str]]:
    """-> (attend, None), or (None, reason) where
    `paged_attention_supports` refuses: the caller then keeps its XLA
    gather path.  A function of the pool's geometry, its dtype and the
    platform alone; it touches no array and runs nothing.

    attend(q, pool_k, pool_v, tables, lengths, layer, scale):
    `paged_attention` at this geometry's heads, with the chunk and the
    row tile chosen from a page's bytes and the table's (the ring's)
    pages: `attend.tiling(table_pages)` says which.  With
    `value_width` the pool is a LATENT one (`paged_attention`): one
    array of rows `kv_width` wide, `pool_v` None and `write`'s V
    None; `select` is `paged_attention`'s."""
    reason = paged_attention_supports(
        d_model=d_model, block_size=block_size, kv_dtype=kv_dtype,
        platform=platform, interpret=interpret, kv_width=kv_width,
        value_width=value_width)
    if reason is not None:
        return None, reason
    page_bytes = (int(block_size) * int(kv_width or d_model)
                  * jnp.dtype(_KV_DTYPES[kv_dtype]).itemsize)
    chunk = _CHUNK_BYTES // page_bytes
    row_tile = max(1, _TILE_ROWS // int(block_size))

    def tiling(table_pages):
        """(pages a chunk at most, pages a row tile) over slots that
        hold `table_pages` pages (a table's, a ring's): the table
        itself where `_CHUNK_BYTES` hold it (every slot is then ONE
        chunk), else the whole groups they hold (`chunk_cap`); a tile
        no longer than the chunk."""
        pages = chunk_cap(int(table_pages), chunk, row_tile)
        return pages, min(row_tile, pages)

    def attend(q, pool_k, pool_v, tables, lengths, layer, scale,
               write=None, select=None):
        pages, tile = tiling(tables.shape[1])
        return paged_attention(
            q, pool_k, pool_v, tables, lengths, layer,
            scale=float(scale), pages=pages, tile=tile,
            n_heads=int(n_heads),
            d_head=int(d_head or d_model // n_heads),
            d_value=int(value_width or 0), interpret=interpret,
            write=write, select=select)

    attend.tiling = tiling
    return attend, None
