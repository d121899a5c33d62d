"""A lightning indexer's SELECTION as one Pallas TPU call: the `k` rows
of largest index score a lane, of those its cursor shows, bit for bit
`lm_block.select_rows`'s.

`select_rows` finds the k-th largest score by 32 counts over the lane's
keys (a float32's bits turned so that integer order is the floats'
order).  XLA compiles the counts to a `while` over the keys [lanes,
rows], and whether the keys stay in VMEM for its 32 passes is the
compiler's memory-space assignment's to decide from everything ELSE in
the step: where a cross-program prefetch of a weight holds VMEM while
the selecting layers run, every count reads the keys from HBM behind a
loop iteration's launches (dots3's step: 0.172 ms a selection where
GLM's step, the same rows, pays 0.027; PERF.md section 7, "From
PR 68").  Here a block of lanes' scores comes into VMEM once, the keys
are made there, every count runs over them there, and the rows above
the k-th key and the lowest of the rows AT it leave as the mask, in one
launch whatever the rest of the step does.

The lanes go through in blocks of whole sublane tiles (`_lanes_block`:
64 where they fit, the two selecting cells' tick in ONE block, which
read 18 us a selection alone on the chip where two blocks of 32 read
22 and eight of 8 read 53), one grid step a block: where a tick has
more, the next block's scores arrive and the last block's mask leaves
under this block's passes (Pallas' own double
buffers).  A lane is a sublane and its rows lie along the lanes, 128 a
tile: a count is a compare, a select and an add a tile of registers
and ONE reduction across lanes a pass.  Keys are int32 in SIGNED order
(`select_rows`' unsigned key with the top bit turned), the k-th key is
built in `select_rows`' own bit pattern.  The ties' prefix count, which
decides which rows at the k-th key are taken, is a product of a tile's
0/1 ties with an upper-triangular matrix of ones on the MXU (exact:
counts under 2^24 in float32 sums of bf16 ones) plus the tiles before
it, whose sum the same product's second half hands to every lane.

-0.0 is +0.0 here because the keys say so in integers (the bits of
-0.0 become 0's).  `select_rows` says it with `scores + 0.0`, which
holds where it is called eagerly; under `jax.jit` XLA takes `x + 0.0`
for x on every backend, so the jitted lines rank -0.0 UNDER +0.0 where
a lane's k-th score is a zero of both signs.  The kernel follows the
words (and the plain references' `scores == kth`), not the fold.

The call sits behind one module-level `jax.jit` (`_call`), for the
reason `kernels/grouped_matmul.py` gives: both selecting layers of a
step share one Mosaic module.

`select_index_selection` is the one entry point: from the shapes and
the platform it returns the kernel, or None and the reason
`select_rows`' passes run instead.
"""
from __future__ import annotations

import functools
import types
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["NAME", "select_index_selection", "index_selection_supports"]

NAME = "pallas:select_rows"

_LANES = 128
# the bits of a key, one count each
_PASSES = 32
# what a step's blocks may take of the 16 MiB a Mosaic kernel gets
# unasked: two buffers each of the scores in and the mask out, and the
# keys
_VMEM_BLOCK_BUDGET = 10 * 1024 * 1024
# lanes a grid step, the first that divides the tick's lanes and fits
_BLOCKS = (64, 32, 16, 8)
_TOP = -(1 << 31)


def _vmem_bytes(block: int, rows: int) -> int:
    return 5 * block * rows * 4


def _lanes_block(lanes: int, rows: int) -> Optional[int]:
    """Lanes a grid step: whole sublane tiles that divide `lanes` (all
    of them where they are no whole number of tiles: one block) and fit
    `_VMEM_BLOCK_BUDGET`; None where not even the fewest do."""
    for block in _BLOCKS if lanes % 8 == 0 else (lanes,):
        if lanes % block == 0 and (_vmem_bytes(block, rows)
                                   <= _VMEM_BLOCK_BUDGET):
            return block
    return None


def _kernel(cur_ref, s_ref, o_ref, keys_ref, *, k: int):
    i32, f32 = jnp.int32, jnp.float32
    b_n, r_n = s_ref.shape
    tiles = r_n // _LANES
    top = jnp.array(_TOP, i32)
    cur = jnp.broadcast_to(cur_ref[...], (b_n, _LANES))
    lane = jax.lax.broadcasted_iota(i32, (b_n, _LANES), 1)

    def at(j):
        return slice(j * _LANES, (j + 1) * _LANES)

    def valid(j):
        return lane + j * _LANES <= cur

    # the keys: a score's bits in signed order, -0.0 as +0.0, a valid
    # row's above an invalid row's, which is under every score
    for j in range(tiles):
        bits = jax.lax.bitcast_convert_type(s_ref[:, at(j)], i32)
        bits = jnp.where(bits == top, 0, bits)
        key = jnp.where(bits < 0, bits ^ jnp.array(0x7FFFFFFF, i32), bits)
        keys_ref[:, at(j)] = jnp.where(valid(j), jnp.maximum(key, top + 1),
                                       top)

    def count(holds, than):
        """Rows a lane whose key `holds` against the lane's `than`
        [b_n, 1]: an add a tile of registers, one reduction over lanes."""
        than = jnp.broadcast_to(than, (b_n, _LANES))
        per_lane = jnp.zeros((b_n, _LANES), i32)
        for j in range(tiles):
            per_lane = per_lane + jnp.where(
                holds(keys_ref[:, at(j)], than), 1, 0)
        return jnp.sum(per_lane, axis=1, keepdims=True)

    def bit(i, kth):
        cand = kth | jnp.left_shift(jnp.array(1, i32), 31 - i)
        return jnp.where(count(jnp.greater_equal, cand ^ top) >= k,
                         cand, kth)

    # the largest key that k rows reach: the k-th largest, in
    # `select_rows`' unsigned pattern
    kth = jax.lax.fori_loop(0, _PASSES, bit, jnp.zeros((b_n, 1), i32))
    kth = kth ^ top
    room = jnp.broadcast_to((k - count(jnp.greater, kth)).astype(f32),
                            (b_n, _LANES))
    kth = jnp.broadcast_to(kth, (b_n, _LANES))
    # a tile's inclusive prefix of ties, and its total on every lane
    a = jax.lax.broadcasted_iota(i32, (_LANES, 2 * _LANES), 0)
    b = jax.lax.broadcasted_iota(i32, (_LANES, 2 * _LANES), 1)
    prefix = jnp.where((a <= b) | (b >= _LANES), 1.0, 0.0).astype(
        jnp.bfloat16)
    before = jnp.zeros((b_n, _LANES), f32)
    for j in range(tiles):
        key = keys_ref[:, at(j)]
        tied = (key == kth) & valid(j)
        sums = jnp.dot(jnp.where(tied, 1.0, 0.0).astype(jnp.bfloat16),
                       prefix, preferred_element_type=f32)
        taken = tied & (before + sums[:, :_LANES] <= room)
        o_ref[:, at(j)] = jnp.where((key > kth) | taken, 1, 0)
        before = before + sums[:, _LANES:]


@functools.partial(jax.jit, static_argnames=("k", "block", "interpret"))
def _call(scores, cursors, *, k, block, interpret):
    """Behind one module-level `jax.jit`, as the grouped matmul's: the
    body is traced once a process for a set of shapes and lowered once a
    program, however many layers call it."""
    s_n, r_n = scores.shape
    lanes = pl.BlockSpec((block, r_n), lambda i: (i, 0))
    chosen = pl.pallas_call(
        functools.partial(_kernel, k=k),
        grid=(s_n // block,),
        in_specs=[pl.BlockSpec((block, 1), lambda i: (i, 0)), lanes],
        out_specs=lanes,
        out_shape=jax.ShapeDtypeStruct((s_n, r_n), jnp.int32),
        scratch_shapes=[pltpu.VMEM((block, r_n), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret, name="select_rows")(
            cursors.astype(jnp.int32).reshape(s_n, 1),
            scores.astype(jnp.float32))
    return chosen != 0


def index_selection_supports(*, rows: int, lanes: int, k: int,
                             platform: str, interpret: bool = False
                             ) -> Optional[str]:
    """None where the kernel selects `k` of `rows` rows for `lanes`
    lanes, else the short reason it is refused (what
    `decoder.kernels["index_selection"]` reports after `passes:`)."""
    if platform != "tpu" and not interpret:
        return "not_tpu"
    if rows % _LANES:
        return "lane_misaligned"
    if lanes % 8 and not interpret:
        return "sublane_misaligned"
    if _lanes_block(lanes, rows) is None:
        return "scores_exceed_vmem"
    return None


def select_index_selection(*, rows: int, lanes: int, k: int, platform: str,
                           interpret: bool = False
                           ) -> Tuple[Optional[types.SimpleNamespace],
                                      Optional[str]]:
    """-> (kernel, None), or (None, reason) where
    `index_selection_supports` refuses: the indexer then keeps
    `lm_block.select_rows`' passes.  A function of the shapes and the
    platform alone.

    kernel.select(scores, cursors) -> bool [lanes, rows]: of scores
    [lanes, rows] float32 and cursors [lanes] (a lane's rows 0 to its
    cursor are valid), what `lm_block.select_rows(scores, valid, k)`
    gives: the k valid rows of largest score, every valid row where
    there are k or fewer, a tie at the k-th score to the lower row."""
    reason = index_selection_supports(rows=rows, lanes=lanes, k=k,
                                      platform=platform, interpret=interpret)
    if reason is not None:
        return None, reason
    block = _lanes_block(lanes, rows)

    def select(scores, cursors):
        return _call(scores, cursors, k=k, block=block, interpret=interpret)

    return types.SimpleNamespace(name=NAME, select=select,
                                 lanes_block=block), None
