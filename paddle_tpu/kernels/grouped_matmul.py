"""The served expert layer's grouped matmuls as a Pallas TPU kernel.

`lm_block.moe_ffn` puts a tick's assignments (rows x experts per
token) in expert order and needs, for every expert that got rows, its rows
times its gate, up and down matrices.  `jax.lax.ragged_dot` says that,
but the TPU compiler runs it as a DENSE product of all rows with all
experts and masks seven eighths of it away (PERF.md section 5): the
layer then costs operations it does not need, and its time grows with
slots x experts.  This kernel reads each expert's rows only.

One work item is (an expert with rows, a tile of `tm` sorted rows it
has rows in); the grid walks the work items in expert order and the
item's expert and row tile ride the scalar-prefetch lane, so each
step's BlockSpec index maps address that expert's matrix ([D, F] or
[F, D], as the state dict holds it: no copy, pad, transpose or
concatenation of a weight) and that tile of rows.  Consecutive items
of one expert keep the same weight block, which Pallas does not fetch
again, so an expert's matrices cross HBM once however its rows
straddle tiles, and an expert with no rows has no item: no bytes, no
operations.  Items past the last (the grid is the static bound
tiles + experts - 1) repeat its indices (its last K tile's, where the
matrix comes in tiles) and skip the body, so they move no block.

A block is the expert's WHOLE matrix wherever that fits the kernel's
VMEM (`_k_tile`), and else a tile of its ROWS (the contraction axis
K: of `d_model` for gate and up, of `d_ff` for down): the largest
equal split that fits.  The grid then has the K tiles as its inner
axis: an item's row tile meets the expert's K tiles in turn, the
partial products are summed in a float32 accumulator in VMEM, and the
last tile's step stores.  Rows, not columns, because a tile of rows is
ONE contiguous run of HBM, as a whole matrix is: a tile of 512 columns
is 6144 runs of 1 KB, which the v5e reads at 81 to 88% of its HBM peak
depending on where the allocator happened to put the weights (PERF.md
section 6, PR 40), a spread that a serving cell's tails cannot carry.
One expert of 6144 x 2048 in bf16 is 25 MB a matrix: gate and up go in
three tiles of 2048 rows, down in two of 1024.  An expert whose rows
straddle two row tiles has its K tiles read once a row tile.

A step multiplies the whole row tile with the expert's matrix (bf16
operands, float32 accumulation over all of K in one dot) and stores
only the rows that are the expert's, under a mask from the group
offsets; the tile's other rows keep what their own experts stored (an
output block stays in VMEM while consecutive items share it).  A
row's result therefore depends on no other row, on no group size and
on no tile boundary.  Gate, up and `silu(gate) * up` are ONE call (the
rows are read once, the float32 intermediates never reach HBM, the
product is rounded to the weights' dtype as `moe_ffn` rounds it), down
is a second.

Both calls sit behind one module-level `jax.jit` (`_call`): the
kernel bodies are traced once a process for a set of shapes and
lowered once a program, however many layers call them (a step is
unrolled over its layers; an inline `pallas_call` is traced and
lowered to Mosaic again at every call site, in every process, compile
cache or not).  The work items
(`plan`) are a few `jax.numpy` lines over the group sizes, computed
inside the step.

`select_grouped_matmul` is the one entry point: from the shapes, the
weights' dtype and the platform it returns the kernel, or None and
the reason `ragged_dot` runs instead.
"""
from __future__ import annotations

import functools
import types
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["NAME", "select_grouped_matmul", "grouped_matmul_supports"]

NAME = "pallas_grouped_matmul"

# A whole expert matrix is one block, double-buffered, and gate and up
# ride together: 16.8 MB at 2048 x 1024 in bf16, over the 16 MiB a
# Mosaic kernel gets unasked.  So both calls ask for this much, a
# matrix that needs more goes in tiles of its rows (`_k_tile`), and a
# shape whose narrowest tile needs more is refused; every TPU since v4
# has at least 64 MiB of VMEM a core.
_VMEM_LIMIT_BYTES = 48 * 1024 * 1024

# rows a tile: bf16 packs 16 rows a sublane tile; 128 is the MXU's
# side, past which a step's cost grows with rows most of which are
# another expert's.  On the v5e 32 to 128 read within 1% of each other
# at 256 and at 768 rows (PERF.md section 6, PR 32): a step's time is
# its expert's bytes.
_ROW_TILES = (16, 32, 64, 128)


def _row_tile(rows: int) -> int:
    """The smallest row tile that holds half the rows, 128 at most: a
    group is a few rows (rows / experts in the mean) but one expert
    may hold all of them, and every tile is one more step."""
    return next((t for t in _ROW_TILES if 2 * t >= rows), _ROW_TILES[-1])


def _call_vmem_bytes(tm: int, k: int, n: int, matrices: int,
                     itemsize: int, out_itemsize: int,
                     accumulate: bool = False) -> int:
    """What one call keeps in VMEM: two buffers of each block (the
    row tile, the matrices' `k` rows, the output tile), a step's
    float32 intermediates (a product a matrix, and the value stored)
    and, where the matrices come in K tiles, an accumulator each."""
    blocks = (tm * k + matrices * k * n) * itemsize + tm * n * out_itemsize
    return (2 * blocks + (matrices + 1) * tm * n * 4
            + (matrices * tm * n * 4 if accumulate else 0))


# Mosaic's lane grid: a block's last dimension is a multiple of this
_LANES = 128


def _k_tile(tm: int, k: int, n: int, matrices: int, itemsize: int,
            out_itemsize: int) -> Optional[int]:
    """Rows of [k, n] matrices a block holds: all `k` where that fits
    `_VMEM_LIMIT_BYTES`, else the largest k / c (c = 2, 3, ...) that
    is a whole number of lanes (it is the row tile's last dimension)
    and fits; None where not even one lane tile does."""
    for c in range(1, max(k // _LANES, 1) + 1):
        tk = k // c
        if k % c or (c > 1 and tk % _LANES):
            continue
        if _call_vmem_bytes(tm, tk, n, matrices, itemsize, out_itemsize,
                            accumulate=c > 1) <= _VMEM_LIMIT_BYTES:
            return tk
    return None


def _k_tiles(tm: int, d_model: int, d_ff: int, itemsize: int):
    """(gate and up's K tile, down's): gate and up ride together and
    give the weights' dtype, down gives float32."""
    return (_k_tile(tm, d_model, d_ff, 2, itemsize, itemsize),
            _k_tile(tm, d_ff, d_model, 1, itemsize, 4))


def grouped_matmul_supports(*, rows: int, d_model: int, d_ff: int,
                            n_experts: int, dtype, platform: str,
                            interpret: bool = False) -> Optional[str]:
    """None when `select_grouped_matmul` would return the kernel, else
    the short reason it is refused (what `decoder.expert_kernel`
    reports after "xla:").  Off a TPU there is no Mosaic compiler:
    refused unless `interpret` (tests) asks for the Pallas interpreter,
    which takes any float dtype and width."""
    if platform != "tpu" and not interpret:
        return "not_tpu"
    if platform == "tpu":
        if jnp.dtype(dtype) != jnp.bfloat16:
            return "weights_dtype"
        if d_model % 128 or d_ff % 128:
            return "width_misaligned"
    if None in _k_tiles(_row_tile(rows), d_model, d_ff,
                        jnp.dtype(dtype).itemsize):
        return "vmem"
    return None


def _kernel(group_ref, tile_ref, offsets_ref, total_ref, x_ref, *refs,
            tm, gated, k_tiles=1):
    """Work item w (the grid's axis 0): rows tile `tile[w]` times
    expert `group[w]`'s matrix (gate and up, then `silu(gate) * up`,
    when `gated`), stored where the tile's rows are that expert's.
    With `k_tiles` > 1 the grid's axis 1 walks the matrix's K tiles:
    the last refs are a float32 accumulator a matrix, and the step of
    the last tile stores."""
    n_w = 2 if gated else 1
    w_refs, o_ref, acc_refs = refs[:n_w], refs[n_w], refs[n_w + 1:]
    w = pl.program_id(0)
    k = pl.program_id(1) if k_tiles > 1 else None

    @pl.when(w < total_ref[0])
    def _item():
        g = group_ref[w]
        row = tile_ref[w] * tm + jax.lax.broadcasted_iota(
            jnp.int32, (tm, 1), 0)
        mine = (row >= offsets_ref[g]) & (row < offsets_ref[g + 1])
        x = x_ref[...]
        outs = [jnp.dot(x, w_ref[0], preferred_element_type=jnp.float32)
                for w_ref in w_refs]

        def store(outs):
            val = jax.nn.silu(outs[0]) * outs[1] if gated else outs[0]
            o_ref[...] = jnp.where(mine, val.astype(o_ref.dtype),
                                   o_ref[...])

        if k_tiles == 1:
            store(outs)
            return

        @pl.when(k == 0)
        def _first():
            for acc, out in zip(acc_refs, outs):
                acc[...] = out

        @pl.when(k > 0)
        def _further():
            for acc, out in zip(acc_refs, outs):
                acc[...] += out

        @pl.when(k == k_tiles - 1)
        def _last():
            store([acc[...] for acc in acc_refs])


def _k_index(w, j, total, k_tiles: int):
    """The K tile step (w, j) addresses: item w's j-th, and for an
    item past the last the last item's FINAL tile at every j, so that
    a padded item moves no block (left to cycle, each one re-reads an
    expert: a fifth more bytes at 19 items for 15 experts with rows)."""
    return jnp.where(w < total[0], j, k_tiles - 1)


@functools.partial(jax.jit, static_argnames=("tm", "tk", "out_dtype",
                                             "interpret"))
def _call(plan, x, *weights, tm, tk, out_dtype, interpret):
    """x [rows (a multiple of tm), K] times weights[i][group] [K, N]
    for the rows of each group -> [rows, N] in `out_dtype`: one
    matrix, or gate and up and the gated product; a block holds `tk`
    of a matrix's K rows.  Whole matrices (`tk` = K) walk the work
    items on a grid of one axis; K tiles are an inner axis after it."""
    group, tile, offsets, total = plan
    m, k = x.shape
    n = weights[0].shape[-1]
    items = group.shape[0]
    gated = len(weights) == 2
    whole = tk == k

    def block_at(index):
        """An index map over (work item w, K tile j), for whichever
        grid the call has."""
        if whole:
            return lambda w, group, tile, offsets, total: index(
                w, 0, group, tile)
        return lambda w, j, group, tile, offsets, total: index(
            w, _k_index(w, j, total, k // tk), group, tile)

    rows_of = block_at(lambda w, j, group, tile: (tile[w], j))
    out_of = block_at(lambda w, j, group, tile: (tile[w], 0))
    matrix_of = block_at(lambda w, j, group, tile: (group[w], j, 0))

    grid = (items,) if whole else (items, k // tk)
    return pl.pallas_call(
        functools.partial(_kernel, tm=tm, gated=gated, k_tiles=k // tk),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4, grid=grid,
            in_specs=[pl.BlockSpec((tm, tk), rows_of)]
            + [pl.BlockSpec((1, tk, n), matrix_of)] * len(weights),
            out_specs=pl.BlockSpec((tm, n), out_of),
            scratch_shapes=() if whole else [
                pltpu.VMEM((tm, n), jnp.float32)] * len(weights)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",) * len(grid),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="grouped_gate_up" if gated else "grouped_down",
    )(group, tile, offsets, total, x, *weights)


def _plan(sizes, rows: int, tm: int):
    """The work items of group sizes `sizes` [E] over `rows` sorted
    rows in tiles of `tm`: (group [W], tile [W], offsets [E + 1],
    total [1]), int32, W = tiles + E - 1 the static bound on the items
    (a group has an item for every tile it has rows in; groups in
    order, a group's tiles in order).  Items past `total` repeat the
    last one's indices, so they move no block."""
    e_n = sizes.shape[0]
    tiles = -(-rows // tm)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    spans = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    item_ends = jnp.cumsum(spans)
    total = item_ends[-1]
    w = jnp.minimum(jnp.arange(tiles + e_n - 1, dtype=jnp.int32),
                    total - 1)
    group = jnp.searchsorted(item_ends, w, side="right",
                             method="compare_all").astype(jnp.int32)
    tile = first[group] + w - (item_ends - spans)[group]
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    return (group, tile.astype(jnp.int32), offsets.astype(jnp.int32),
            total.reshape(1).astype(jnp.int32))


def select_grouped_matmul(*, rows: int, d_model: int, d_ff: int,
                          n_experts: int, dtype, platform: str,
                          interpret: bool = False
                          ) -> Tuple[Optional[types.SimpleNamespace],
                                     Optional[str]]:
    """-> (kernel, None), or (None, reason) where
    `grouped_matmul_supports` refuses: the caller then keeps its
    `ragged_dot`s.  A function of the shapes, the weights' dtype and
    the platform alone; it touches no array and runs nothing.

    kernel.plan(sizes) -> the work items of int32 group sizes [E]
    (traced: part of the step); kernel.gate_up(x, w_gate, w_up, plan)
    -> `silu(x @ w_gate[e]) * (x @ w_up[e])` [rows, d_ff] in the
    weights' dtype and kernel.down(act, w_down, plan) -> [rows,
    d_model] float32, for x and act [rows, .] SORTED by expert in the
    weights' dtype; kernel.name is what the decoder reports, and
    kernel.k_tiles the rows of a matrix a block of (gate and up,
    down) holds: all of them wherever the whole matrix fits."""
    reason = grouped_matmul_supports(
        rows=rows, d_model=d_model, d_ff=d_ff, n_experts=n_experts,
        dtype=dtype, platform=platform, interpret=interpret)
    if reason is not None:
        return None, reason
    tm = _row_tile(rows)
    pad = -rows % tm
    tk_gate_up, tk_down = _k_tiles(tm, d_model, d_ff,
                                   jnp.dtype(dtype).itemsize)

    def call(x, weights, plan, tk, out_dtype):
        if pad:
            x = jnp.pad(x, ((0, pad), (0, 0)))
        out = _call(plan, x, *weights, tm=tm, tk=tk,
                    out_dtype=jnp.dtype(out_dtype), interpret=interpret)
        return out[:rows] if pad else out

    return types.SimpleNamespace(
        name=NAME, row_tile=tm, k_tiles=(tk_gate_up, tk_down),
        plan=lambda sizes: _plan(sizes, rows, tm),
        gate_up=lambda x, w_gate, w_up, plan: call(
            x, (w_gate, w_up), plan, tk_gate_up, w_gate.dtype),
        down=lambda act, w_down, plan: call(
            act, (w_down,), plan, tk_down, jnp.float32)), None
