"""Flash attention as a Pallas TPU kernel (fwd + bwd, custom_vjp).

The reference composes attention from primitive ops
(/root/reference/python/paddle/v2/fluid/nets.py:162-219
scaled_dot_product_attention: matmul -> softmax -> matmul), which
materializes the [seq_q, seq_k] score matrix in main memory.  On TPU that
matrix is the HBM-bandwidth bottleneck; this kernel keeps score tiles in
VMEM and streams K/V blocks through the MXU with an online softmax, so HBM
traffic is O(seq·d) instead of O(seq²).

Layout: [batch, seq, heads, head_dim] (matches parallel/ring_attention.py).
The kernels take it as [batch, seq, heads * head_dim], the projections'
own arrangement, and pick a head's (or, at head size 64, a head pair's)
tile out of a row by the BlockSpec's index map (`_tile`), so nothing is
transposed around a call; a head size that does not fill whole lanes
goes through a transposed copy, [batch * heads, seq, head_dim].  grid =
(bh, q_blocks, k_blocks) with the k dimension innermost so the VMEM
accumulator scratch persists across K/V blocks of one query tile.

The forward's online softmax keeps three things a query row: the running
maximum m (a scratch column), the unnormalised output (the accumulator)
and the running denominator l.  Where a head's values leave lanes of the
128 free (head size 64, paired or alone: `_rides`) l has NO scratch and
NO reduction of its own: the `p . V` product's right operand is the
head's V tile with one column of ones in a free lane, so `sum_j p_ij` is
one more column of the product the MXU makes anyway, and l lives in that
column of the head's accumulator, [block_q, 128] float32 a head, rescaled
with the rest.  It sums the p the values are multiplied by (rounded to
their dtype).  A head of 128 fills the lanes: its l is a scratch column
fed by a reduction over the score tile's lanes.

Backward is the standard flash recomputation: forward saves only the
per-row logsumexp ([bh, pack, seq], one lane a query); dq, dk and dv come
from ONE more streaming kernel that computes each score tile once, or
from a dq and a dk/dv kernel where dq's whole-sequence accumulator does
not fit VMEM (`_fused_bwd_fits`).  `flash_attention` under `jax.grad` is
a custom_vjp over the two halves; a caller that keeps the residuals
itself (the Program op, ops/attention.py) calls the halves:
`flash_attention_forward`, `flash_attention_backward`.

The backward's kernels follow the diagonal inside a causal tile by
sub-tiles of `block_q` keys and leave out those above it
(`_causal_keys`).

Falls back to a plain XLA composition when shapes don't tile (seq not a
multiple of the block) or no TPU is present and interpret mode is off.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_forward",
           "flash_attention_backward", "flash_attention_reference",
           "flash_attention_subtiles", "flash_attention_row_reductions",
           "causal_subtiles"]

NEG_INF = -1e30  # finite mask value: keeps exp()/max() NaN-free in-kernel
# measured on v5e at seq 4096, d 128, bf16 (async-chain, distinct inputs):
# 512x1024 blocks run 6.5 ms vs 21.8 ms at 128x128 and 15.1 ms for the XLA
# composition — big K blocks amortize the per-step acc rescale + m/l
# bookkeeping, big Q blocks amortize K/V streaming
DEFAULT_BLOCK_Q = 512
DEFAULT_BLOCK_K = 1024


def _select_blocks(sq: int, sk: int, d: int) -> tuple[int, int]:
    """(block_q, block_k) keyed on the attention shape — the r4 ridge
    work measured the 512x1024 defaults (tuned at seq 4096 / d 128)
    leaving throughput on the table at longer sequences: at seq 8192,
    d 128 the fwd+bwd layer step runs +8% at 1024x2048 (2048x2048 fails
    to compile: the f32 score tile alone is 16 MB of VMEM).  Larger K
    blocks amortize the per-step rescale bookkeeping, and the benefit
    grows with how many K blocks stream past a resident Q tile."""
    if sk >= 8192:
        return 1024, 2048
    return DEFAULT_BLOCK_Q, DEFAULT_BLOCK_K


# ---------------------------------------------------------------------------
# a causal tile against the diagonal
# ---------------------------------------------------------------------------
#
# The mask is the kernels' own `query >= key`, top-left aligned.  A tile
# of block_q queries x block_k keys is placed against it by ONE integer,
# d = its first query - its first key, and divided along its keys into
# sub-tiles of block_q keys where block_k is 2 to 4 times block_q (both
# rows of `_select_blocks`); else the tile is its own one sub-tile (a
# larger ratio too: every case is a body the compiler must hold, its
# score tiles stacked in VMEM beside the others').  A sub-tile holds a
# kept element or none, and a tile's dead sub-tiles are its last.  The
# backward's kernels compute a tile's live sub-tiles only: their time is
# their tiles' area (0.43 ms of a 2.92 ms fused call at 64 x 2048 x 2 x
# 64).  The forward takes a live tile whole: its time is what it does a
# ROW, and the cut gained it nothing (PERF.md section 6, PR 47).  A
# second diagonal (a window) would be more rows of this table.

def _causal_keys(block_q, block_k):
    """[(lo, hi, keys)]: a tile with lo <= d < hi (hi None: no upper
    end) computes its first `keys` keys; one with d under every `lo`
    holds no kept element and computes nothing."""
    ratio = block_k // block_q
    sub = block_q if block_k % block_q == 0 and 1 < ratio <= 4 else block_k
    # sub-tile n holds a kept element from this d on: the tile's last
    # query at the sub-tile's first key
    live_from = [n * sub - (block_q - 1) for n in range(block_k // sub)]
    return [(lo, hi, (n + 1) * sub) for n, (lo, hi) in enumerate(
        zip(live_from, live_from[1:] + [None]))]


def causal_subtiles(sq, sk, block_q, block_k):
    """(forward, backward, live) over one head's grid of a causal call,
    in sub-tiles of block_q queries x block_q keys (x block_k where
    block_q does not divide it): those the forward kernel computes (its
    live tiles whole), those a backward kernel computes (`_causal_keys`)
    and those that hold a kept element, whatever a kernel does.  12, 10,
    10 at 2048 in blocks of 512 x 1024."""
    unit = block_q if block_k % block_q == 0 else block_k
    cases = _causal_keys(block_q, block_k)
    forward = backward = live = 0
    for qi in range(sq // block_q):
        for ki in range(sk // block_k):
            d = qi * block_q - ki * block_k
            live += sum(d + block_q - 1 >= n * unit
                        for n in range(block_k // unit))
            forward += (d >= cases[0][0]) * block_k // unit
            backward += sum(keys // unit for lo, hi, keys in cases
                            if lo <= d and (hi is None or d < hi))
    return forward, backward, live


def _when_causal_keys(qi, ki, block_q, block_k, compute):
    """Run `compute(keys)` for the tile of q block `qi` and k block `ki`
    under `pl.when`, for the case of `_causal_keys` the tile is in."""
    d = qi * block_q - ki * block_k
    for lo, hi, keys in _causal_keys(block_q, block_k):
        pl.when(d >= lo if hi is None else (d >= lo) & (d < hi))(
            functools.partial(compute, keys))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------

def _rides(d_head):
    """Whether the softmax denominator rides the `p . V` product: a
    head's value columns leave lanes of the 128 free (head size 64), so
    a column of ones beside them gives `sum_j p_ij` as one more column
    of the product the kernel makes anyway (the MXU pass costs the same
    at 64 columns and at 128).  A head of 128 fills the lanes: a ones
    column would be a whole pass more, and the sum is a reduction over
    the score tile's lanes."""
    return d_head < 128


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref,
                l_ref=None, *, scale, causal, block_q, block_k, nk, pack,
                d_head):
    """pack >= 2 folds `pack` heads side-by-side in the trailing dim
    (q/k/v tiles [block, pack*d_head]): loads/stores fill the 128-lane
    dim even at d_head 64, and the online softmax runs per packed head
    on its own [block_q, block_k] score tile (block-diagonal — heads
    never mix).

    Where the denominator rides (`_rides`) a head's accumulator is its
    product's full 128 columns, acc_ref [block_q, pack * 128]: the
    head's values in the lanes they have in the tile and the running
    denominator l in the lane of the ones, `stat` below, which
    `acc * alpha` rescales with the rest; there is no l scratch.  Else
    acc_ref is [block_q, pack * d_head] and l_ref holds l.  m (and such
    an l) sit in column `stat` of [block_q, 128]."""
    i, j = pl.program_id(1), pl.program_id(2)
    rides = l_ref is None
    aw = 128 if rides else d_head     # a head's accumulator columns

    def stat(hs):
        # the lane after the head's values: 64 | 0 for a pair of 64
        return (hs + 1) * d_head % 128

    def denominator(hs):
        """Head hs's l, [block_q, 1]: the ONE place it is read."""
        if rides:
            return acc_ref[:, hs * aw + stat(hs):hs * aw + stat(hs) + 1]
        return l_ref[:, stat(hs):stat(hs) + 1]

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        if not rides:
            l_ref[:] = jnp.zeros_like(l_ref)

    def values(v, hs):
        """The product's right operand: head hs's [block_k, d_head]; or,
        riding, [block_k, 128] with the head's values where they lie in
        the tile and ones in lane `stat(hs)`."""
        sl = slice(hs * d_head, (hs + 1) * d_head)
        if not rides:
            return v[:, sl]
        if pack * d_head == 128:
            # the tile fills the lanes: two selects, no lane moves
            lanes = jax.lax.broadcasted_iota(jnp.int32, v.shape, 1)
            mine = (lanes >= sl.start) & (lanes < sl.stop)
            ones = jnp.where(lanes == stat(hs), 1.0, 0.0).astype(v.dtype)
            return jnp.where(mine, v, ones)
        # a head alone: every free lane holds the ones (and so l)
        return jnp.concatenate(
            [v, jnp.ones((v.shape[0], 128 - d_head), v.dtype)], axis=1)

    def _compute():
        # operands stay in their storage dtype: bf16 x bf16 -> f32 rides
        # the MXU's native path (an .astype(f32) here forces the ~8x
        # slower fp32 MXU passes — measured 0.54x vs XLA before, 1.8x+
        # after); accumulation is f32 via preferred_element_type
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            keep = rows >= cols
        for hs in range(pack):
            sl = slice(hs * d_head, (hs + 1) * d_head)
            s = jax.lax.dot_general(
                q[:, sl], k[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                s = jnp.where(keep, s, NEG_INF)
            m_prev = m_ref[:, stat(hs):stat(hs) + 1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            # riding, the denominator sums the p that the values are
            # multiplied by, rounded to their dtype: a row's weights sum
            # to 1 over what is multiplied
            cols_h = slice(hs * aw, (hs + 1) * aw)
            acc_ref[:, cols_h] = acc_ref[:, cols_h] * alpha + (
                jax.lax.dot_general(
                    p.astype(v.dtype), values(v, hs),
                    (((1,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32))
            # the statistics' one column alone is ever read (here and
            # in `_finish`).  Heads that share the lanes store that
            # column: a store across half the lanes was 0.38 ms of a
            # 2.19 ms call at 64 x 2048 x 2 x 64.  A head alone fills
            # them with one unmasked store, which read 2% faster than
            # its column at 16 x 8192 x 128 (PERF.md section 6, PR 47)
            band = (slice(0, 128) if pack == 1
                    else slice(stat(hs), stat(hs) + 1))
            width = band.stop - band.start
            m_ref[:, band] = jnp.broadcast_to(m_new, (block_q, width))
            if not rides:
                l_new = alpha * denominator(hs) + jnp.sum(
                    p, axis=1, keepdims=True)
                l_ref[:, band] = jnp.broadcast_to(l_new, (block_q, width))

    if causal:
        # skip K/V blocks strictly above the diagonal of this query tile
        @pl.when(j * block_k <= i * block_q + (block_q - 1))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finish():
        # column `stat` of a head is its l; the other lanes read 1
        lanes = jax.lax.broadcasted_iota(jnp.int32, (block_q, 128), 1)
        l_all = jnp.ones((block_q, 128), jnp.float32)
        for hs in range(pack):
            l = denominator(hs)
            l = jnp.where(l == 0.0, 1.0, l)
            sl = slice(hs * d_head, (hs + 1) * d_head)
            # riding, the head's values have the tile's lanes
            at = hs * aw + (sl.start if rides else 0)
            o_ref[0, :, sl] = (acc_ref[:, at:at + d_head] / l
                               ).astype(o_ref.dtype)
            l_all = jnp.where(lanes == stat(hs), l, l_all)
        # the statistics sit one row a query (block_q sublanes); they are
        # SAVED one lane a query, [pack, block_q]: a [.., seq, pack] array
        # pads its 2 lanes to 128 in HBM (67 MB a layer at 64 x 2048 x 2)
        lse_t = (m_ref[:] + jnp.log(l_all)).T      # (128, block_q)
        for hs in range(pack):
            lse_ref[0, hs:hs + 1, :] = lse_t[stat(hs):stat(hs) + 1, :]


def _tile(block, d, groups, seq_block):
    """BlockSpec of one folded head's (block, d) tile in a
    [bh / groups, seq, groups * d] array: grid index b is batch element
    b // groups and, inside its rows, the folded head b % groups.
    `seq_block(i, j)` gives the tile's place along the sequence.  With
    groups = heads / pack the array is the projection's own
    [batch, seq, heads * d_head] and nothing is transposed around the
    call; with groups = 1 it is the folded [batch * heads / pack, seq,
    d]."""
    return pl.BlockSpec((1, block, d), lambda b, i, j: (
        b // groups, seq_block(i, j), b % groups))


def _kv_block(causal, block_q, block_k):
    """K/V block of grid step (i, j) = (q block, k block).

    Causal: clamp j to the diagonal block of query tile i.  Steps above the
    diagonal (compute skipped by pl.when) then repeat the previous block
    index, and the Pallas pipeline skips the HBM->VMEM copy for a repeated
    index — masked K/V tiles cost no bandwidth.
    """
    if not causal:
        return lambda i, j: j
    return lambda i, j: jnp.minimum(
        j, (i * block_q + (block_q - 1)) // block_k)


def _fwd_pallas(q, k, v, scale, causal, block_q, block_k, interpret,
                pack=1, groups=1):
    """q, k, v [bh / groups, seq, groups * d] (`_tile`); (out like q,
    lse [bh, pack, sq])."""
    nb, sq, gd = q.shape
    sk = k.shape[1]
    bh, d = nb * groups, gd // groups    # d = pack * d_head
    nq, nk = sq // block_q, sk // block_k
    d_head = d // pack
    rides = _rides(d_head)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k, nk=nk,
                             pack=pack, d_head=d_head)
    q_spec = _tile(block_q, d, groups, lambda i, j: i)
    kv_spec = _tile(block_k, d, groups, _kv_block(causal, block_q, block_k))
    return pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[
            q_spec,
            pl.BlockSpec((1, pack, block_q), lambda b, i, j: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, pack, sq), jnp.float32),
        ],
        # the accumulator, m and, where it does not ride in the
        # accumulator (`_rides`), l
        scratch_shapes=[
            pltpu.VMEM((block_q, pack * 128 if rides else d), jnp.float32),
        ] + [pltpu.VMEM((block_q, 128), jnp.float32)] * (1 if rides else 2),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v)


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------
#
# The row statistics reach every backward kernel one lane a query:
# lse and delta are [bh, pack, seq] float32 and a block of them is
# (pack, block_q).  The two kernels whose score tile is k-major (rows =
# k positions, lanes = q positions) subtract such a row as it lies; the
# q-major dq kernel turns its block into columns itself.

def _stat_columns(lse_ref, delta_ref, pack, block_q):
    """The (pack, block_q) blocks of lse and delta as ONE (block_q, 128)
    tile whose column hs is lse's and pack + hs delta's: one transpose a
    grid step where two padded [.., seq, pack] copies crossed HBM."""
    rows = jnp.concatenate(
        [lse_ref[0], delta_ref[0],
         jnp.zeros((128 - 2 * pack, block_q), jnp.float32)], axis=0)
    return rows.T


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale, causal, block_q, block_k, nk, pack,
               d_head):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute(keys):
        # native-dtype MXU operands, f32 accumulate (see _fwd_kernel)
        q = q_ref[0]
        k = k_ref[0, :keys]
        v = v_ref[0, :keys]
        do = do_ref[0]
        stats = _stat_columns(lse_ref, delta_ref, pack, block_q)
        if causal:
            rows = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, keys), 0)
            cols = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, keys), 1)
            keep = rows >= cols
        for hs in range(pack):
            sl = slice(hs * d_head, (hs + 1) * d_head)
            s = jax.lax.dot_general(
                q[:, sl], k[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                s = jnp.where(keep, s, NEG_INF)
            p = jnp.exp(s - stats[:, hs:hs + 1])
            dp = jax.lax.dot_general(
                do[:, sl], v[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - stats[:, pack + hs:pack + hs + 1]) * scale
            dq_acc[:, sl] += jax.lax.dot_general(
                ds.astype(k.dtype), k[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        _when_causal_keys(i, j, block_q, block_k, _compute)
    else:
        _compute(block_k)

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, *rest,
                scale, causal, block_q, block_k, nq, nk, pack, d_head,
                fused):
    """dk and dv of one K block, accumulated over its Q blocks in
    scratch.  `fused`: dq as well, from the same score tile: s, p, dp
    and ds are computed ONCE a tile (five products and one exp where
    the dq kernel and this one spend seven and two).  dq then
    accumulates in a float32 scratch that holds the head pair's WHOLE
    sequence, under an output block that stays resident for the head
    pair and is written once."""
    if fused:
        dq_ref, dk_acc, dv_acc, dq_acc = rest
    else:
        dk_acc, dv_acc = rest
    # grid = (bh, k_blocks, q_blocks): q innermost so dk/dv scratch persists
    i, j = pl.program_id(1), pl.program_id(2)   # i: k block, j: q block
    q_rows = pl.ds(pl.multiple_of(j * block_q, block_q), block_q)

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    if fused:
        @pl.when(i == 0)
        def _init_dq():
            dq_acc[q_rows, :] = jnp.zeros((block_q, pack * d_head),
                                          jnp.float32)

    def _compute(keys):
        # native-dtype MXU operands, f32 accumulate (see _fwd_kernel)
        q = q_ref[0]
        k = k_ref[0, :keys]
        v = v_ref[0, :keys]
        do = do_ref[0]
        lse = lse_ref[0]        # (pack, block_q): a row a packed head
        delta = delta_ref[0]    # (pack, block_q)
        if causal:
            krows = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (keys, block_q), 0)
            qcols = j * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (keys, block_q), 1)
            keep = qcols >= krows
        for hs in range(pack):
            sl = slice(hs * d_head, (hs + 1) * d_head)
            # transposed tile: rows = k positions, cols = q positions
            st = jax.lax.dot_general(
                k[:, sl], q[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            if causal:
                st = jnp.where(keep, st, NEG_INF)
            pt = jnp.exp(st - lse[hs:hs + 1, :])
            dv_acc[:keys, sl] += jax.lax.dot_general(
                pt.astype(do.dtype), do[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(
                v[:, sl], do[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dst = (pt * (dpt - delta[hs:hs + 1, :]) * scale).astype(q.dtype)
            dk_acc[:keys, sl] += jax.lax.dot_general(
                dst, q[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            if fused:
                # ds^T k: the tile's rows (k positions) are contracted
                dq_acc[q_rows, sl] += jax.lax.dot_general(
                    dst, k[:, sl], (((0,), (0,)), ((), ())),
                    preferred_element_type=jnp.float32)

    if causal:
        # a k block gets gradient only from q blocks at/below its diagonal,
        # and from a q block on it only its keys up to that block's last
        # query
        _when_causal_keys(j, i, block_q, block_k, _compute)
    else:
        _compute(block_k)

    @pl.when(j == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)

    if fused:
        @pl.when((i == nk - 1) & (j == nq - 1))
        def _finish_dq():
            dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


# The fused backward keeps, for one head pair, a float32 dq of the whole
# sequence and its resident output block (two buffers) in VMEM beside the
# tiles: sq x d x (4 + 2 x itemsize) bytes, 2 MiB at seq 2048 and 8 MiB at
# 8192 (bf16, packed width 128).  Past this budget (seq 16384 and up) the
# dq kernel and the dk/dv kernel stay two.
FUSED_BWD_DQ_VMEM_BUDGET = 8 * 1024 * 1024
# what a call may take in all, of the v5e's 128 MiB: at blocks of 1024 x
# 2048 (seq 8192 and up) the compiler asks for more than the 16 MiB a
# call gets unasked (the needs: tests/test_kernels_lower_tpu.py)
VMEM_LIMIT = 32 * 1024 * 1024


def _fused_bwd_fits(sq: int, d: int, itemsize: int) -> bool:
    return sq * d * (4 + 2 * itemsize) <= FUSED_BWD_DQ_VMEM_BUDGET


def _bwd_pallas(q, k, v, lse, delta, do, scale, causal, block_q, block_k,
                interpret, pack=1, groups=1):
    """(dq, dk, dv) like q, k, v: [bh / groups, seq, groups * d]
    (`_tile`).  lse is the forward's [bh, pack, sq]; delta = rowsum(do *
    o) a head in the same layout."""
    nb, sq, gd = q.shape
    sk = k.shape[1]
    bh, d = nb * groups, gd // groups    # d = pack * d_head
    d_head = d // pack
    nq, nk = sq // block_q, sk // block_k
    fused = _fused_bwd_fits(sq, d, q.dtype.itemsize)

    if causal:
        # q blocks strictly below a k block's diagonal are masked; clamping
        # their index repeats the previous block -> the pipeline skips the
        # copy (mirror of _kv_block for the transposed iteration).  min()
        # keeps the index in range when sk > sq (the last k blocks'
        # diagonals lie past the final q block); out-of-range block
        # indices are undefined behavior on Mosaic even for compute-masked
        # steps
        def q_block(i, j):
            return jnp.minimum(jnp.maximum(j, (i * block_k) // block_q),
                               nq - 1)
    else:
        def q_block(i, j):
            return j

    # grid (bh, k block i, q block j)
    q_spec = _tile(block_q, d, groups, q_block)
    kv_spec = _tile(block_k, d, groups, lambda i, j: i)
    stat_spec = pl.BlockSpec((1, pack, block_q),
                             lambda b, i, j: (b, 0, q_block(i, j)))
    kv_shape = jax.ShapeDtypeStruct(k.shape, k.dtype)
    res = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq, nk=nk,
                          pack=pack, d_head=d_head, fused=fused),
        grid=(bh, nk, nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=[kv_spec, kv_spec] + (
            [_tile(sq, d, groups, lambda i, j: 0)] if fused else []),
        out_shape=[kv_shape, kv_shape] + (
            [jax.ShapeDtypeStruct(q.shape, q.dtype)] if fused else []),
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)] + (
            [pltpu.VMEM((sq, d), jnp.float32)] if fused else []),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    if fused:
        dk, dv, dq = res
        return dq, dk, dv
    dk, dv = res

    # grid (bh, q block i, k block j)
    q_spec = _tile(block_q, d, groups, lambda i, j: i)
    kv_spec = _tile(block_k, d, groups, _kv_block(causal, block_q, block_k))
    stat_spec = pl.BlockSpec((1, pack, block_q), lambda b, i, j: (b, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          pack=pack, d_head=d_head),
        grid=(bh, nq, nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, stat_spec, stat_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper over the kernels' layout (`_tile`)
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8, 9))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret, pack,
           groups):
    """(o, lse): the row statistics are a result, so that a caller which
    keeps them (the Program op) can run the backward itself; their own
    cotangent is never used."""
    return _fwd_pallas(q, k, v, scale, causal, block_q, block_k,
                       interpret, pack, groups)


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, pack,
               groups):
    o, lse = _fwd_pallas(q, k, v, scale, causal, block_q, block_k,
                         interpret, pack, groups)
    return (o, lse), (q, k, v, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, pack, groups,
               res, cts):
    q, k, v, o, lse = res
    return _backward(q, k, v, o, lse, cts[0], scale, causal, block_q,
                     block_k, interpret, pack, groups)


def _backward(q, k, v, o, lse, do, scale, causal, block_q, block_k,
              interpret, pack, groups):
    """(dq, dk, dv) from the forward's `o` and `lse` and o's cotangent,
    all but lse in the kernels' layout."""
    nb, sq, gd = o.shape
    # `o` waits for `do`: what the backward makes of `o` alone (a float32
    # or a transposed copy) XLA otherwise schedules into the FORWARD pass
    # and keeps alive a layer to the backward (33 to 67 MB a layer at
    # 64 x 2048 x 128)
    o, do = jax.lax.optimization_barrier((o, do))
    delta = jnp.sum((do.astype(jnp.float32) * o.astype(jnp.float32)
                     ).reshape(nb, sq, groups, pack, gd // groups // pack),
                    axis=-1)
    delta = jnp.transpose(delta, (0, 2, 3, 1)).reshape(lse.shape)
    return _bwd_pallas(q, k, v, lse, delta, do.astype(q.dtype), scale,
                       causal, block_q, block_k, interpret, pack, groups)


_flash.defvjp(_flash_fwd, _flash_bwd)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def flash_attention_reference(q, k, v, causal=False, scale=None):
    """XLA-composed fallback / test oracle; layout [b, s, h, d]."""
    scale = q.shape[-1] ** -0.5 if scale is None else scale
    s = jnp.einsum("bqhd,bkhd->bhqk", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * scale
    if causal:
        ql, kl = q.shape[1], k.shape[1]
        mask = jnp.arange(ql)[:, None] >= jnp.arange(kl)[None, :]
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p,
                      v.astype(jnp.float32)).astype(q.dtype)


# below this K/V length the materialized-scores XLA composition measured
# faster than the Pallas kernel on v5e (the S^2 matrix still fits cache-
# friendly tiles and XLA's single fusion beats the grid-loop overhead);
# above it the kernel wins and keeps winning as S^2 grows (1.5-2.3x at
# 4k-8k, and 32k+ only runs at all on the kernel: benchmark/README.md)
MIN_PALLAS_SEQ_K = 2048


def _largest_tile(seq, block, align=128):
    """Largest multiple of `align` that divides `seq`, capped at `block`;
    0 when none exists (seq not `align`-aligned)."""
    for m in range(min(block, seq) // align, 0, -1):
        if seq % (m * align) == 0:
            return m * align
    return 0


class _Plan(NamedTuple):
    """How the kernel runs one attention shape: the static arguments of
    `_flash` after q, k, v."""
    scale: float
    causal: bool
    block_q: int
    block_k: int
    interpret: bool
    pack: int
    groups: int     # folded heads a row of the kernels' arrays (`_tile`)


def _plan(q, k, v, causal, scale, block_q, block_k, interpret, min_seq_k,
          platform) -> Optional[_Plan]:
    """The ONE decision on blocks, head packing and fallback, for the
    forward, the backward and the function under `jax.grad` alike; None
    where the XLA composition runs instead: the computation will not run
    on a TPU (unless `interpret=True` asks for the pallas interpreter,
    e.g. tests), the sequence doesn't tile onto MXU-aligned blocks, or
    the K/V length is below `min_seq_k`."""
    b, sq, h, d = q.shape
    sk = k.shape[1]
    sel_q, sel_k = _select_blocks(sq, sk, d)
    block_q = sel_q if block_q is None else block_q
    block_k = sel_k if block_k is None else block_k
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    interp = bool(interpret)
    if not interp and (platform or jax.default_backend()) != "tpu":
        # Mosaic only lowers on TPU, and emulating the grid loop on CPU/GPU
        # is far slower than one fused XLA attention — fall back unless the
        # caller opted into the pallas interpreter (interpret=True, tests)
        return None
    if not interp and sk < min_seq_k:
        return None
    if not interp and (sq % block_q or sk % block_k):
        # seqs that are MXU-aligned but not multiples of the large
        # default blocks (e.g. sk=2560 vs block_k=1024) must shrink to
        # the largest 128-multiple divisor, not fall back to the
        # score-materializing composition — above the crossover that
        # fallback is exactly what the kernel exists to avoid
        block_q = _largest_tile(sq, block_q) or block_q
        block_k = _largest_tile(sk, block_k) or block_k
    tiles_ok = sq % block_q == 0 and sk % block_k == 0
    if not interp:
        # Mosaic lowering wants MXU-aligned tiles; route small/ragged
        # shapes to the XLA composition instead of failing at jit time
        tiles_ok = (tiles_ok and block_q % 128 == 0 and block_k % 128 == 0
                    and d % 8 == 0)
    if (not tiles_ok
            or k.shape != (b, sk, h, d) or v.shape != (b, sk, h, d)):
        return None
    # head-pair packing: at d_head 64 the [block, d] tiles fill half the
    # 128-lane dim; folding two heads side-by-side ([b*h/2, s, 128])
    # fills the lanes for every load/store while the per-head score
    # tiles stay block-diagonal inside the kernel
    pack = 2 if d == 64 and h % 2 == 0 else 1
    # a folded head's tile taken straight from [batch, seq, heads * d]
    # where it fills whole lanes; else from a transposed copy (`_to_kernel`)
    groups = h // pack if (pack * d) % 128 == 0 else 1
    return _Plan(float(d ** -0.5 if scale is None else scale), bool(causal),
                 block_q, block_k, interp, pack, groups)


def _to_kernel(x, plan):
    """[b, s, h, d] -> the kernels' [b * h / pack / groups, s, groups *
    pack * d] (`_tile`): a reshape where a folded head fills whole lanes
    (groups = h / pack), else the fold: ADJACENT heads pair up by a pure
    reshape ((h, d) dims are contiguous) and one transpose brings the
    folded heads before the sequence."""
    b, s, h, d = x.shape
    if plan.groups * plan.pack == h:
        return x.reshape(b, s, h * d)
    x = x.reshape(b, s, h // plan.pack, plan.pack * d)
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(
        b * h // plan.pack, s, plan.pack * d)


def _from_kernel(x, shape, plan):
    """`_to_kernel`'s inverse, to `shape` = [b, s, h, d]."""
    b, s, h, d = shape
    if plan.groups * plan.pack == h:
        return x.reshape(shape)
    x = x.reshape(b, h // plan.pack, s, plan.pack * d)
    return jnp.transpose(x, (0, 2, 1, 3)).reshape(shape)


def flash_attention_forward(q, k, v, causal=False, scale=None,
                            block_q=None, block_k=None, interpret=None,
                            min_seq_k=MIN_PALLAS_SEQ_K, platform=None):
    """The kernel's forward half for a caller that keeps the residuals
    itself: (out [b, sq, h, d], lse), or None where `flash_attention`
    would run the XLA composition.  lse is float32
    [b*h/pack, pack, sq], one LANE a query (pack: heads folded side by
    side, 2 at head size 64): what `flash_attention_backward` takes.
    Differentiable like `flash_attention` (its custom_vjp)."""
    plan = _plan(q, k, v, causal, scale, block_q, block_k, interpret,
                 min_seq_k, platform)
    if plan is None:
        return None
    o, lse = _flash(*(_to_kernel(x, plan) for x in (q, k, v)), *plan)
    return _from_kernel(o, q.shape, plan), lse


def flash_attention_subtiles(q, k, v, causal=False, scale=None,
                             block_q=None, block_k=None, interpret=None,
                             min_seq_k=MIN_PALLAS_SEQ_K, platform=None):
    """`causal_subtiles` of a head's grid in the kernel calls
    `flash_attention` makes of the same arguments (arrays or their
    shapes); None where they are not causal or are not made."""
    plan = _plan(q, k, v, causal, scale, block_q, block_k, interpret,
                 min_seq_k, platform)
    if plan is None or not plan.causal:
        return None
    return causal_subtiles(q.shape[1], k.shape[1], plan.block_q,
                           plan.block_k)


def flash_attention_row_reductions(q, k, v, causal=False, scale=None,
                                   block_q=None, block_k=None,
                                   interpret=None,
                                   min_seq_k=MIN_PALLAS_SEQ_K,
                                   platform=None):
    """Reductions over a score tile's lanes that the forward kernel
    makes a query row a key block, in the call `flash_attention` makes
    of the same arguments (arrays or their shapes): 2, the row maximum
    and the row sum; 1 where the sum rides the `p . V` product
    (`_rides`); None where the kernel is not what runs."""
    plan = _plan(q, k, v, causal, scale, block_q, block_k, interpret,
                 min_seq_k, platform)
    if plan is None:
        return None
    return 1 if _rides(q.shape[-1]) else 2


def flash_attention_backward(q, k, v, out, lse, d_out, causal=False,
                             scale=None, block_q=None, block_k=None,
                             interpret=None, min_seq_k=MIN_PALLAS_SEQ_K,
                             platform=None):
    """(dq, dk, dv) from what `flash_attention_forward` gave for the
    same arguments: no score tile of the forward is computed again.
    None where the forward gave None."""
    plan = _plan(q, k, v, causal, scale, block_q, block_k, interpret,
                 min_seq_k, platform)
    if plan is None:
        return None
    grads = _backward(*(_to_kernel(x, plan) for x in (q, k, v, out)), lse,
                      _to_kernel(d_out, plan), *plan)
    return tuple(_from_kernel(g, x.shape, plan)
                 for g, x in zip(grads, (q, k, v)))


def flash_attention(q, k, v, causal=False, scale=None,
                    block_q=None, block_k=None,
                    interpret=None, min_seq_k=MIN_PALLAS_SEQ_K,
                    platform=None):
    """Flash attention over [batch, seq, heads, head_dim] tensors.

    Streams K/V through VMEM with online softmax (fwd) and recomputation
    (bwd).  Falls back to the XLA composition when the computation will
    not run on a TPU (unless `interpret=True` asks for the pallas
    interpreter, e.g. tests), when the sequence doesn't tile onto
    MXU-aligned blocks, or when the K/V length is below `min_seq_k`
    (where the XLA composition measures faster; pass min_seq_k=0 to
    force the kernel).  Block sizes default to the shape-keyed measured
    table (`_select_blocks`); explicit block_q/block_k override it.
    `platform` names the backend the traced computation targets (the
    op lowering passes its executor's; None = the process default).
    """
    res = flash_attention_forward(q, k, v, causal, scale, block_q, block_k,
                                  interpret, min_seq_k, platform)
    if res is None:
        return flash_attention_reference(q, k, v, causal, scale)
    return res[0]
