"""Flash attention as a Pallas TPU kernel (fwd + bwd, custom_vjp).

The reference composes attention from primitive ops
(/root/reference/python/paddle/v2/fluid/nets.py:162-219
scaled_dot_product_attention: matmul -> softmax -> matmul), which
materializes the [seq_q, seq_k] score matrix in main memory.  On TPU that
matrix is the HBM-bandwidth bottleneck; this kernel keeps score tiles in
VMEM and streams K/V blocks through the MXU with an online softmax, so HBM
traffic is O(seq·d) instead of O(seq²).

Layout: [batch, seq, heads, head_dim] (matches parallel/ring_attention.py).
Internally folded to [batch·heads, seq, head_dim]; grid = (bh, q_blocks,
k_blocks) with the k dimension innermost so the VMEM accumulator scratch
persists across K/V blocks of one query tile.

Backward is the standard flash recomputation: forward saves only the
per-row logsumexp; dq / dk / dv are three more streaming kernels.

Falls back to a plain XLA composition when shapes don't tile (seq not a
multiple of the block) or no TPU is present and interpret mode is off.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_reference"]

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
# forward
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref,
                *, scale, causal, block_q, block_k, nk, pack, d_head):
    """pack >= 2 folds `pack` heads side-by-side in the trailing dim
    (q/k/v tiles [block, pack*d_head]): loads/stores fill the 128-lane
    dim even at d_head 64, and the online softmax runs per packed head
    on its own [block_q, block_k] score tile (block-diagonal — heads
    never mix).  m/l scratch columns are banded per head."""
    i, j = pl.program_id(1), pl.program_id(2)
    cw = 128 // pack  # scratch column band per packed head

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

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
            m_prev = m_ref[:, hs * cw:hs * cw + 1]
            l_prev = l_ref[:, hs * cw:hs * cw + 1]
            m_cur = jnp.max(s, axis=1, keepdims=True)
            m_new = jnp.maximum(m_prev, m_cur)
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.exp(s - m_new)
            l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
            acc_ref[:, sl] = acc_ref[:, sl] * alpha + jax.lax.dot_general(
                p.astype(v.dtype), v[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[:, hs * cw:(hs + 1) * cw] = jnp.broadcast_to(
                m_new, (block_q, cw))
            l_ref[:, hs * cw:(hs + 1) * cw] = jnp.broadcast_to(
                l_new, (block_q, cw))

    if causal:
        # skip K/V blocks strictly above the diagonal of this query tile
        @pl.when(j * block_k <= i * block_q + (block_q - 1))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finish():
        for hs in range(pack):
            sl = slice(hs * d_head, (hs + 1) * d_head)
            l = l_ref[:, hs * cw:hs * cw + 1]
            l_safe = jnp.where(l == 0.0, 1.0, l)
            o_ref[0, :, sl] = (acc_ref[:, sl] / l_safe).astype(o_ref.dtype)
            # (block_q, pack) tile: one lse column per packed head
            lse_ref[0, :, hs:hs + 1] = (m_ref[:, hs * cw:hs * cw + 1]
                                        + jnp.log(l_safe))


def _kv_index_map(causal, block_q, block_k):
    """K/V block index for grid step (b, i, j).

    Causal: clamp j to the diagonal block of query tile i.  Steps above the
    diagonal (compute skipped by pl.when) then repeat the previous block
    index, and the Pallas pipeline skips the HBM->VMEM copy for a repeated
    index — masked K/V tiles cost no bandwidth.
    """
    if not causal:
        return lambda b, i, j: (b, j, 0)
    return lambda b, i, j: (
        b, jnp.minimum(j, (i * block_q + (block_q - 1)) // block_k), 0)


def _fwd_pallas(q, k, v, scale, causal, block_q, block_k, interpret,
                pack=1):
    bh, sq, d = q.shape          # d = pack * d_head (packed layout)
    sk = k.shape[1]
    nq, nk = sq // block_q, sk // block_k
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k, nk=nk,
                             pack=pack, d_head=d // pack)
    kv_map = _kv_index_map(causal, block_q, block_k)
    o, lse = pl.pallas_call(
        kern,
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, pack), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, sq, pack), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, d), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
            pltpu.VMEM((block_q, 128), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v)
    return o, lse


# ---------------------------------------------------------------------------
# backward
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, scale, causal, block_q, block_k, nk, pack,
               d_head):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    def _compute():
        # native-dtype MXU operands, f32 accumulate (see _fwd_kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]        # (block_q, pack)
        delta = delta_ref[0]    # (block_q, pack)
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
            p = jnp.exp(s - lse[:, hs:hs + 1])
            dp = jax.lax.dot_general(
                do[:, sl], v[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            ds = p * (dp - delta[:, hs:hs + 1]) * scale
            dq_acc[:, sl] += jax.lax.dot_general(
                ds.astype(k.dtype), k[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        @pl.when(j * block_k <= i * block_q + (block_q - 1))
        def _():
            _compute()
    else:
        _compute()

    @pl.when(j == nk - 1)
    def _finish():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, scale, causal, block_q, block_k, nq, pack, d_head):
    # grid = (bh, k_blocks, q_blocks): q innermost so dk/dv scratch persists
    i, j = pl.program_id(1), pl.program_id(2)   # i: k block, j: q block

    @pl.when(j == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    def _compute():
        # native-dtype MXU operands, f32 accumulate (see _fwd_kernel)
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0]
        lse = lse_ref[0]        # (pack, block_q) — transposed layout
        delta = delta_ref[0]    # (pack, block_q)
        if causal:
            krows = i * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 0)
            qcols = j * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_k, block_q), 1)
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
            dv_acc[:, sl] += jax.lax.dot_general(
                pt.astype(do.dtype), do[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            dpt = jax.lax.dot_general(
                v[:, sl], do[:, sl], (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32)
            dst = pt * (dpt - delta[hs:hs + 1, :]) * scale
            dk_acc[:, sl] += jax.lax.dot_general(
                dst.astype(q.dtype), q[:, sl], (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)

    if causal:
        # a k block gets gradient only from q blocks at/below its diagonal
        @pl.when(j * block_q + (block_q - 1) >= i * block_k)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(j == nq - 1)
    def _finish():
        dk_ref[0] = dk_acc[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _bwd_pallas(q, k, v, o, lse, do, scale, causal, block_q, block_k,
                interpret, pack=1):
    bh, sq, d = q.shape          # d = pack * d_head
    sk = k.shape[1]
    d_head = d // pack
    nq, nk = sq // block_q, sk // block_k
    # lse arrives as (bh, sq, pack); delta matches (per packed head),
    # plus (bh, pack, sq) transposed copies for the dkv kernel's k-major
    # tiles
    delta = jnp.sum(
        (do.astype(jnp.float32) * o.astype(jnp.float32)).reshape(
            bh, sq, pack, d_head),
        axis=-1)
    lse_t = jnp.transpose(lse, (0, 2, 1))
    delta_t = jnp.transpose(delta, (0, 2, 1))

    kv_map = _kv_index_map(causal, block_q, block_k)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nk=nk,
                          pack=pack, d_head=d_head),
        grid=(bh, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, pack), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, pack), lambda b, i, j: (b, i, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, sq, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
    )(q, k, v, do, lse, delta)

    if causal:
        # q blocks strictly below a k block's diagonal are masked; clamping
        # their index repeats the previous block -> the pipeline skips the
        # copy (mirror of _kv_index_map for the transposed iteration)
        def _clamped(i, j):
            # min() keeps the index in range when sk > sq (the last k
            # blocks' diagonals lie past the final q block); out-of-range
            # block indices are undefined behavior on Mosaic even for
            # compute-masked steps
            return jnp.minimum(jnp.maximum(j, (i * block_k) // block_q),
                               nq - 1)

        def q_map(b, i, j):
            return (b, _clamped(i, j), 0)

        def q_vec_map(b, i, j):
            return (b, 0, _clamped(i, j))
    else:
        def q_map(b, i, j):
            return (b, j, 0)

        def q_vec_map(b, i, j):
            return (b, 0, j)

    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, scale=scale, causal=causal,
                          block_q=block_q, block_k=block_k, nq=nq,
                          pack=pack, d_head=d_head),
        grid=(bh, nk, nq),
        in_specs=[
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, d), q_map),
            pl.BlockSpec((1, pack, block_q), q_vec_map),
            pl.BlockSpec((1, pack, block_q), q_vec_map),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, sk, d), k.dtype),
            jax.ShapeDtypeStruct((bh, sk, d), v.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        interpret=interpret,
    )(q, k, v, do, lse_t, delta_t)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# custom_vjp wrapper over [bh, seq, d]
# ---------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7, 8))
def _flash(q, k, v, scale, causal, block_q, block_k, interpret, pack):
    o, _ = _fwd_pallas(q, k, v, scale, causal, block_q, block_k,
                       interpret, pack)
    return o


def _flash_fwd(q, k, v, scale, causal, block_q, block_k, interpret, pack):
    o, lse = _fwd_pallas(q, k, v, scale, causal, block_q, block_k,
                         interpret, pack)
    return o, (q, k, v, o, lse)


def _flash_bwd(scale, causal, block_q, block_k, interpret, pack, res, do):
    q, k, v, o, lse = res
    return _bwd_pallas(q, k, v, o, lse, do, scale, causal,
                       block_q, block_k, interpret, pack)


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
    b, sq, h, d = q.shape
    sk = k.shape[1]
    sel_q, sel_k = _select_blocks(sq, sk, d)
    block_q = sel_q if block_q is None else block_q
    block_k = sel_k if block_k is None else block_k
    block_q = min(block_q, sq)
    block_k = min(block_k, sk)
    scale_v = float(d ** -0.5 if scale is None else scale)
    interp = bool(interpret)
    if not interp and (platform or jax.default_backend()) != "tpu":
        # Mosaic only lowers on TPU, and emulating the grid loop on CPU/GPU
        # is far slower than one fused XLA attention — fall back unless the
        # caller opted into the pallas interpreter (interpret=True, tests)
        return flash_attention_reference(q, k, v, causal, scale_v)
    if not interp and sk < min_seq_k:
        return flash_attention_reference(q, k, v, causal, scale_v)
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
        return flash_attention_reference(q, k, v, causal, scale_v)
    # head-pair packing: at d_head 64 the [block, d] tiles fill half the
    # 128-lane dim; folding two heads side-by-side ([b*h/2, s, 128])
    # fills the lanes for every load/store while the per-head score
    # tiles stay block-diagonal inside the kernel
    pack = 2 if d == 64 and h % 2 == 0 else 1

    def fold(x, s_len):
        # ADJACENT heads pair up by a pure reshape ((h, d) dims are
        # contiguous), so packing costs exactly the transposes the
        # unpacked path already pays — and the one real transpose now
        # moves a full-128-lane last dim instead of a half-filled one
        x = x.reshape(b, s_len, h // pack, pack * d)
        x = jnp.transpose(x, (0, 2, 1, 3))
        return x.reshape(b * h // pack, s_len, pack * d)

    o = _flash(fold(q, sq), fold(k, sk), fold(v, sk), scale_v,
               bool(causal), block_q, block_k, interp, pack)
    o = jnp.transpose(o.reshape(b, h // pack, sq, pack * d),
                      (0, 2, 1, 3))
    return o.reshape(b, sq, h, d)
