"""One position of a gated delta rule as a Pallas TPU kernel: the
recurrence of `lm_block.delta_rule_step` with a head's matrix state
brought into VMEM once and written back once, in place on the lanes'
pool.

A lane keeps, a delta-rule layer, a float32 matrix [keys, values] a
head: 64 heads of 128 x 128 are 4.19 MB, 64 lanes 268 MB.  The rule
needs `seen = k^T S'` (a reduction over all of a head's decayed state)
before it can form `S' + beta k (v - seen)^T`, and a compiler's fusion
cannot keep the tile it reduced for the update that depends on it: XLA
reads S for `seen` in one fusion and reads it again, writes the new
state and reduces `o` in a second (PERF.md section 6, PR 60).  A head's
matrix is 64 KB and sits in VMEM whole, so here each (lane, block of
heads) crosses HBM once in and once out.

The grid is (lanes, heads / block): a step's state block is [block,
keys, values] of one lane, fetched and written back by the pipeline's
double buffers, and the output state ALIASES the input state
(`input_output_aliases`): the pool's array is updated in place, as the
served step (which donates its pools) needs, with no copy of the pool.
A head's tile has its keys on sublanes and its values on lanes.  What
the rule takes a VALUE (v, beta repeated over the lanes of a row) is a
row of the block as it comes; what it takes a KEY (the decay e^g, k, q)
must stand along sublanes, so a grid step lays the block's rows of
those three under each other in a VMEM scratch and transposes the
scratch once (the transpose unit is otherwise idle): a head's three
columns are then lane slices of the result.  Nothing is transposed,
copied or padded outside the kernel but beta's repeat (2 MB at the
cell's shape).  Inside, a head is

    S' = e^g[:, None] * S;  seen = sum_keys(k[:, None] * S')
    new = S' + k[:, None] * (beta * (v - seen))[None, :]
    o = sum_keys(q[:, None] * new)

all float32 on the vector unit, in `delta_rule_step`'s order: no
product goes through the MXU and nothing is rounded to fewer bits.
`fresh` and `live` ride the scalar-prefetch lane, a word a lane: a
fresh lane's tile is never read as anything but zeros (a branch, not a
`where` over the tile), a lane that is not live has its block handed
through bit for bit.

`select_delta_rule` is the one entry point: from the shapes and the
platform it returns the kernel, or None and the reason the `jax.numpy`
lines run instead.
"""
from __future__ import annotations

import functools
import types
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["NAME", "select_delta_rule", "delta_rule_supports"]

NAME = "pallas_delta_rule"

# what the call asks of VMEM (a Mosaic kernel gets 16 MiB unasked); the
# flash kernels ask the same
_VMEM_LIMIT_BYTES = 32 * 1024 * 1024
# and what a step's blocks may take of it: the rest is a head's
# intermediates (a few tiles of 64 KB) and the compiler's own
_VMEM_BLOCK_BUDGET = 12 * 1024 * 1024
_LANES = 128


def _column_rows(block: int) -> int:
    """Rows of the scratch that holds e^g, k and q of a block's heads
    under each other: whole lane tiles, since they become the lanes of
    its transpose."""
    return -(-3 * block // _LANES) * _LANES


def _vmem_bytes(block: int, d_head: int) -> int:
    """What a step's blocks keep in VMEM: two buffers each of the state
    in and the state out and of the six rows (g, k, q, v, beta, o), and
    the two scratches of the transpose."""
    state = block * d_head * d_head * 4
    rows = -(-block // 8) * 8 * d_head * 4
    return 2 * (2 * state + 6 * rows) + 2 * _column_rows(block) * d_head * 4


def _heads_block(heads: int, d_head: int) -> Optional[int]:
    """Heads a step: the most that divide `heads`, are a whole number of
    sublane tiles (a block's rows of v, beta and o) or all of them, and
    fit `_VMEM_BLOCK_BUDGET` under double buffering; None where not
    even the fewest do."""
    for block in range(heads, 0, -1):
        if heads % block or (block % 8 and block != heads):
            continue
        if _vmem_bytes(block, d_head) <= _VMEM_BLOCK_BUDGET:
            return block
    return None


def delta_rule_supports(*, lanes: int, heads: int, d_head: int,
                        platform: str, interpret: bool = False
                        ) -> Optional[str]:
    """None when `select_delta_rule` would return the kernel, else the
    short reason it is refused (what `decoder.delta_kernel` reports
    after "xla:").  Off a TPU there is no Mosaic compiler: refused
    unless `interpret` (tests) asks for the Pallas interpreter."""
    if platform != "tpu" and not interpret:
        return "not_tpu"
    if d_head % _LANES:
        return "lane_misaligned"
    if _heads_block(heads, d_head) is None:
        return "vmem"
    return None


def _head(s, e, k, q, v, beta):
    """One head's rule: s [keys, values] (None: zeros), e, k, q [keys,
    1], v and beta [1, values] -> (new [keys, values], o [1, values])."""
    if s is None:
        delta = beta * v
        new = k * delta
    else:
        decayed = e * s
        seen = jnp.sum(k * decayed, axis=0, keepdims=True)
        delta = beta * (v - seen)
        new = decayed + k * delta
    return new, jnp.sum(q * new, axis=0, keepdims=True)


def _kernel(fresh_ref, live_ref, g_ref, k_ref, q_ref, v_ref, beta_ref,
            s_ref, s_out_ref, o_ref, rows_ref, columns_ref, *, block):
    """Grid step (lane, block of heads): the rule over the block's
    heads, each head's tile read once and stored once."""
    lane = pl.program_id(0)
    fresh = fresh_ref[lane] != 0
    # e^g, k and q of the block's heads as columns: head h's are lanes
    # h, block + h and 2 block + h (the scratch's other rows hold
    # whatever they held, in columns nothing reads)
    for i, rows in enumerate((jnp.exp(g_ref[0]), k_ref[0], q_ref[0])):
        rows_ref[i * block:(i + 1) * block] = rows
    columns_ref[...] = rows_ref[...].T

    def rule(zeros):
        for h in range(block):
            new, o = _head(
                None if zeros else s_ref[0, h],
                *(columns_ref[:, i * block + h:i * block + h + 1]
                  for i in range(3)),
                *(ref[0, h:h + 1, :] for ref in (v_ref, beta_ref)))
            o_ref[0, h:h + 1, :] = o
            s_out_ref[0, h] = new

    pl.when(fresh)(lambda: rule(True))
    pl.when(jnp.logical_not(fresh))(lambda: rule(False))

    # a lane that is not live gives its o as the `jax.numpy` lines do
    # and keeps its block: the rule's stores above are overwritten in
    # VMEM before the block goes back
    @pl.when(live_ref[lane] == 0)
    def _keep():
        s_out_ref[...] = s_ref[...]


def _state_block(lane, blk, fresh, live):
    """The state block grid step (lane, blk) reads and writes."""
    return lane, blk, 0, 0


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def _call(fresh, live, g, k, q, v, beta, state, *, block, interpret):
    """fresh, live int32 [S]; g, k, q, v, beta float32 [S, H, K]; state
    float32 [S, H, K, K] -> (the new state, ALIASING `state`; o [S, H,
    K])."""
    s_n, h_n, k_n, _ = state.shape
    row = pl.BlockSpec((1, block, k_n),
                       lambda lane, blk, fresh, live: (lane, blk, 0))
    tile = pl.BlockSpec((1, block, k_n, k_n), _state_block)
    return pl.pallas_call(
        functools.partial(_kernel, block=block),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(s_n, h_n // block),
            in_specs=[row] * 5 + [tile], out_specs=[tile, row],
            scratch_shapes=[
                pltpu.VMEM((_column_rows(block), k_n), jnp.float32),
                pltpu.VMEM((k_n, _column_rows(block)), jnp.float32)]),
        out_shape=[jax.ShapeDtypeStruct(state.shape, jnp.float32),
                   jax.ShapeDtypeStruct((s_n, h_n, k_n), jnp.float32)],
        # operand 7 (after the two prefetched words and the five rows)
        # is the state: the pool's array, updated in place
        input_output_aliases={7: 0},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES),
        interpret=interpret,
        name="delta_rule",
    )(fresh, live, g, k, q, v, beta, state)


def select_delta_rule(*, lanes: int, heads: int, d_head: int,
                      platform: str, interpret: bool = False
                      ) -> Tuple[Optional[types.SimpleNamespace],
                                 Optional[str]]:
    """-> (kernel, None), or (None, reason) where `delta_rule_supports`
    refuses: the caller then keeps its `jax.numpy` lines.  A function
    of the shapes and the platform alone; it touches no array and runs
    nothing.

    kernel.rule(state, q, k, v, g, beta, fresh, live) -> (state, o):
    `lm_block.delta_rule_step`'s recurrence over state [lanes, heads,
    d_head keys, d_head values], q, k, v and the log decay g [lanes,
    heads, d_head], beta [lanes, heads] (all float32) and fresh, live
    bool [lanes]; the state returned is the state given, updated in
    place where the caller donates it.  kernel.name is what the decoder
    reports, kernel.heads_block the heads a grid step takes and
    kernel.grid its steps."""
    reason = delta_rule_supports(lanes=lanes, heads=heads, d_head=d_head,
                                 platform=platform, interpret=interpret)
    if reason is not None:
        return None, reason
    block = _heads_block(heads, d_head)

    def rule(state, q, k, v, g, beta, fresh, live):
        return _call(
            fresh.astype(jnp.int32), live.astype(jnp.int32), g, k, q, v,
            jnp.broadcast_to(beta[..., None], v.shape), state,
            block=block, interpret=interpret)

    return types.SimpleNamespace(
        name=NAME, heads_block=block, grid=(lanes, heads // block),
        rule=rule), None
