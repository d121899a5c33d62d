"""An expert layer's CHOICE as one Pallas TPU call: the k largest of a
router's scores a token, largest first and a tie to the lower expert,
with the group limit before it where the router has one, and no sort.

`lm_block.route` chooses with `lm_block._largest`: k passes of a
maximum over the row, which XLA compiles to two fusions a pass (and the
`jax.lax.top_k` they replaced to a FULL sort of the row, three a layer
under a group limit by the two best of a group: PERF.md section 6,
PR 63).  A tick's scores are small, [128 tokens, 512 experts] float32
is 256 KB, so here they come into VMEM once, every pass runs over them
there and one launch hands back the k experts and their weights.

The scores arrive TRANSPOSED, [experts, tokens]: a token is a LANE and
its experts lie down the sublanes and across vector registers, so a
pass's two reductions (the largest score not yet taken, the lowest
expert that holds it) are elementwise maxima and minima of registers
and one reduction over eight sublanes, not a shuffle across lanes; a
group of consecutive experts is a slice of rows.  The transposes in
and out belong to the fusions that make the scores and read the
choice.  Taken is a MASK, never a value written over a score: a row
holds `-inf` outside the kept groups and no expert is chosen twice.
The weights are read where the choice fell (a maximum over one
unmasked element: exact), from the scores the weights are
(`probs`) where they are not the scores the choice reads (`by`: the
biased ones).

The call sits behind one module-level `jax.jit` (`_call`), for the
reason `kernels/grouped_matmul.py` gives.

`select_router_choice` is the one entry point: from the shapes and the
platform it returns the kernel, or None and the reason `_largest`'s
passes run instead.
"""
from __future__ import annotations

import functools
import types
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["NAME", "select_router_choice", "router_choice_supports"]

NAME = "pallas_router_choice"

# the scores, the taken and kept masks and the iotas of a call, all in
# VMEM at once: a bound on tokens x experts well inside the default
# scoped limit (Ling's 128 x 512 is 65536)
_MAX_SCORES = 1 << 18


def _kernel(*refs, k: int, n_group: int, topk_group: int, top2: bool,
            weighed: bool):
    by_ref, probs_ref = refs[0], refs[1] if weighed else None
    w_ref, e_ref = refs[-2:]
    f32, i32 = jnp.float32, jnp.int32
    x = by_ref[...]                                 # [experts, tokens]
    e_n, t_n = x.shape
    row = jax.lax.broadcasted_iota(i32, (e_n, t_n), 0)
    low = jnp.array(-jnp.inf, f32)

    def first_of(mask, ids, past):
        """The lowest id under `mask`, a token: [1, tokens]."""
        return jnp.min(jnp.where(mask, ids, past), axis=0, keepdims=True)

    if n_group > 1:
        per = e_n // n_group
        g_row = jax.lax.broadcasted_iota(i32, (n_group, t_n), 0)
        in_row = jax.lax.broadcasted_iota(i32, (per, t_n), 0)
        scores = jnp.full((n_group, t_n), low, f32)
        for g in range(n_group):
            block = x[g * per:(g + 1) * per]
            best = jnp.max(block, axis=0, keepdims=True)
            if top2:
                at = first_of(block == best, in_row, per)
                best = best + jnp.max(jnp.where(in_row == at, low, block),
                                      axis=0, keepdims=True)
            scores = jnp.where(g_row == g, best, scores)
        group = jnp.zeros((e_n, t_n), i32)
        for g in range(1, n_group):
            group = group + (row >= g * per).astype(i32)
        gone = g_row < 0
        keep = row < 0
        for _ in range(topk_group):
            best = jnp.max(jnp.where(gone, low, scores), axis=0,
                           keepdims=True)
            at = first_of((scores == best) & ~gone, g_row, n_group)
            gone = gone | (g_row == at)
            keep = keep | (group == at)
        x = jnp.where(keep, x, low if top2 else jnp.array(0.0, f32))
    taken = row < 0
    for j in range(k):
        best = jnp.max(jnp.where(taken, low, x), axis=0, keepdims=True)
        at = first_of((x == best) & ~taken, row, e_n)
        hit = row == at
        taken = taken | hit
        e_ref[j:j + 1, :] = at
        w_ref[j:j + 1, :] = best if not weighed else jnp.max(
            jnp.where(hit, probs_ref[...], low), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("k", "n_group", "topk_group",
                                             "top2", "interpret"))
def _call(by, probs, *, k, n_group, topk_group, top2, interpret):
    """Behind one module-level `jax.jit`, as the grouped matmul's: the
    body is traced once a process for a set of shapes and lowered once a
    program, however many layers call it."""
    t_n = by.shape[0]
    ins = [by.T] + ([] if probs is None else [probs.T])
    w, e = pl.pallas_call(
        functools.partial(_kernel, k=k, n_group=n_group,
                          topk_group=topk_group, top2=top2,
                          weighed=probs is not None),
        out_shape=(jax.ShapeDtypeStruct((k, t_n), jnp.float32),
                   jax.ShapeDtypeStruct((k, t_n), jnp.int32)),
        interpret=interpret, name="router_choice")(*ins)
    return w.T, e.T


def router_choice_supports(*, rows: int, width: int, k: int, n_group: int,
                           platform: str, interpret: bool = False
                           ) -> Optional[str]:
    """None where the kernel runs a choice of `k` of `width` experts
    for `rows` tokens, else the short reason it is refused (what
    `decoder.router_choice` reports after `passes:`)."""
    if platform != "tpu" and not interpret:
        return "not_tpu"
    if width % 8 or width % n_group:
        return "sublane_misaligned"
    if rows * width > _MAX_SCORES:
        return "scores_exceed_vmem"
    if k > width:
        return "k_exceeds_width"
    return None


def select_router_choice(*, rows: int, width: int, k: int, n_group: int,
                         topk_group: int, group_score: str, platform: str,
                         interpret: bool = False
                         ) -> Tuple[Optional[types.SimpleNamespace],
                                    Optional[str]]:
    """-> (kernel, None), or (None, reason) where `router_choice_supports`
    refuses: `lm_block.route` then keeps `_largest`'s passes.  A function
    of the shapes and the platform alone.

    kernel.choose(by, probs=None) -> (weights [rows, k] float32, experts
    [rows, k] int32): the k largest of `by` [rows, width] float32 a row,
    largest first, a tie to the lower expert; under `n_group` > 1 of the
    experts of the `topk_group` groups of highest score alone (a group's
    score its largest `by`, or under `group_score: "top2_sum"` the sum
    of its two largest; every other expert's `by` counts as 0, or as
    `-inf` under "top2_sum").  The weights are `by` at the chosen (as
    masked), or `probs` there where `probs` is given."""
    reason = router_choice_supports(rows=rows, width=width, k=k,
                                    n_group=n_group, platform=platform,
                                    interpret=interpret)
    if reason is not None:
        return None, reason

    def choose(by, probs=None):
        return _call(by, probs, k=k, n_group=n_group,
                     topk_group=topk_group,
                     top2=group_score == "top2_sum", interpret=interpret)

    return types.SimpleNamespace(name=NAME, choose=choose), None
