"""Fused MoE gate + capacity dispatch as one Pallas kernel.

The oracle (parallel/moe.moe_gate + the dispatch einsum in moe_dense)
lowers to ~15 XLA ops that materialize the [T, E] routing tensors and
the [T, E, C] one-hot dispatch tensor in HBM before the dispatch
einsum reads them back — at serving batch sizes the routing tensors
cost more HBM round-trips than the math is worth (the static analyzer
flags moe_ffn memory-bound).  This kernel runs the WHOLE pass — gate
logits, softmax, top-k argmax, capacity-position cumsum, dispatch
one-hots, the dispatch contraction and the aux loss — in one
pallas_call with every intermediate resident in VMEM, emitting only
what the expert matmuls and the combine step actually consume:
`expert_in` [E, C, D], `combine` [T, E, C] and the aux-loss scalar.

The math is LINE-FOR-LINE parallel/moe.moe_gate (top-1 Switch or
top-2 GShard with the after-all-first-choices position rule) plus
moe_dense's `einsum("td,tec->ecd")` dispatch, which keeps the fused
path bit-identical to the oracle composition
(tests/test_serving_kernels.py pins it under interpret mode).

Selection and fallback accounting: kernels/registry.py
("moe_gate_dispatch"); oversized routing tensors or non-f32 operands
fall back to the oracle, counted.  So does every TPU selection: Mosaic
cannot lower the in-kernel cumsum, so the kernel runs only under the
Pallas interpreter (its parity tests).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .registry import register_kernel

__all__ = ["moe_dispatch_supports", "build_moe_gate_dispatch"]

# everything lives in VMEM at once (that is the point); past this the
# routing tensors need tiling and the capacity cumsum stops being one
# in-register scan — fall back to the oracle instead
_VMEM_BUDGET_BYTES = 10 * 1024 * 1024


def _vmem_bytes(T: int, D: int, E: int, C: int) -> int:
    # x, gate_w, expert_in, combine + the [T, E] routing intermediates
    return 4 * (T * D + D * E + E * C * D + 2 * T * E * C + 6 * T * E)


def moe_dispatch_supports(*, tokens: int, d_model: int,
                          num_experts: int, capacity: int,
                          top_k: int = 1, dtype: str = "float32",
                          platform: str = "cpu", **_) -> Optional[str]:
    if top_k not in (1, 2):
        return "top_k"
    if dtype != "float32":
        return "dtype"
    if _vmem_bytes(tokens, d_model, num_experts, capacity) \
            > _VMEM_BUDGET_BYTES:
        return "vmem_routing"
    if platform == "tpu":
        # Mosaic has no lowering for the capacity-position cumsum
        # ("Unimplemented primitive in Pallas TPU lowering: cumsum"):
        # on a TPU this kernel is a counted fallback at every geometry
        # (ROADMAP S5 replaces the capacity machinery it fuses)
        return "mosaic_no_cumsum"
    return None


def _gate_dispatch_kernel(x_ref, gw_ref, ei_ref, cb_ref, aux_ref, *,
                          num_experts, capacity, top_k):
    x = x_ref[...]
    logits = jnp.dot(x, gw_ref[...])                     # [T, E]
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    idx1 = jnp.argmax(probs, axis=-1)
    mask1 = jax.nn.one_hot(idx1, num_experts, dtype=jnp.float32)
    g1 = jnp.sum(probs * mask1, axis=-1)

    pos1 = jnp.sum((jnp.cumsum(mask1, axis=0) - 1.0) * mask1, axis=-1)
    keep1 = (pos1 < capacity).astype(jnp.float32)
    pos1_1h = jax.nn.one_hot(pos1.astype(jnp.int32), capacity,
                             dtype=jnp.float32)
    d1 = mask1[:, :, None] * pos1_1h[:, None, :] * keep1[:, None, None]

    frac_tokens = jnp.mean(mask1, axis=0)
    frac_probs = jnp.mean(probs, axis=0)
    aux_ref[0, 0] = num_experts * jnp.sum(frac_tokens * frac_probs)

    if top_k == 1:
        dispatch = d1
        combine = d1 * g1[:, None, None]
    else:
        probs2 = probs * (1.0 - mask1)
        idx2 = jnp.argmax(probs2, axis=-1)
        mask2 = jax.nn.one_hot(idx2, num_experts, dtype=jnp.float32)
        g2 = jnp.sum(probs * mask2, axis=-1)
        first_count = jnp.sum(mask1, axis=0)
        pos2 = jnp.sum(((jnp.cumsum(mask2, axis=0) - 1.0)
                        + first_count[None, :]) * mask2, axis=-1)
        keep2 = (pos2 < capacity).astype(jnp.float32)
        pos2_1h = jax.nn.one_hot(pos2.astype(jnp.int32), capacity,
                                 dtype=jnp.float32)
        d2 = (mask2[:, :, None] * pos2_1h[:, None, :]
              * keep2[:, None, None])
        denom = jnp.maximum(g1 + g2, 1e-9)
        dispatch = d1 + d2
        combine = (d1 * (g1 / denom)[:, None, None]
                   + d2 * (g2 / denom)[:, None, None])

    ei_ref[...] = jnp.einsum("td,tec->ecd", x.astype(jnp.float32),
                             dispatch)
    cb_ref[...] = combine


@register_kernel("moe_gate_dispatch", moe_dispatch_supports)
def build_moe_gate_dispatch(*, tokens: int, d_model: int,
                            num_experts: int, capacity: int,
                            top_k: int = 1, interpret: bool = False,
                            platform: str = "cpu", **_):
    """-> fused(x [T, D] f32, gate_w [D, E] f32) ->
    (expert_in [E, C, D] f32, combine [T, E, C] f32, aux [1, 1] f32)."""
    T, D, E, C = int(tokens), int(d_model), int(num_experts), \
        int(capacity)
    kern = functools.partial(_gate_dispatch_kernel, num_experts=E,
                             capacity=C, top_k=int(top_k))

    def fused(x, gate_w):
        return pl.pallas_call(
            kern,
            out_shape=[
                jax.ShapeDtypeStruct((E, C, D), jnp.float32),
                jax.ShapeDtypeStruct((T, E, C), jnp.float32),
                jax.ShapeDtypeStruct((1, 1), jnp.float32),
            ],
            interpret=interpret,
        )(x, gate_w)

    return fused
