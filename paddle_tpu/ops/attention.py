"""Attention op backed by the Pallas flash-attention kernel.

The reference has no attention operator — attention is composed from
matmul/softmax ops (/root/reference/python/paddle/v2/fluid/nets.py:162-219).
The rebuild promotes it to a first-class op so the hot path runs the
Pallas kernel (kernels/flash_attention.py) instead of materializing the
score matrix.

The op has a gradient of its own.  The generic VJP grad re-traces the
forward lowering, and XLA cannot merge two Mosaic calls as it merges
its own ops: the forward kernel ran twice a layer.  So the forward
saves the kernel's row statistics in a second output, `LSE`, and
`flash_attention_grad` hands them to the kernel's backward.  Where the
kernel is not selected (no TPU, a short or ragged sequence, the
sequence-parallel ring) the forward writes no `LSE` and the gradient is
the generic one: XLA's own ops do merge.
"""
from __future__ import annotations

from ..core.execution import data_of, generic_grad_lower, one
from ..core.registry import get_op_info, register_op
from ..kernels.flash_attention import (flash_attention_backward,
                                       flash_attention_forward,
                                       flash_attention_reference)

_ATTRS = {"causal": False, "scale": 1.0, "default_scale": True,
          "min_seq_k": -1}


def _sp_axis(ctx):
    """The manual sequence-parallel axis of the enclosing shard_map
    (PipelineExecutor's staged trunk with sp), or None."""
    root = getattr(ctx, "root", None)
    return getattr(root, "sp_axis", None) if root is not None else None


def _kernel_args(ctx, attrs):
    """What the lowerings pass `flash_attention*` beside the tensors."""
    kw = {"causal": bool(attrs.get("causal", False)),
          "scale": (None if attrs.get("default_scale", True)
                    else attrs["scale"]),
          "platform": ctx.platform}
    msk = int(attrs.get("min_seq_k", -1))
    if msk < 0:
        # per-op attr unset: the process-wide flag may override the
        # kernel's crossover policy (see core/flags.py flash_min_seq_k)
        from ..core.flags import get_flag
        msk = int(get_flag("flash_min_seq_k"))
    if msk >= 0:
        kw["min_seq_k"] = msk
    return kw


@register_op("flash_attention", inputs=("Q", "K", "V"),
             outputs=("Out", "LSE"), attrs=_ATTRS, diff_outputs=("Out",),
             cost="attention")
def flash_attention_op(ctx, ins, attrs):
    """Q/K/V: [batch, seq, heads, head_dim].  default_scale=True ->
    1/sqrt(head_dim); otherwise the explicit `scale` attr (0.0 included).
    min_seq_k: -1 = kernel policy default (XLA composition below ~2k K/V
    length, where it measures faster); 0 forces the Pallas kernel.
    LSE: the kernel's row statistics, float32 [batch*heads/pack, pack,
    seq] (kernels/flash_attention.py), written where the kernel runs."""
    q = data_of(one(ins, "Q"))
    k = data_of(one(ins, "K"))
    v = data_of(one(ins, "V"))
    kw = _kernel_args(ctx, attrs)
    sp_axis = _sp_axis(ctx)
    if sp_axis:
        # q/k/v arrive as LOCAL sequence blocks and attention must ring
        # the K/V shards over that manual axis
        from ..parallel.ring_attention import ring_attention_local
        out = ring_attention_local(
            q, k, v, sp_axis, int(ctx.root.sp_size),
            causal=kw["causal"], scale=kw["scale"])
        return {"Out": out}
    res = flash_attention_forward(q, k, v, **kw)
    if res is None:
        return {"Out": flash_attention_reference(q, k, v, kw["causal"],
                                                 kw["scale"])}
    return {"Out": res[0], "LSE": res[1]}


@register_op("flash_attention_grad",
             inputs=("Q", "K", "V", "Out", "LSE", "Out@GRAD"),
             outputs=("Q@GRAD", "K@GRAD", "V@GRAD"), attrs=_ATTRS,
             cost="attention")
def flash_attention_grad_op(ctx, ins, attrs):
    """The kernel's backward on what the forward saved.  Without `LSE`
    (the forward did not run the kernel, or the Program was built before
    the op had the slot) the generic VJP over the forward lowering."""
    lse = one(ins, "LSE")
    res = None
    if lse is not None and not _sp_axis(ctx):
        q = data_of(one(ins, "Q"))
        res = flash_attention_backward(
            q, data_of(one(ins, "K")), data_of(one(ins, "V")),
            data_of(one(ins, "Out")), lse, data_of(one(ins, "Out@GRAD")),
            **_kernel_args(ctx, attrs))
    if res is None:
        return generic_grad_lower(ctx, ins, attrs,
                                  get_op_info("flash_attention"))
    return dict(zip(("Q@GRAD", "K@GRAD", "V@GRAD"), res))
