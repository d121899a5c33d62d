"""Attention op backed by the Pallas flash-attention kernel.

The reference has no attention operator — attention is composed from
matmul/softmax ops (/root/reference/python/paddle/v2/fluid/nets.py:162-219).
The rebuild promotes it to a first-class op so the hot path runs the
Pallas kernel (kernels/flash_attention.py) instead of materializing the
score matrix; the generic-VJP grad machinery picks up the kernel's
custom_vjp automatically.
"""
from __future__ import annotations

from ..core.execution import data_of, one
from ..core.registry import register_op
from ..kernels import flash_attention as _flash


@register_op("flash_attention", inputs=("Q", "K", "V"), outputs=("Out",),
             attrs={"causal": False, "scale": 1.0, "default_scale": True,
                    "min_seq_k": -1},
             cost="attention")
def flash_attention_op(ctx, ins, attrs):
    """Q/K/V: [batch, seq, heads, head_dim].  default_scale=True ->
    1/sqrt(head_dim); otherwise the explicit `scale` attr (0.0 included).
    min_seq_k: -1 = kernel policy default (XLA composition below ~2k K/V
    length, where it measures faster); 0 forces the Pallas kernel."""
    q = data_of(one(ins, "Q"))
    k = data_of(one(ins, "K"))
    v = data_of(one(ins, "V"))
    scale = None if attrs.get("default_scale", True) else attrs["scale"]
    # sequence parallelism: when the executor runs this op inside a
    # shard_map whose ExecContext carries sp_axis (PipelineExecutor's
    # staged trunk with sp), q/k/v arrive as LOCAL sequence blocks and
    # attention must ring the K/V shards over that manual axis
    root = getattr(ctx, "root", None)
    sp_axis = getattr(root, "sp_axis", None) if root is not None else None
    if sp_axis:
        from ..parallel.ring_attention import ring_attention_local
        out = ring_attention_local(
            q, k, v, sp_axis, int(root.sp_size),
            causal=bool(attrs.get("causal", False)), scale=scale)
        return {"Out": out}
    kw = {}
    msk = int(attrs.get("min_seq_k", -1))
    if msk < 0:
        # per-op attr unset: the process-wide flag may override the
        # kernel's crossover policy (see core/flags.py flash_min_seq_k)
        from ..core.flags import get_flag
        msk = int(get_flag("flash_min_seq_k"))
    if msk >= 0:
        kw["min_seq_k"] = msk
    out = _flash(q, k, v, causal=bool(attrs.get("causal", False)),
                 scale=scale, platform=ctx.platform, **kw)
    return {"Out": out}
