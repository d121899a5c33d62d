"""Convolution / pooling ops.

Reference: /root/reference/paddle/fluid/operators/conv_op.cc (GEMM im2col
path), conv_cudnn_op.cu.cc, conv_transpose_op.cc, pool_op.cc,
pool_with_index, math/depthwise_conv.cu, spp_op, unpool_op.

TPU design: all lower to `lax.conv_general_dilated` / `lax.reduce_window`,
which XLA maps onto the MXU with its own im2col/winograd-free tiling — the
`use_cudnn`-vs-GEMM kernel choice of the reference (conv_op.cc:72-91
GetExpectedKernelType) has no analogue; the compiler owns algorithm choice.
Layout is kept NCHW at the IR level (reference default); XLA relayouts
internally for the hardware.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..amp import amp_cast
from ..core.execution import data_of, one
from ..core.registry import register_op


def _pair(v, n=2):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v),) * n


@register_op("conv2d", inputs=("Input", "Filter"), outputs=("Output",),
             attrs={"strides": [1, 1], "paddings": [0, 0],
                    "dilations": [1, 1], "groups": 1, "use_cudnn": True,
                    "data_format": "NCHW"},
             cost="conv")
def conv2d(ctx, ins, attrs):
    """data_format "NHWC" keeps activations channels-last — the TPU's
    native conv layout (vector lanes = channels); weights stay OIHW at the
    IR level either way (lax handles the rhs spec)."""
    x = data_of(one(ins, "Input"))        # [N, C, H, W] or [N, H, W, C]
    w = data_of(one(ins, "Filter"))       # [M, C/groups, kh, kw]
    x, w = amp_cast(x, w)
    s, p, d = (_pair(attrs["strides"]), _pair(attrs["paddings"]),
               _pair(attrs["dilations"]))
    df = attrs.get("data_format", "NCHW")
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=s,
        padding=[(p[0], p[0]), (p[1], p[1])],
        rhs_dilation=d,
        dimension_numbers=(df, "OIHW", df),
        feature_group_count=int(attrs.get("groups") or 1),
        preferred_element_type=jnp.float32
        if x.dtype == jnp.float32 else None)
    return {"Output": out.astype(x.dtype)}


@register_op("depthwise_conv2d", inputs=("Input", "Filter"),
             outputs=("Output",),
             attrs={"strides": [1, 1], "paddings": [0, 0],
                    "dilations": [1, 1], "groups": 1})
def depthwise_conv2d(ctx, ins, attrs):
    x = data_of(one(ins, "Input"))
    groups = attrs.get("groups") or x.shape[1]
    return conv2d(ctx, ins, {**attrs, "groups": groups})


@register_op("conv3d", inputs=("Input", "Filter"), outputs=("Output",),
             attrs={"strides": [1, 1, 1], "paddings": [0, 0, 0],
                    "dilations": [1, 1, 1], "groups": 1})
def conv3d(ctx, ins, attrs):
    x = data_of(one(ins, "Input"))        # [N, C, D, H, W]
    w = data_of(one(ins, "Filter"))
    x, w = amp_cast(x, w)
    s = _pair(attrs["strides"], 3)
    p = _pair(attrs["paddings"], 3)
    d = _pair(attrs["dilations"], 3)
    out = jax.lax.conv_general_dilated(
        x, w, window_strides=s, padding=[(pi, pi) for pi in p],
        rhs_dilation=d, dimension_numbers=("NCDHW", "OIDHW", "NCDHW"),
        feature_group_count=int(attrs.get("groups") or 1))
    return {"Output": out}


@register_op("conv2d_transpose", inputs=("Input", "Filter"),
             outputs=("Output",),
             attrs={"strides": [1, 1], "paddings": [0, 0],
                    "dilations": [1, 1]})
def conv2d_transpose(ctx, ins, attrs):
    x = data_of(one(ins, "Input"))        # [N, C, H, W]
    w = data_of(one(ins, "Filter"))       # [C, M, kh, kw] (reference layout)
    x, w = amp_cast(x, w)
    s, p = _pair(attrs["strides"]), _pair(attrs["paddings"])
    d = _pair(attrs.get("dilations", [1, 1]))
    kh, kw = w.shape[2], w.shape[3]
    # effective (dilated) kernel extents
    ekh, ekw = (kh - 1) * d[0] + 1, (kw - 1) * d[1] + 1
    # gradient-of-conv formulation: lhs-dilate input by stride, full-pad conv
    # with the spatially-flipped, IO-swapped, rhs-dilated kernel
    out = jax.lax.conv_general_dilated(
        x, jnp.flip(w, axis=(2, 3)).swapaxes(0, 1),
        window_strides=(1, 1),
        padding=[(ekh - 1 - p[0], ekh - 1 - p[0]),
                 (ekw - 1 - p[1], ekw - 1 - p[1])],
        lhs_dilation=s,
        rhs_dilation=d,
        dimension_numbers=("NCHW", "OIHW", "NCHW"))
    return {"Output": out}


def _pool_window(x, attrs, rank):
    """(window, strides, pads) for an N-spatial-dim pool; channels-last
    supported for rank 2 via data_format."""
    k = _pair(attrs.get("ksize", [2] * rank), rank)
    s = _pair(attrs.get("strides", [1] * rank), rank)
    p = _pair(attrs.get("paddings", [0] * rank), rank)
    nhwc = attrs.get("data_format", "NCHW") == "NHWC"
    sp_axes = (tuple(range(1, 1 + rank)) if nhwc
               else tuple(range(2, 2 + rank)))
    if attrs.get("global_pooling"):
        k = tuple(x.shape[a] for a in sp_axes)
        s, p = (1,) * rank, (0,) * rank
    sp_pads = tuple((pi, pi) for pi in p)
    if nhwc:
        return (1,) + k + (1,), (1,) + s + (1,), \
            ((0, 0),) + sp_pads + ((0, 0),)
    return (1, 1) + k, (1, 1) + s, ((0, 0), (0, 0)) + sp_pads


def _pool(x, attrs, rank):
    ptype = attrs.get("pooling_type", "max")
    window, strides, pads = _pool_window(x, attrs, rank)
    if ptype == "max":
        init = -jnp.inf if jnp.issubdtype(x.dtype, jnp.floating) else \
            jnp.iinfo(x.dtype).min
        return jax.lax.reduce_window(x, init, jax.lax.max, window, strides,
                                     pads)
    ssum = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides,
                                 pads)
    cnt = jax.lax.reduce_window(jnp.ones_like(x), 0.0, jax.lax.add,
                                window, strides, pads)
    return ssum / cnt


def _pool2d(x, attrs):
    return _pool(x, attrs, 2)


@register_op("pool2d", inputs=("X",), outputs=("Out",),
             attrs={"pooling_type": "max", "ksize": [2, 2],
                    "strides": [1, 1], "paddings": [0, 0],
                    "global_pooling": False, "use_cudnn": True,
                    "data_format": "NCHW"})
def pool2d(ctx, ins, attrs):
    return {"Out": _pool2d(data_of(one(ins, "X")), attrs)}


@register_op("max_pool2d_with_index", inputs=("X",),
             outputs=("Out", "Mask"),
             attrs={"ksize": [2, 2], "strides": [1, 1], "paddings": [0, 0],
                    "global_pooling": False},
             diff_outputs=("Out",))
def max_pool2d_with_index(ctx, ins, attrs):
    """Max pool + flat-spatial argmax per window in one variadic pass
    (reference pool_with_index); int32 iota so indices stay exact."""
    x = data_of(one(ins, "X"))
    h, w = x.shape[2:]
    flat_idx = jnp.arange(h * w, dtype=jnp.int32).reshape(1, 1, h, w)
    flat_idx = jnp.broadcast_to(flat_idx, x.shape)
    window, strides, pads = _pool_window(x, attrs, 2)

    def sel(a, b):
        av, ai = a
        bv, bi = b
        take_b = bv > av
        return (jnp.where(take_b, bv, av), jnp.where(take_b, bi, ai))

    init = (jnp.asarray(-jnp.inf, x.dtype), jnp.asarray(-1, jnp.int32))
    vals, idxs = jax.lax.reduce_window((x, flat_idx), init, sel,
                                       window, strides, pads)
    return {"Out": vals, "Mask": idxs.astype(jnp.int64)}


@register_op("spp", inputs=("X",), outputs=("Out",),
             attrs={"pyramid_height": 2, "pooling_type": "max"})
def spp(ctx, ins, attrs):
    """Spatial pyramid pooling (reference spp_op.cc)."""
    x = data_of(one(ins, "X"))
    n, c, h, w = x.shape
    outs = []
    for level in range(attrs["pyramid_height"]):
        bins = 2 ** level
        kh, kw = int(np.ceil(h / bins)), int(np.ceil(w / bins))
        ph, pw = (kh * bins - h + 1) // 2, (kw * bins - w + 1) // 2
        pooled = _pool2d(x, {"pooling_type": attrs["pooling_type"],
                             "ksize": [kh, kw], "strides": [kh, kw],
                             "paddings": [ph, pw]})
        outs.append(pooled.reshape(n, -1))
    return {"Out": jnp.concatenate(outs, axis=1)}


@register_op("unpool", inputs=("X", "Indices"), outputs=("Out",),
             attrs={"ksize": [2, 2], "strides": [2, 2], "paddings": [0, 0],
                    "unpooling_type": "max"},
             diff_inputs=("X",))
def unpool(ctx, ins, attrs):
    """Max-unpool via the saved flat indices (reference unpool_op.cc)."""
    x = data_of(one(ins, "X"))
    idx = data_of(one(ins, "Indices"))
    n, c, h, w = x.shape
    oh = (h - 1) * attrs["strides"][0] - 2 * attrs["paddings"][0] + \
        attrs["ksize"][0]
    ow = (w - 1) * attrs["strides"][1] - 2 * attrs["paddings"][1] + \
        attrs["ksize"][1]
    flat = jnp.zeros((n, c, oh * ow), x.dtype)
    out = jax.vmap(jax.vmap(
        lambda o, i, v: o.at[i].set(v)))(flat, idx.reshape(n, c, -1),
                                         x.reshape(n, c, -1))
    return {"Out": out.reshape(n, c, oh, ow)}


@register_op("conv_shift", inputs=("X", "Y"), outputs=("Out",))
def conv_shift(ctx, ins, attrs):
    """Circular correlation (reference conv_shift_op.cc): out[i,j] =
    sum_k x[i, (j+k-M/2) mod N] * y[i,k]."""
    x = data_of(one(ins, "X"))  # [B, N]
    y = data_of(one(ins, "Y"))  # [B, M], M odd
    m = y.shape[1]
    half = m // 2
    shifted = jnp.stack(
        [jnp.roll(x, shift=half - k, axis=1) for k in range(m)], axis=2)
    return {"Out": jnp.einsum("bnm,bm->bn", shifted, y)}


@register_op("row_conv", inputs=("X", "Filter"), outputs=("Out",))
def row_conv(ctx, ins, attrs):
    """Lookahead row convolution (reference row_conv_op.cc) over a batched
    [B, T, D] input; Filter is [future_context, D]."""
    from ..core.lod import LoDTensor

    xv = one(ins, "X")
    x = data_of(xv)
    w = data_of(one(ins, "Filter"))  # [K, D]
    k = w.shape[0]
    batched = x.ndim == 3
    if not batched:
        x3 = x[None]  # single sequence
    else:
        x3 = x
    pad = jnp.pad(x3, ((0, 0), (0, k - 1), (0, 0)))
    out = sum(pad[:, i:i + x3.shape[1], :] * w[i] for i in range(k))
    out = out if batched else out[0]
    if isinstance(xv, LoDTensor):
        return {"Out": LoDTensor(out, xv.lod)}
    return {"Out": out}


# ---------------------------------------------------------------------------
# 3D pooling + transposed conv3d (reference pool_op.cc REGISTER pool3d,
# pool_with_index_op.cc max_pool3d_with_index, conv_transpose_op.cc
# conv3d_transpose)
# ---------------------------------------------------------------------------


@register_op("pool3d", inputs=("X",), outputs=("Out",),
             attrs={"pooling_type": "max", "ksize": [2, 2, 2],
                    "strides": [1, 1, 1], "paddings": [0, 0, 0],
                    "global_pooling": False})
def pool3d(ctx, ins, attrs):
    return {"Out": _pool(data_of(one(ins, "X")), attrs, 3)}


@register_op("max_pool3d_with_index", inputs=("X",),
             outputs=("Out", "Mask"),
             attrs={"ksize": [2, 2, 2], "strides": [1, 1, 1],
                    "paddings": [0, 0, 0], "global_pooling": False},
             diff_outputs=("Out",))
def max_pool3d_with_index(ctx, ins, attrs):
    """Max pool + flat-spatial argmax index per window in ONE variadic
    reduce_window pass (reference pool_with_index_op.cc, 3D
    registration).  The index iota is int32 — float32 iotas collapse
    above 2^24 voxels."""
    x = data_of(one(ins, "X"))
    d, h, w = x.shape[2:]
    flat = jnp.arange(d * h * w, dtype=jnp.int32).reshape(1, 1, d, h, w)
    flat = jnp.broadcast_to(flat, x.shape)
    window, strides, pads = _pool_window(x, attrs, 3)

    def sel(a, b):
        av, ai = a
        bv, bi = b
        take_b = bv > av
        return jnp.where(take_b, bv, av), jnp.where(take_b, bi, ai)

    init = (jnp.asarray(-jnp.inf, x.dtype), jnp.asarray(-1, jnp.int32))
    out, idx = jax.lax.reduce_window((x, flat), init, sel, window, strides,
                                     pads)
    return {"Out": out, "Mask": idx}


@register_op("conv3d_transpose", inputs=("Input", "Filter"),
             outputs=("Output",),
             attrs={"strides": [1, 1, 1], "paddings": [0, 0, 0],
                    "dilations": [1, 1, 1]})
def conv3d_transpose(ctx, ins, attrs):
    """Gradient-of-conv formulation, 3D (reference conv_transpose_op.cc
    conv3d_transpose registration); filter layout [C, M, kd, kh, kw]."""
    x = data_of(one(ins, "Input"))        # [N, C, D, H, W]
    w = data_of(one(ins, "Filter"))
    x, w = amp_cast(x, w)
    s = _pair(attrs["strides"], 3)
    p = _pair(attrs["paddings"], 3)
    d = _pair(attrs.get("dilations", [1, 1, 1]), 3)
    ks = w.shape[2:]
    ek = tuple((ks[i] - 1) * d[i] + 1 for i in range(3))
    out = jax.lax.conv_general_dilated(
        x, jnp.flip(w, axis=(2, 3, 4)).swapaxes(0, 1),
        window_strides=(1, 1, 1),
        padding=[(ek[i] - 1 - p[i], ek[i] - 1 - p[i]) for i in range(3)],
        lhs_dilation=s, rhs_dilation=d,
        dimension_numbers=("NCDHW", "OIDHW", "NCDHW"))
    return {"Output": out}
