"""Plain reference of ResNet-50 (He et al. 2015, arXiv:1512.03385,
Table 1, 50-layer): float32 `jax.numpy`, training-mode forward and the
mean cross-entropy loss of one batch, convolutions at `highest`
precision.  It takes a dict of named arrays and reads them in the
creation order of `models/resnet.py` (`conv2d_<i>.w_0`,
`batch_norm_<i>.scale_0/.offset_0`, `fc_0`): per bottleneck block the
projection shortcut comes first where there is one, then the 1x1, 3x3
and 1x1 convolutions.

As published: 7x7/2 stem, 3x3/2 max pool, stages of 3, 4, 6, 3
bottleneck blocks of widths 64, 128, 256, 512 (x4 out), the stride of a
stage on its first block's first 1x1 convolution and on the 1x1
projection shortcut, batch normalisation after every convolution and
before the ReLU, global average pool, a 1000-way fully connected layer.

Departures: none in the architecture.  Batch normalisation uses the
batch's own biased variance and eps 1e-5 (the repo's op; the paper
names no eps), convolutions carry no bias, weights are random from the
seed.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

STAGES = ((3, 64), (4, 128), (6, 256), (3, 512))
EPS = 1e-5


def _conv(x, w, stride, pad):
    return lax.conv_general_dilated(
        x, w.astype(jnp.float32), (stride, stride),
        ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST)


def _bn(x, scale, shift):
    mu = x.mean((0, 2, 3), keepdims=True)
    var = ((x - mu) ** 2).mean((0, 2, 3), keepdims=True)
    y = (x - mu) / jnp.sqrt(var + EPS)
    return (y * scale.astype(jnp.float32)[None, :, None, None]
            + shift.astype(jnp.float32)[None, :, None, None])


@jax.jit
def _loss(states, images, labels):
    it = iter(range(10 ** 6))

    def conv_bn(x, stride, pad, relu=True):
        i = next(it)
        y = _bn(_conv(x, states[f"conv2d_{i}.w_0"], stride, pad),
                states[f"batch_norm_{i}.scale_0"],
                states[f"batch_norm_{i}.offset_0"])
        return jax.nn.relu(y) if relu else y

    x = conv_bn(images.astype(jnp.float32), 2, 3)
    x = lax.reduce_window(x, -jnp.inf, lax.max, (1, 1, 3, 3),
                          (1, 1, 2, 2),
                          ((0, 0), (0, 0), (1, 1), (1, 1)))
    c_in = 64
    for stage, (count, c_mid) in enumerate(STAGES):
        for block in range(count):
            stride = 2 if (block == 0 and stage > 0) else 1
            short = x
            if stride != 1 or c_in != 4 * c_mid:
                short = conv_bn(x, stride, 0, relu=False)
            y = conv_bn(x, stride, 0)
            y = conv_bn(y, 1, 1)
            y = conv_bn(y, 1, 0, relu=False)
            x = jax.nn.relu(short + y)
            c_in = 4 * c_mid
    x = x.mean((2, 3))
    with jax.default_matmul_precision("highest"):
        lg = x @ states["fc_0.w_0"].astype(jnp.float32) \
            + states["fc_0.b_0"].astype(jnp.float32)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, labels.reshape(-1, 1), axis=-1)[:, 0]
    return jnp.mean(lse - picked)


def loss(states: dict, images, labels) -> float:
    """[B, 3, H, W] float images and [B] or [B, 1] integer labels ->
    the batch's mean cross-entropy under training-mode batch norm."""
    return float(_loss(states, jnp.asarray(images),
                       jnp.asarray(labels).astype(jnp.int32)))
