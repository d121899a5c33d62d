"""Operations and bytes from shapes: what the algorithm needs, not what
a compiler happened to emit.  Recomputed operations do not count, a
multiply-add counts as two, and the backward pass as twice the forward.
Every function takes plain sizes, so a metric file can call it with a
configuration's numbers.
"""
from __future__ import annotations

RESNET_STAGES = {50: (3, 4, 6, 3), 101: (3, 4, 23, 3), 152: (3, 8, 36, 3)}


def conv_macs(h_out: int, w_out: int, c_in: int, c_out: int, k: int) -> int:
    return h_out * w_out * c_in * c_out * k * k


def resnet_forward_macs(depth: int = 50, image: int = 224,
                        classes: int = 1000) -> int:
    """Multiply-adds of one image through the bottleneck ResNet of
    He et al. 2015, Table 1, as models/resnet.py builds it: the stride
    of a stage sits on the first 1x1 convolution of its first block
    (the paper's placement) and on the projection shortcut.  Batch
    norm, ReLU, pooling and the softmax are left out: they are under
    1% of the operations and bound by memory."""
    size = image // 2                              # conv1, stride 2
    macs = conv_macs(size, size, 3, 64, 7)
    size //= 2                                     # 3x3 max pool
    c_in = 64
    for i, (count, c_mid) in enumerate(zip(RESNET_STAGES[depth],
                                           (64, 128, 256, 512))):
        for block in range(count):
            stride = 2 if (block == 0 and i > 0) else 1
            out = size // stride
            if block == 0:                         # projection shortcut
                macs += conv_macs(out, out, c_in, 4 * c_mid, 1)
            macs += conv_macs(out, out, c_in, c_mid, 1)
            macs += conv_macs(out, out, c_mid, c_mid, 3)
            macs += conv_macs(out, out, c_mid, 4 * c_mid, 1)
            c_in, size = 4 * c_mid, out
    return macs + c_in * classes


def resnet_train_flops_per_image(depth: int = 50, image: int = 224,
                                 classes: int = 1000) -> float:
    return 3 * 2 * resnet_forward_macs(depth, image, classes)


def lm_layer_params(d_model: int, ffn: int) -> int:
    """Weights of one pre-LN decoder block (biases and LayerNorm left
    out: they add vectors, not matrix multiplications)."""
    return 4 * d_model * d_model + 2 * d_model * ffn


def lm_train_flops_per_token(d_model: int, ffn: int, layers: int,
                             vocab: int, seq: int) -> float:
    """Forward and backward operations per trained token of a causal
    decoder-only LM with an untied output head: 6 per weight of the
    blocks and the head (the embedding is a gather), plus causal
    attention: QK^T and AV are 2*seq*d_model multiply-adds a token
    over the full square, half of it under the causal mask."""
    weights = layers * lm_layer_params(d_model, ffn) + d_model * vocab
    attention = layers * 2 * seq * d_model          # MACs, forward, full
    return 6 * weights + 3 * 2 * attention / 2


def lm_decode_tick(d_model: int, ffn: int, layers: int, vocab: int,
                   slots: int, context: int, weight_bytes: int = 2,
                   kv_bytes: int = 2) -> dict:
    """One decode tick of `slots` sequences with `context` cached
    positions each: the operations, and the bytes that must cross HBM
    at least once (every weight, and each slot's K and V)."""
    weights = layers * lm_layer_params(d_model, ffn) + d_model * vocab
    flops = slots * (2 * weights + layers * 2 * 2 * context * d_model)
    weight_b = weights * weight_bytes
    kv_b = slots * layers * 2 * context * d_model * kv_bytes
    return {"flops": float(flops), "weight_bytes": float(weight_b),
            "kv_bytes": float(kv_b), "bytes": float(weight_b + kv_b)}
