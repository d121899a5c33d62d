"""Loss ops.

Reference: /root/reference/paddle/fluid/operators/{cross_entropy,
softmax_with_cross_entropy,sigmoid_cross_entropy_with_logits,hinge_loss,
huber_loss,log_loss,margin_rank_loss,modified_huber_loss,rank_loss,
smooth_l1_loss,squared_l2_distance}_op.cc and math/cross_entropy.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from ..amp import amp_upcast
from ..core.execution import data_of, one, with_lod_of
from ..core.registry import register_op


def _label_index(label):
    """label: [N] or [N,1] int -> [N]."""
    label = data_of(label)
    if label.ndim == 2 and label.shape[-1] == 1:
        label = label.squeeze(-1)
    return label


def _take_label(x, label):
    """x: [N, D] probabilities/logits; label: [N] or [N,1] int -> x[i, label[i]]."""
    label = _label_index(label)
    return jnp.take_along_axis(x, label[:, None].astype(jnp.int32),
                               axis=1), label


@register_op("cross_entropy", inputs=("X", "Label"), outputs=("Y",),
             attrs={"soft_label": False}, diff_inputs=("X",))
def cross_entropy(ctx, ins, attrs):
    xv = one(ins, "X")
    # numerically sensitive tail: bf16 probabilities upcast to f32
    x = amp_upcast(data_of(xv))
    # additive eps (not clamp): keeps a finite, recovery-capable gradient
    # -1/(p+eps) when the softmax saturates to p≈0 on the true class
    eps = jnp.asarray(1e-10 if x.dtype == jnp.float32 else 1e-20, x.dtype)
    if attrs.get("soft_label"):
        lbl = data_of(one(ins, "Label"))
        y = -jnp.sum(lbl * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        picked, _ = _take_label(x, one(ins, "Label"))
        y = -jnp.log(picked + eps)
    return {"Y": with_lod_of(xv, y)}


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _lse_minus_picked(logits, label, wide):
    """-log_softmax(logits)[i, label[i]] as logsumexp - the picked logit,
    [N, 1] in `wide`.  The reductions run in `wide` over the logits as
    they arrive (the upcast fuses into them) and the pick reads them as
    they are, so nothing of [rows, classes] wider than the logits is
    written here or kept for the gradient; log_softmax then
    take_along_axis wrote the upcast logits and log_p whole, at a
    50k-class LM head 3.3 GB of float32 to read 8192 of its elements."""
    return _lse_minus_picked_fwd(logits, label, wide)[0]


def _lse_minus_picked_fwd(logits, label, wide):
    lse = jax.nn.logsumexp(logits.astype(wide), axis=-1, keepdims=True)
    picked = jnp.take_along_axis(logits, label[:, None], axis=1)
    return lse - picked.astype(wide), (logits, label, lse)


def _lse_minus_picked_bwd(wide, res, g):
    """(softmax - onehot) * g formed in `wide` and rounded ONCE to the
    logits' dtype, which is what autodiff through log_softmax gave.
    Autodiff of the forward above rounds softmax * g and -g apart and
    adds them in bf16: where the true class is the likely one their sum
    cancels and keeps a few bits.

    The gradient is WRITTEN, once: one pass reads the logits and the
    rows' lse, and its consumers (at an LM head the two gradient
    products and the bias's reduction) read what it wrote.  Left to
    itself the compiler clones this producer into each consumer's
    prologue, where an operand tile is formed anew for every output
    tile that reads it, so the exponentials are not hidden under the
    products: at 8192 tokens x 50272 classes on a v5e the weight's
    gradient took 15.3 ms with the prologue and 11.0 reading the
    written gradient, the hidden state's 10.0 and 8.9, and the pass
    that writes it 2.5 (`PERF.md` section 6, PR 52)."""
    logits, label, lse = res
    # a negative label counts from the end, as take_along_axis reads it
    label = jnp.where(label < 0, label + logits.shape[-1], label)
    hot = jax.lax.broadcasted_iota(label.dtype, logits.shape, 1) \
        == label[:, None]
    d = (jnp.exp(logits.astype(wide) - lse) - hot.astype(wide)) * g
    # the barrier keeps `d` one array with one producer, which is what
    # the Program declares (`Logits@GRAD` is a variable): the value and
    # its one rounding are unchanged, and no consumer re-derives it
    return jax.lax.optimization_barrier(d.astype(logits.dtype)), None


_lse_minus_picked.defvjp(_lse_minus_picked_fwd, _lse_minus_picked_bwd)


@register_op("softmax_with_cross_entropy", inputs=("Logits", "Label"),
             outputs=("Softmax", "Loss"),
             attrs={"soft_label": False},
             diff_inputs=("Logits",), diff_outputs=("Loss",))
def softmax_with_cross_entropy(ctx, ins, attrs):
    raw = data_of(one(ins, "Logits"))
    logits = amp_upcast(raw)
    if attrs.get("soft_label"):
        # a distribution a row needs every class's log-probability
        lbl = data_of(one(ins, "Label"))
        log_p = jax.nn.log_softmax(logits, axis=-1)
        loss = -jnp.sum(lbl * log_p, axis=-1, keepdims=True)
        return {"Softmax": jnp.exp(log_p), "Loss": loss}
    label = _label_index(one(ins, "Label")).astype(jnp.int32)
    # `Softmax` is dead code unless a Program fetches it
    return {"Softmax": jax.nn.softmax(logits, axis=-1),
            "Loss": _lse_minus_picked(raw, label, logits.dtype)}


@register_op("sigmoid_cross_entropy_with_logits", inputs=("X", "Label"),
             outputs=("Out",), diff_inputs=("X",))
def sigmoid_cross_entropy_with_logits(ctx, ins, attrs):
    x = data_of(one(ins, "X"))
    lbl = data_of(one(ins, "Label")).astype(x.dtype)
    out = jnp.maximum(x, 0) - x * lbl + jnp.log1p(jnp.exp(-jnp.abs(x)))
    return {"Out": out}


@register_op("hinge_loss", inputs=("Logits", "Labels"), outputs=("Loss",),
             diff_inputs=("Logits",))
def hinge_loss(ctx, ins, attrs):
    x = data_of(one(ins, "Logits"))
    y = data_of(one(ins, "Labels")).astype(x.dtype)
    return {"Loss": jnp.maximum(1.0 - (2.0 * y - 1.0) * x, 0.0)}


@register_op("huber_loss", inputs=("X", "Y"), outputs=("Residual", "Out"),
             attrs={"delta": 1.0}, diff_outputs=("Out",))
def huber_loss(ctx, ins, attrs):
    x = data_of(one(ins, "X"))
    y = data_of(one(ins, "Y"))
    d = jnp.asarray(attrs["delta"], x.dtype)
    r = y - x
    out = jnp.where(jnp.abs(r) <= d, 0.5 * jnp.square(r),
                    d * (jnp.abs(r) - 0.5 * d))
    return {"Residual": r, "Out": out}


@register_op("log_loss", inputs=("Predicted", "Labels"), outputs=("Loss",),
             attrs={"epsilon": 1e-4}, diff_inputs=("Predicted",))
def log_loss(ctx, ins, attrs):
    p = data_of(one(ins, "Predicted"))
    y = data_of(one(ins, "Labels")).astype(p.dtype)
    eps = jnp.asarray(attrs["epsilon"], p.dtype)
    return {"Loss": -y * jnp.log(p + eps) - (1 - y) * jnp.log(1 - p + eps)}


@register_op("margin_rank_loss", inputs=("X1", "X2", "Label"),
             outputs=("Out", "Activated"),
             attrs={"margin": 0.0},
             diff_inputs=("X1", "X2"), diff_outputs=("Out",))
def margin_rank_loss(ctx, ins, attrs):
    x1 = data_of(one(ins, "X1"))
    x2 = data_of(one(ins, "X2"))
    lbl = data_of(one(ins, "Label")).astype(x1.dtype)
    m = jnp.asarray(attrs["margin"], x1.dtype)
    out = jnp.maximum(-lbl * (x1 - x2) + m, 0.0)
    return {"Out": out, "Activated": (out > 0).astype(x1.dtype)}


@register_op("modified_huber_loss", inputs=("X", "Y"),
             outputs=("IntermediateVal", "Out"),
             diff_inputs=("X",), diff_outputs=("Out",))
def modified_huber_loss(ctx, ins, attrs):
    x = data_of(one(ins, "X"))
    y = data_of(one(ins, "Y")).astype(x.dtype)
    z = (2.0 * y - 1.0) * x
    out = jnp.where(z < -1.0, -4.0 * z,
                    jnp.where(z < 1.0, jnp.square(1.0 - z),
                              jnp.zeros_like(z)))
    return {"IntermediateVal": z, "Out": out}


@register_op("rank_loss", inputs=("Label", "Left", "Right"), outputs=("Out",),
             diff_inputs=("Left", "Right"))
def rank_loss(ctx, ins, attrs):
    lbl = data_of(one(ins, "Label"))
    left = data_of(one(ins, "Left"))
    right = data_of(one(ins, "Right"))
    d = left - right
    return {"Out": jnp.log1p(jnp.exp(d)) - lbl.astype(d.dtype) * d}


@register_op("smooth_l1_loss", inputs=("X", "Y", "InsideWeight",
                                       "OutsideWeight"),
             outputs=("Diff", "Out"),
             attrs={"sigma": 1.0},
             diff_inputs=("X",), diff_outputs=("Out",))
def smooth_l1_loss(ctx, ins, attrs):
    x = data_of(one(ins, "X"))
    y = data_of(one(ins, "Y"))
    iw = one(ins, "InsideWeight")
    ow = one(ins, "OutsideWeight")
    sigma2 = attrs["sigma"] ** 2
    diff = x - y
    if iw is not None:
        diff = diff * data_of(iw)
    ad = jnp.abs(diff)
    val = jnp.where(ad < 1.0 / sigma2, 0.5 * sigma2 * jnp.square(diff),
                    ad - 0.5 / sigma2)
    if ow is not None:
        val = val * data_of(ow)
    return {"Diff": diff,
            "Out": jnp.sum(val, axis=tuple(range(1, val.ndim))).reshape(-1, 1)}


# -- explicit build-time shape inference -------------------------------------

from ..core.registry import register_infer_shape  # noqa: E402
from ..core.shape_inference import input_var, set_output_shape  # noqa: E402


@register_infer_shape("cross_entropy")
def _infer_cross_entropy(op, block):
    """One loss value per row: [..., C] -> [..., 1].  Default inference
    trips when X and Label carry DIFFERENT -1 row sentinels (both map to
    the same placeholder size only if the dims really agree)."""
    x = input_var(op, block, "X")
    if x is None or x.shape is None:
        return
    set_output_shape(op, block, "Y", tuple(x.shape[:-1]) + (1,), x.dtype)
