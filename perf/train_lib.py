"""What the three training jobs share: the programs as a user would
write them (copied from chip_smoke.py, which stays free to change), the
seeded inputs, and the comparison of the first loss with the reference.
"""
from __future__ import annotations

import math
import time

import numpy as np

import common


def build_resnet(fluid, m: dict, seed: int):
    """ResNet through the layers DSL with momentum SGD, as the book
    examples write it (NCHW, the DSL's default)."""
    from paddle_tpu.core import framework as fw
    from paddle_tpu.models.resnet import resnet_cifar10, resnet_imagenet

    fw.reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    main.seed = startup.seed = common.seed31(seed)
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img",
                                shape=[3, m["image_size"], m["image_size"]],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        if m["depth"] >= 50:
            predict = resnet_imagenet(img, class_dim=m["num_classes"],
                                      depth=m["depth"])
        else:
            predict = resnet_cifar10(img, class_dim=m["num_classes"],
                                     depth=m["depth"])
        avg = fluid.layers.mean(
            fluid.layers.cross_entropy(input=predict, label=label))
        fluid.Momentum(learning_rate=m["learning_rate"],
                       momentum=m["momentum"]).minimize(avg)
    return main, startup, img, label, avg


def build_lm(fluid, m: dict, seq: int, seed: int):
    """The decoder-only LM with its loss and Adam, for `Executor.run`."""
    from paddle_tpu.core import framework as fw
    from paddle_tpu.models.transformer import transformer_lm

    fw.reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    main.seed = startup.seed = common.seed31(seed)
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[seq], dtype="int64")
        lbl = fluid.layers.data(name="lbl", shape=[seq, 1], dtype="int64")
        logits = transformer_lm(
            ids, m["vocab_size"], d_model=m["hidden_size"],
            n_heads=m["num_attention_heads"],
            n_layers=m["num_hidden_layers"], d_inner=m["ffn_dim"],
            max_len=seq, dropout_rate=0.0, return_logits=True)
        cost = fluid.layers.softmax_with_cross_entropy(
            fluid.layers.reshape(logits, shape=[-1, m["vocab_size"]]),
            fluid.layers.reshape(lbl, shape=[-1, 1]))
        avg = fluid.layers.mean(cost)
        fluid.Adam(learning_rate=m["learning_rate"]).minimize(avg)
    return main, startup, avg


def image_batches(n: int, batch: int, m: dict, seed: int):
    """`n` distinct seeded batches of float32 images in [0, 1) and
    int64 labels, made in bulk before any step."""
    rng = np.random.default_rng([common.seed31(seed), 0x1A6E])
    shape = (batch, 3, m["image_size"], m["image_size"])
    return [(rng.random(shape, dtype=np.float32),
             rng.integers(0, m["num_classes"], (batch, 1), dtype=np.int64))
            for _ in range(n)]


def parameters(program, get) -> dict:
    """name -> array of every parameter of `program`, through `get`."""
    return {v.name: get(v.name)
            for v in program.global_block().all_parameters()}


def compare_loss(got: float, want: float, tol: float) -> dict:
    rel = abs(got - want) / max(abs(want), 1e-9)
    return {"loss": got, "reference_loss": want, "rel_err": rel,
            "rel_tol": tol,
            "ok": bool(math.isfinite(got) and rel <= tol)}


def pipelined_window(step, seconds: float, on_open=None):
    """The measured window of a loop that dispatches ahead: `step(n)`
    dispatches step n and returns its loss without waiting; the loop
    then waits for the step two before it, which keeps the device's
    queue at most two deep and stamps one completion a step.  The
    window closes when the last dispatched step has finished.  Returns
    (t_open, t_close, losses, completion stamps)."""
    import jax

    t_open = time.perf_counter()
    if on_open is not None:
        on_open()
    deadline = t_open + seconds
    losses, done = [], []
    while time.perf_counter() < deadline:
        losses.append(step(len(losses)))
        if len(losses) >= 3:
            jax.block_until_ready(losses[-3])
            done.append(time.perf_counter())
    for loss in losses[-2:]:
        jax.block_until_ready(loss)
        done.append(time.perf_counter())
    return t_open, time.perf_counter(), losses, done
