"""Mixed-precision (bf16) training mode.

Reference analogue: doc/design/float16.md (design only — the reference
never shipped AMP training; this is the TPU rebuild's MXU-native mode).
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import amp


def _convnet():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[1, 8, 8],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        conv = fluid.layers.conv2d(input=img, num_filters=4,
                                   filter_size=3, act="relu")
        fc = fluid.layers.fc(input=conv, size=10, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=fc, label=label))
        fluid.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, conv, fc, loss


def _feed(rng):
    return {"img": rng.rand(8, 1, 8, 8).astype(np.float32),
            "label": rng.randint(0, 10, (8, 1)).astype(np.int64)}


def test_bf16_guard_activations_and_master_weights():
    rng = np.random.RandomState(0)
    main, startup, conv, fc, loss = _convnet()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    param_names = [v.name for v in main.list_vars()
                   if getattr(v, "trainable", False)]
    assert param_names

    with fluid.amp.bf16_guard():
        feed = _feed(rng)
        conv_v, loss0 = exe.run(main, feed=feed,
                                fetch_list=[conv, loss], scope=scope,
                                return_numpy=False)
        # conv output flows in bf16...
        assert str(np.asarray(conv_v).dtype) == "bfloat16" or \
            str(conv_v.dtype) == "bfloat16"
        losses = [float(np.asarray(loss0).reshape(-1)[0])]
        for _ in range(30):
            lv, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
            losses.append(float(np.asarray(lv).reshape(-1)[0]))
    # ...while master params stay float32 and training converges
    for n in param_names:
        assert np.asarray(scope.find_var(n)).dtype == np.float32, n
    assert losses[-1] < losses[0] * 0.9, losses


def test_amp_off_keeps_f32_and_caches_separately():
    rng = np.random.RandomState(1)
    main, startup, conv, fc, loss = _convnet()
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feed = _feed(rng)
    conv_f32, = exe.run(main, feed=feed, fetch_list=[conv], scope=scope,
                        return_numpy=False)
    assert str(conv_f32.dtype) == "float32"
    # same program/feeds with amp on must NOT reuse the f32 executable
    with fluid.amp.bf16_guard():
        conv_bf16, = exe.run(main, feed=feed, fetch_list=[conv],
                             scope=scope, return_numpy=False)
    assert str(conv_bf16.dtype) == "bfloat16"
    conv_back, = exe.run(main, feed=feed, fetch_list=[conv], scope=scope,
                         return_numpy=False)
    assert str(conv_back.dtype) == "float32"


def test_amp_master_weights_adam_converges():
    """Regression: under amp, a layer whose input is a bf16 intermediate
    (fc bias off the bf16 matmul output) must still create f32 params —
    bf16 Adam state explodes within two steps (beta2 rounds to 0.996 in
    bf16).  Also covers the f32-compute wrapper on optimizer ops."""
    r = np.random.RandomState(0)
    V, B = 50, 16
    xs = r.rand(B, 8).astype(np.float32)
    ys = r.randint(0, V, (B, 1)).astype(np.int32)
    amp.enable_bf16()
    try:
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            x = fluid.layers.data(name="x", shape=[8], dtype="float32")
            y = fluid.layers.data(name="y", shape=[1], dtype="int64")
            p = fluid.layers.fc(input=x, size=V, act="softmax")
            loss = fluid.layers.mean(
                fluid.layers.cross_entropy(input=p, label=y))
            fluid.Adam(learning_rate=1e-3).minimize(loss)
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        bias = [n for n in scope.local_names() if n.endswith(".b_0")]
        assert np.asarray(scope.find_var(bias[0])).dtype == np.float32
        tr = [np.asarray(exe.run(main, feed={"x": xs, "y": ys},
                                 fetch_list=[loss],
                                 scope=scope)[0]).item()
              for _ in range(10)]
        assert tr[-1] < tr[0] and tr[-1] < 5.0, tr
    finally:
        amp.disable_bf16()


def test_explicit_bf16_adam_actually_trains():
    """Regression: an explicitly-bf16 model (no amp) under Adam — beta
    pow accumulators must be f32 (bf16 rounds 0.999 to 1.0, pinning
    lr_t at 0) and update arithmetic runs in f32."""
    r = np.random.RandomState(1)
    xs = r.rand(8, 4).astype(np.float32).astype("bfloat16")
    ys = r.rand(8, 1).astype(np.float32).astype("bfloat16")
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="bfloat16")
        y = fluid.layers.data(name="y", shape=[1], dtype="bfloat16")
        pred = fluid.layers.fc(input=x, size=1)
        loss = fluid.layers.mean(
            fluid.layers.cast(
                fluid.layers.square_error_cost(pred, y), "float32"))
        fluid.Adam(learning_rate=0.05).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    b2p = [n for n in scope.local_names() if "beta2_pow" in n]
    assert np.asarray(scope.find_var(b2p[0])).dtype == np.float32
    w0 = np.asarray(scope.find_var("fc_0.w_0"), np.float32).copy()
    tr = [np.asarray(exe.run(main, feed={"x": xs, "y": ys},
                             fetch_list=[loss], scope=scope)[0])
          .reshape(-1)[0].item() for _ in range(20)]
    w1 = np.asarray(scope.find_var("fc_0.w_0"), np.float32)
    assert not np.allclose(w0, w1), "params frozen"
    assert tr[-1] < tr[0], tr
    # beta2_pow actually decays
    assert np.asarray(scope.find_var(b2p[0])).reshape(-1)[0] < 0.999


@pytest.mark.parametrize("label_shape", [[], [1]], ids=["N", "Nx1"])
def test_softmax_ce_hard_label_reads_bf16_logits(label_shape):
    """bf16 logits under amp: the loss is float32 and exact to float32's
    rounding over the logits AS ROUNDED (the reductions and the one
    subtraction are float32; the pick reads bf16, which float32 holds),
    and the gradient comes back in bf16 rounded ONCE (8 bits: 2**-9
    relative), also where the true class is the likely one and softmax *
    g and -g all but cancel."""
    import ml_dtypes

    from test_basic_ops import check_softmax_ce_hard_label

    with amp.bf16_guard():
        grad = check_softmax_ce_hard_label(ml_dtypes.bfloat16, label_shape,
                                           2.0 ** -8)
    assert str(grad.dtype) == "bfloat16"


@pytest.mark.parametrize("from_the_end", [False, True],
                         ids=["label", "label-from-the-end"])
@pytest.mark.parametrize("label_shape", [[], [1]], ids=["N", "Nx1"])
def test_softmax_ce_hard_label_bf16_gradient_is_rounded_once_to_the_bit(
        label_shape, from_the_end):
    """bf16 logits under amp: the written gradient equals, TO THE BIT,
    numpy's `(softmax - onehot) / rows` formed in float32 over the
    logits as rounded and rounded once to bf16, compiled and
    interpreted, on the likely row (where softmax * g and -g rounded
    apart keep a few bits) and on the saturated one."""
    import ml_dtypes

    from test_basic_ops import (softmax_ce_case_gradients,
                                softmax_ce_grad_rounded_once)

    with amp.bf16_guard():
        grads, logits, label = softmax_ce_case_gradients(
            ml_dtypes.bfloat16, label_shape, from_the_end)
    want = softmax_ce_grad_rounded_once(logits, label)
    for grad in grads:
        assert str(grad.dtype) == "bfloat16"
        np.testing.assert_array_equal(grad.view(np.uint16),
                                      want.view(np.uint16))


def test_softmax_ce_soft_label_upcasts_bf16_logits_as_before():
    """`soft_label` keeps log_softmax over the upcast logits, to the
    bit."""
    import jax
    import jax.numpy as jnp
    import ml_dtypes

    from test_basic_ops import run_softmax_ce, softmax_ce_case

    logits, _ = softmax_ce_case(ml_dtypes.bfloat16, [1])
    soft = np.random.RandomState(3).rand(6, 9).astype(np.float32)
    soft /= soft.sum(-1, keepdims=True)
    with amp.bf16_guard():
        loss, softmax, _ = run_softmax_ce(logits, soft, soft_label=True,
                                          compiled=False)
    log_p = jax.nn.log_softmax(jnp.asarray(logits).astype(jnp.float32),
                               axis=-1)
    np.testing.assert_array_equal(
        loss, np.asarray(-jnp.sum(soft * log_p, axis=-1, keepdims=True)))
    np.testing.assert_array_equal(softmax, np.asarray(jnp.exp(log_p)))


# -- the loss's written gradient under every executor (PR 52) ----------------

LM = dict(vocab=48, seq=8, batch=4, d_model=16, n_heads=2, n_layers=2)


def _tiny_lm(pipeline_stages=None, recompute_head=False):
    """A two-layer language model with its loss on hard labels and Adam,
    as the training cell writes it (Momentum under `pipeline_stages`:
    `PipelineExecutor` refuses Adam's shared beta-pow accumulators);
    `recompute_head` builds the head and the loss inside a
    `layers.recompute` segment."""
    from paddle_tpu.core.framework import reset_unique_names
    from paddle_tpu.models.transformer import transformer_decoder

    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    main.seed = startup.seed = 11
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[LM["seq"]],
                                dtype="int64")
        lbl = fluid.layers.data(name="lbl", shape=[LM["seq"], 1],
                                dtype="int64")
        h = transformer_decoder(
            ids, None, LM["vocab"], d_model=LM["d_model"],
            n_heads=LM["n_heads"], n_layers=LM["n_layers"],
            d_inner=2 * LM["d_model"], max_len=LM["seq"],
            pipeline_stages=pipeline_stages)

        def head():
            logits = fluid.layers.fc(input=h, size=LM["vocab"],
                                     num_flatten_dims=2)
            return fluid.layers.softmax_with_cross_entropy(
                fluid.layers.reshape(logits, shape=[-1, LM["vocab"]]),
                fluid.layers.reshape(lbl, shape=[-1, 1]))

        cost = fluid.layers.recompute(head) if recompute_head else head()
        loss = fluid.layers.mean(cost)
        (fluid.Momentum(learning_rate=0.1, momentum=0.9) if pipeline_stages
         else fluid.Adam(learning_rate=1e-2)).minimize(loss)
    params = sorted(p.name for p in main.global_block().all_parameters())
    return main, startup, loss, params


def _lm_batches(steps):
    r = np.random.RandomState(5)
    toks = r.randint(0, LM["vocab"], (steps, LM["batch"], LM["seq"] + 1))
    return [{"ids": t[:, :-1].astype(np.int64),
             "lbl": t[:, 1:, None].astype(np.int64)} for t in toks]


def _train_lm(how, steps):
    """(losses, {parameter: value}) of the tiny model's `steps` steps
    through one executor, under bf16 amp but for `pipeline` (a stage's
    bf16 output does not match the float32 carry of the schedule's
    scan: `PipelineExecutor` runs float32 Programs)."""
    import contextlib

    from paddle_tpu import parallel
    from paddle_tpu.core.flags import get_flag, set_flags

    batches = _lm_batches(steps)
    with (contextlib.nullcontext() if how == "pipeline"
          else amp.bf16_guard()):
        if how in ("pipeline", "parallel"):
            main, startup, loss, params = _tiny_lm(
                pipeline_stages=2 if how == "pipeline" else None)
            pe = (parallel.PipelineExecutor(
                main, ["ids", "lbl"], [loss], mesh={"dp": 1, "pp": 2},
                startup_program=startup, n_micro=2)
                if how == "pipeline" else parallel.ParallelExecutor(
                    main, ["ids", "lbl"], [loss], mesh={"dp": 2},
                    startup_program=startup))
            losses = [float(np.asarray(pe.run(b)[0]).ravel()[0])
                      for b in batches]
            return losses, {n: np.asarray(pe.state(n)) for n in params}
        main, startup, loss, params = _tiny_lm(
            recompute_head=how == "recompute")
        was = get_flag("memory_optimize")
        set_flags({"memory_optimize": how == "memory_optimize"})
        try:
            scope = fluid.Scope()
            exe = fluid.Executor(fluid.CPUPlace())
            exe.run(startup, scope=scope)
            losses = [float(np.asarray(exe.run(
                main, feed=b, fetch_list=[loss],
                scope=scope)[0]).ravel()[0]) for b in batches]
        finally:
            set_flags({"memory_optimize": was})
        return losses, {n: np.asarray(scope.find_var(n)) for n in params}


@pytest.mark.parametrize("how", ["executor", "pipeline", "parallel",
                                 "memory_optimize", "recompute"])
def test_lm_steps_equal_the_unwritten_gradients_to_the_bit(how,
                                                           monkeypatch):
    """The barrier behind which `softmax_with_cross_entropy`'s gradient
    is written changes WHERE the value is formed and not the value: a
    tiny language model's steps (12 through `Executor.run`; 4 through
    `PipelineExecutor`, which differentiates the composed forward with
    `jax.value_and_grad`, through `ParallelExecutor`'s mapping, under
    the `memory_optimize` flag and with the head inside a `recompute`
    segment; bf16 amp wherever the executor takes it) give the losses and
    every parameter that the same steps give with the barrier taken out
    (the parent's form: the consumers form the gradient themselves), bit
    for bit, and the model learns."""
    import jax

    steps = 12 if how == "executor" else 4
    losses, params = _train_lm(how, steps)
    assert np.isfinite(losses).all() and losses[-1] < losses[0]
    monkeypatch.setattr(jax.lax, "optimization_barrier", lambda x: x)
    want_losses, want = _train_lm(how, steps)
    assert losses == want_losses
    for name, value in want.items():
        np.testing.assert_array_equal(params[name], value, err_msg=name)
