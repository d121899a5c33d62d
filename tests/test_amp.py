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
