"""First-wave op tests: matmul/mul/elementwise/activations/reductions/
softmax/losses — numpy-reference forward + finite-difference gradients.

Mirrors reference tests python/paddle/v2/fluid/tests/test_{mul,matmul,
elementwise_*,activation,softmax,cross_entropy,mean}_op.py.
"""
import numpy as np
import pytest

from op_test import OpTest

rng = np.random.RandomState(42)


class TestMulOp(OpTest):
    op_type = "mul"

    def setUp(self):
        x = rng.rand(3, 4).astype(np.float32)
        y = rng.rand(4, 5).astype(np.float32)
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": x @ y}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["X", "Y"])


class TestMulOpFlatten(OpTest):
    op_type = "mul"
    attrs = {"x_num_col_dims": 2, "y_num_col_dims": 1}

    def setUp(self):
        x = rng.rand(2, 3, 4).astype(np.float32)
        y = rng.rand(4, 5).astype(np.float32)
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": (x.reshape(6, 4) @ y).reshape(2, 3, 5)}

    def test_output(self):
        self.check_output()


class TestMatmulTranspose(OpTest):
    op_type = "matmul"
    attrs = {"transpose_X": False, "transpose_Y": True}

    def setUp(self):
        x = rng.rand(3, 4).astype(np.float32)
        y = rng.rand(5, 4).astype(np.float32)
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": x @ y.T}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["X", "Y"])


class TestMatmulBatched(OpTest):
    op_type = "matmul"

    def setUp(self):
        x = rng.rand(2, 3, 4).astype(np.float32)
        y = rng.rand(2, 4, 5).astype(np.float32)
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": np.matmul(x, y)}

    def test_output(self):
        self.check_output()


class TestElementwiseAddBroadcast(OpTest):
    op_type = "elementwise_add"
    attrs = {"axis": 1}

    def setUp(self):
        x = rng.rand(2, 3, 4).astype(np.float32)
        y = rng.rand(3).astype(np.float32)
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": x + y.reshape(1, 3, 1)}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["X", "Y"])


class TestElementwiseDiv(OpTest):
    op_type = "elementwise_div"

    def setUp(self):
        x = rng.rand(3, 4).astype(np.float32) + 1.0
        y = rng.rand(3, 4).astype(np.float32) + 1.0
        self.inputs = {"X": x, "Y": y}
        self.outputs = {"Out": x / y}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["X", "Y"], max_relative_error=1e-2)


@pytest.mark.parametrize("act,fn", [
    ("sigmoid", lambda x: 1 / (1 + np.exp(-x))),
    ("tanh", np.tanh),
    ("relu", lambda x: np.maximum(x, 0)),
    ("exp", np.exp),
    ("square", np.square),
    ("softsign", lambda x: x / (1 + np.abs(x))),
    ("reciprocal", lambda x: 1 / x),
    ("abs", np.abs),
])
def test_activation_forward(act, fn):
    class T(OpTest):
        op_type = act

        def setUp(self):
            x = rng.rand(3, 4).astype(np.float32) + 0.5
            self.inputs = {"X": x}
            self.outputs = {"Out": fn(x)}

    t = T()
    t.check_output()


@pytest.mark.parametrize("act", ["sigmoid", "tanh", "square", "log",
                                 "sqrt", "softplus"])
def test_activation_grad(act):
    x = rng.rand(3, 4).astype(np.float32) + 0.5

    class T(OpTest):
        op_type = act

        def setUp(self):
            self.inputs = {"X": x}
            self.outputs = {"Out": np.zeros_like(x)}  # only dtype is used

    T().check_grad(["X"], max_relative_error=1e-2)


class TestSoftmax(OpTest):
    op_type = "softmax"

    def setUp(self):
        x = rng.rand(4, 7).astype(np.float32)
        e = np.exp(x - x.max(-1, keepdims=True))
        self.inputs = {"X": x}
        self.outputs = {"Out": e / e.sum(-1, keepdims=True)}
        # softmax rows sum to 1, so the harness's plain mean(Out) loss is
        # CONSTANT in X: its true gradient is 0 and the check compares
        # float32 rounding noise right at the tolerance — the historical
        # intermittent tier-1 flake.  A fixed non-uniform weighting makes
        # the loss (and gradient) a real function of X.
        # wide spread so the signal dominates the f32 rounding noise in
        # the central differences (a narrow spread left it borderline)
        self.grad_output_weights = {
            "Out": np.linspace(-4.0, 4.0, 28, dtype=np.float32)
            .reshape(4, 7)}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        # wider central-difference step: softmax is smooth, so the
        # truncation error stays negligible while the f32 eval noise
        # (∝ 1/delta) drops well under the tolerance
        self.check_grad(["X"], max_relative_error=1e-2,
                        numeric_delta=2e-3)


class TestMean(OpTest):
    op_type = "mean"

    def setUp(self):
        x = rng.rand(3, 4).astype(np.float32)
        self.inputs = {"X": x}
        self.outputs = {"Out": np.asarray([x.mean()], np.float32)}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["X"])


class TestCrossEntropy(OpTest):
    op_type = "cross_entropy"

    def setUp(self):
        p = rng.rand(4, 5).astype(np.float32) + 0.1
        p /= p.sum(-1, keepdims=True)
        label = rng.randint(0, 5, (4, 1)).astype(np.int64)
        y = -np.log(p[np.arange(4), label.ravel()]).reshape(4, 1)
        self.inputs = {"X": p, "Label": label}
        self.outputs = {"Y": y.astype(np.float32)}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["X"], max_relative_error=1e-2)


class TestSoftmaxWithCE(OpTest):
    op_type = "softmax_with_cross_entropy"

    def setUp(self):
        logits = rng.rand(4, 5).astype(np.float32)
        label = rng.randint(0, 5, (4, 1)).astype(np.int64)
        e = np.exp(logits - logits.max(-1, keepdims=True))
        sm = e / e.sum(-1, keepdims=True)
        loss = -np.log(sm[np.arange(4), label.ravel()]).reshape(4, 1)
        self.inputs = {"Logits": logits, "Label": label}
        self.outputs = {"Softmax": sm, "Loss": loss.astype(np.float32)}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["Logits"], max_relative_error=1e-2)


def log_softmax_np(x):
    """float32 log_softmax written out: the reference of the hard-label
    cases below and of test_amp.py's bf16 ones."""
    x = np.asarray(x, np.float32)
    z = x - x.max(-1, keepdims=True)
    return z - np.log(np.exp(z).sum(-1, keepdims=True))


def softmax_ce_case(dtype, label_shape):
    """Six rows of 9 classes, the labels and the logits: row 0's true
    class has the largest logit, row 1's lies 60 below the largest (the
    saturated case: its probability is e^-60)."""
    case_rng = np.random.RandomState(7)
    logits = case_rng.randn(6, 9).astype(np.float32)
    label = case_rng.randint(0, 9, 6)
    logits[0, label[0]] = logits[0].max() + 3.0
    logits[1, label[1]] = logits[1].max() - 60.0
    return (logits.astype(dtype),
            label.astype(np.int64).reshape([6] + label_shape))


def run_softmax_ce(logits, label, soft_label=False, compiled=True):
    """`Loss`, `Softmax` and the gradient of mean(Loss) to `Logits`
    through a Program and its backward, as the Executor gives them."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=list(logits.shape[1:]),
                              dtype=str(logits.dtype))
        x.stop_gradient = False
        y = fluid.layers.data(name="y", shape=list(label.shape[1:]),
                              dtype=str(label.dtype))
        loss = fluid.layers.softmax_with_cross_entropy(
            x, y, soft_label=soft_label)
        fluid.backward.append_backward(fluid.layers.mean(loss))
    softmax = main.global_block().ops[0].outputs["Softmax"][0]
    got = fluid.Executor(fluid.CPUPlace()).run(
        main, feed={"x": logits, "y": label},
        fetch_list=[loss, softmax, "x@GRAD"], return_numpy=False,
        compiled=compiled)
    return [np.asarray(v) for v in got]


def check_softmax_ce_hard_label(dtype, label_shape, grad_rtol):
    """The op on `softmax_ce_case` against float32 log_softmax over the
    logits as given: `Loss` and `Softmax` to float32's rounding, the
    gradient of mean(Loss) to `grad_rtol`; the saturated row keeps a
    finite loss and -1/N on its true class.  Returns the gradient."""
    logits, label = softmax_ce_case(dtype, label_shape)
    loss, softmax, grad = run_softmax_ce(logits, label)
    log_p = log_softmax_np(logits)
    rows, flat = np.arange(6), label.ravel()
    assert loss.dtype == np.float32 and loss.shape == (6, 1)
    np.testing.assert_allclose(loss.ravel(), -log_p[rows, flat],
                               rtol=1e-6, atol=1e-6)
    assert 59.5 < loss[1, 0] < 63.0 and loss[0, 0] < 0.5
    assert softmax.dtype == np.float32
    np.testing.assert_allclose(softmax, np.exp(log_p), rtol=1e-5,
                               atol=1e-7)
    want = np.exp(log_p)
    want[rows, flat] -= 1.0
    np.testing.assert_allclose(grad.astype(np.float32), want / 6.0,
                               rtol=grad_rtol, atol=1e-6 * grad_rtol)
    assert float(grad[1, flat[1]]) == pytest.approx(-1.0 / 6.0,
                                                    rel=grad_rtol)
    return grad


@pytest.mark.parametrize("label_shape", [[], [1]], ids=["N", "Nx1"])
def test_softmax_ce_hard_label_against_float32_log_softmax(label_shape):
    grad = check_softmax_ce_hard_label(np.float32, label_shape, 1e-5)
    assert grad.dtype == np.float32


def softmax_ce_grad_rounded_once(logits, label):
    """numpy: the gradient of mean(Loss) to the logits, `(softmax -
    onehot) / rows` formed in float32 over the logits as given and
    rounded ONCE to their dtype.  A negative label counts from the
    end."""
    x = np.asarray(logits, np.float32)
    flat = label.ravel() % x.shape[-1]
    top = x.max(-1, keepdims=True)
    lse = np.log(np.exp(x - top).sum(-1, keepdims=True,
                                     dtype=np.float32)) + top
    hot = (np.arange(x.shape[-1])[None, :] == flat[:, None])
    d = (np.exp(x - lse) - hot.astype(np.float32)) \
        * np.float32(1.0 / len(x))
    return d.astype(logits.dtype)


def softmax_ce_case_gradients(dtype, label_shape, from_the_end):
    """`softmax_ce_case`'s gradient through the compiled step and
    through the interpreter, and the case itself; `from_the_end` names
    every class by its negative index."""
    logits, label = softmax_ce_case(dtype, label_shape)
    if from_the_end:
        label = label - logits.shape[-1]
    grads = [run_softmax_ce(logits, label, compiled=compiled)[2]
             for compiled in (True, False)]
    return grads, logits, label


@pytest.mark.parametrize("from_the_end", [False, True],
                         ids=["label", "label-from-the-end"])
@pytest.mark.parametrize("label_shape", [[], [1]], ids=["N", "Nx1"])
def test_softmax_ce_hard_label_gradient_is_formed_once_in_float32(
        label_shape, from_the_end):
    """The written gradient (`ops/loss.py`: one producer behind a
    barrier, read by every consumer) is the formula's value: to the bit
    the same jax calls made here in float32, and numpy's to float32's
    last places (its exp and log round apart from XLA's), on the row
    whose true class is the likely one and on the saturated row."""
    import jax
    import jax.numpy as jnp

    grads, logits, label = softmax_ce_case_gradients(
        np.float32, label_shape, from_the_end)
    x = jnp.asarray(logits)
    flat = jnp.asarray(label.ravel() % logits.shape[-1])
    hot = jnp.arange(logits.shape[-1])[None, :] == flat[:, None]
    want = (jnp.exp(x - jax.nn.logsumexp(x, axis=-1, keepdims=True))
            - hot.astype(jnp.float32)) * jnp.float32(1.0 / len(logits))
    for grad in grads:
        assert grad.dtype == np.float32
        np.testing.assert_array_equal(grad, np.asarray(want))
        np.testing.assert_array_max_ulp(
            grad, softmax_ce_grad_rounded_once(logits, label), maxulp=8)


def test_softmax_ce_soft_label_is_log_softmax_to_the_bit():
    """A distribution a row keeps the form that needs log_p whole: the
    interpreter's results equal the same jax calls made here."""
    import jax
    import jax.numpy as jnp

    logits, _ = softmax_ce_case(np.float32, [1])
    soft = np.random.RandomState(3).rand(6, 9).astype(np.float32)
    soft /= soft.sum(-1, keepdims=True)
    loss, softmax, grad = run_softmax_ce(logits, soft, soft_label=True,
                                         compiled=False)

    def written_out(x):
        log_p = jax.nn.log_softmax(x, axis=-1)
        return -jnp.sum(soft * log_p, axis=-1, keepdims=True), log_p

    (want, log_p), vjp = jax.vjp(written_out, jnp.asarray(logits))
    np.testing.assert_array_equal(loss, np.asarray(want))
    np.testing.assert_array_equal(softmax, np.asarray(jnp.exp(log_p)))
    want_grad, = vjp((jnp.full((6, 1), 1.0 / 6.0, jnp.float32),
                      jnp.zeros_like(log_p)))
    np.testing.assert_array_equal(grad, np.asarray(want_grad))


class TestReduceSum(OpTest):
    op_type = "reduce_sum"
    attrs = {"dim": [1], "keep_dim": False, "reduce_all": False}

    def setUp(self):
        x = rng.rand(3, 4, 2).astype(np.float32)
        self.inputs = {"X": x}
        self.outputs = {"Out": x.sum(axis=1)}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["X"])


class TestConcatOp(OpTest):
    op_type = "concat"
    attrs = {"axis": 1}

    def setUp(self):
        a = rng.rand(2, 3).astype(np.float32)
        b = rng.rand(2, 4).astype(np.float32)
        self.inputs = {"X": [("a", a), ("b", b)]}
        self.outputs = {"Out": np.concatenate([a, b], axis=1)}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["a", "b"])


class TestTopK(OpTest):
    op_type = "top_k"
    attrs = {"k": 2}

    def setUp(self):
        x = rng.rand(3, 5).astype(np.float32)
        idx = np.argsort(-x, axis=1)[:, :2]
        self.inputs = {"X": x}
        self.outputs = {"Out": np.take_along_axis(x, idx, 1),
                        "Indices": idx.astype(np.int64)}

    def test_output(self):
        self.check_output()


class TestSgd(OpTest):
    op_type = "sgd"

    def setUp(self):
        p = rng.rand(4, 3).astype(np.float32)
        g = rng.rand(4, 3).astype(np.float32)
        lr = np.asarray([0.1], np.float32)
        self.inputs = {"Param": p, "Grad": g, "LearningRate": lr}
        self.outputs = {"ParamOut": p - 0.1 * g}

    def test_output(self):
        self.check_output()


class TestAdam(OpTest):
    op_type = "adam"
    attrs = {"beta1": 0.9, "beta2": 0.999, "epsilon": 1e-8}

    def setUp(self):
        p = rng.rand(4).astype(np.float32)
        g = rng.rand(4).astype(np.float32)
        m1 = rng.rand(4).astype(np.float32)
        m2 = rng.rand(4).astype(np.float32)
        lr = np.asarray([0.01], np.float32)
        b1p = np.asarray([0.9], np.float32)
        b2p = np.asarray([0.999], np.float32)
        m1o = 0.9 * m1 + 0.1 * g
        m2o = 0.999 * m2 + 0.001 * g * g
        lr_t = 0.01 * np.sqrt(1 - 0.999) / (1 - 0.9)
        po = p - lr_t * m1o / (np.sqrt(m2o) + 1e-8)
        self.inputs = {"Param": p, "Grad": g, "Moment1": m1, "Moment2": m2,
                       "LearningRate": lr, "Beta1Pow": b1p,
                       "Beta2Pow": b2p}
        self.outputs = {"ParamOut": po, "Moment1Out": m1o,
                        "Moment2Out": m2o}

    def test_output(self):
        self.check_output(atol=1e-4)


class TestLookupTable(OpTest):
    op_type = "lookup_table"

    def setUp(self):
        w = rng.rand(10, 4).astype(np.float32)
        ids = rng.randint(0, 10, (5, 1)).astype(np.int64)
        self.inputs = {"Ids": ids, "W": w}
        self.outputs = {"Out": w[ids.ravel()]}

    def test_output(self):
        self.check_output()

    def test_grad(self):
        self.check_grad(["W"])


class TestBatchNormTrain(OpTest):
    op_type = "batch_norm"
    attrs = {"momentum": 0.9, "epsilon": 1e-5, "is_test": False}

    def setUp(self):
        x = rng.rand(3, 2, 4, 4).astype(np.float32)
        scale = rng.rand(2).astype(np.float32)
        bias = rng.rand(2).astype(np.float32)
        mean = np.zeros(2, np.float32)
        var = np.ones(2, np.float32)
        bm = x.mean(axis=(0, 2, 3))
        bv = x.var(axis=(0, 2, 3))
        y = ((x - bm.reshape(1, 2, 1, 1)) /
             np.sqrt(bv.reshape(1, 2, 1, 1) + 1e-5)
             * scale.reshape(1, 2, 1, 1) + bias.reshape(1, 2, 1, 1))
        self.inputs = {"X": x, "Scale": scale, "Bias": bias, "Mean": mean,
                       "Variance": var}
        self.outputs = {
            "Y": y,
            "MeanOut": 0.9 * mean + 0.1 * bm,
            "VarianceOut": 0.9 * var + 0.1 * bv,
            "SavedMean": bm,
            "SavedVariance": 1.0 / np.sqrt(bv + 1e-5),
        }

    def test_output(self):
        self.check_output(atol=1e-4)


class TestLayerNorm(OpTest):
    op_type = "layer_norm"
    attrs = {"epsilon": 1e-5, "begin_norm_axis": 1}

    def setUp(self):
        x = rng.rand(3, 8).astype(np.float32)
        scale = rng.rand(8).astype(np.float32)
        bias = rng.rand(8).astype(np.float32)
        mean = x.mean(-1, keepdims=True)
        var = x.var(-1, keepdims=True)
        y = (x - mean) / np.sqrt(var + 1e-5) * scale + bias
        self.inputs = {"X": x, "Scale": scale, "Bias": bias}
        self.outputs = {"Y": y, "Mean": mean.ravel(), "Variance": var.ravel()}

    def test_output(self):
        self.check_output(atol=1e-4)

    def test_grad(self):
        self.check_grad(["X", "Scale", "Bias"], max_relative_error=2e-2)


class TestBatchNormLargeMeanF32(OpTest):
    """f32 variance must use the centered two-pass form: E[x^2]-m^2
    catastrophically cancels when |mean| >> std (review r2 finding)."""
    op_type = "batch_norm"
    attrs = {"momentum": 0.9, "epsilon": 1e-5, "is_test": False}

    def setUp(self):
        x = (1e4 + rng.randn(4, 3, 4, 4)).astype(np.float32)
        scale = np.ones(3, np.float32)
        bias = np.zeros(3, np.float32)
        bm = x.mean(axis=(0, 2, 3))
        bv = x.var(axis=(0, 2, 3))
        y = ((x - bm.reshape(1, 3, 1, 1)) /
             np.sqrt(bv.reshape(1, 3, 1, 1) + 1e-5))
        self.inputs = {"X": x, "Scale": scale, "Bias": bias,
                       "Mean": np.zeros(3, np.float32),
                       "Variance": np.ones(3, np.float32)}
        self.outputs = {"Y": y}

    def test_output(self):
        self.check_output(atol=5e-3)


def test_reduce_max_grad_single_route_on_ties():
    """reduce_max/min backward routes each output's cotangent to exactly
    one input element even under exact ties (index routing, not the
    float-equality VJP that duplicates under TPU fusion — see
    ops/reduce.py _index_routed_extreme and the sequence_pool MAX bug)."""
    import paddle_tpu as fluid

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[3], dtype="float32")
        x.stop_gradient = False
        m = fluid.layers.reduce_max(x, dim=1)
        loss = fluid.layers.mean(m)
        fluid.backward.append_backward(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    xd = np.array([[2.0, 2.0, 1.0],
                   [0.0, 3.0, 3.0]], np.float32)
    g, = exe.run(main, feed={"x": xd}, fetch_list=["x@GRAD"])
    g = np.asarray(g)
    # one nonzero per row, each worth 1/2 (mean over 2 rows)
    np.testing.assert_array_equal((np.abs(g) > 0).sum(axis=1), [1, 1])
    np.testing.assert_allclose(g.sum(axis=1), [0.5, 0.5])
