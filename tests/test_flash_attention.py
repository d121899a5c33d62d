"""Pallas flash-attention kernel tests (interpret mode on CPU) + the
framework op / layer / nets integration.

Mirrors the reference's testing discipline for hand-written kernels: the
composed XLA attention (flash_attention_reference) is the oracle, like
Compare2Function CPU/GPU pairs (/root/reference/paddle/function/FunctionTest.h).
"""
import numpy as np
import jax
import jax.numpy as jnp
import pytest

import paddle_tpu as fluid
from paddle_tpu.kernels import flash_attention, flash_attention_reference


def _rand_qkv(b=2, s=256, h=2, d=64, seed=0, dtype=np.float32):
    rng = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(rng.randn(b, s, h, d).astype(dtype))
    return mk(), mk(), mk()


def _reference_lse(q, k, causal):
    """logsumexp of the scaled, masked scores, [b, h, sq] float32."""
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    if causal:
        s = jnp.where(jnp.arange(q.shape[1])[:, None]
                      >= jnp.arange(k.shape[1])[None, :], s, -jnp.inf)
    return jax.scipy.special.logsumexp(s, axis=-1)


@pytest.mark.parametrize("heads,d_head,dtype", [
    (2, 64, "float32"),     # a pair of 64: the denominator rides p . V
    (2, 64, "bfloat16"),
    (1, 64, "bfloat16"),    # a head of 64 alone: it rides too
    (2, 32, "float32"),     # so does any head under the lanes' 128
    (1, 96, "bfloat16"),
    (1, 128, "bfloat16"),   # a head of 128 keeps the summed denominator
])
@pytest.mark.parametrize("causal", [False, True])
def test_forward_matches_reference(causal, heads, d_head, dtype):
    """`out` and `lse` against the composition on float32 copies of the
    inputs as they are stored.  float32 inputs: the order of float32
    sums alone differs.  bfloat16 inputs: the scores are exact products
    summed in float32 on both sides; the kernel rounds p to bfloat16 for
    the `p . V` product (8 bits of mantissa: at most 2**-9 of each
    weight, to nearest), and where the denominator rides that product it
    sums the ROUNDED p, so `lse` = m + log(l) moves by at most
    log(1 + 2**-9) < 2**-9; `out` carries p's rounding and its own to
    bfloat16, 2**-9 each of values that reach 3.3 here."""
    fa = _kernel_module()
    q, k, v = (x.astype(dtype) for x in _rand_qkv(h=heads, d=d_head))
    out, lse = fa.flash_attention_forward(q, k, v, causal=causal,
                                          interpret=True)
    assert out.dtype == q.dtype and lse.dtype == jnp.float32
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    ref = flash_attention_reference(q32, k32, v32, causal=causal)
    tol, lse_tol = ((2e-5, 2e-5) if dtype == "float32" else
                    (2 * 2.0 ** -9 * 3.3, 2.0 ** -9 if d_head < 128 else 2e-5))
    np.testing.assert_allclose(np.asarray(out.astype(jnp.float32)),
                               np.asarray(ref), rtol=tol, atol=tol)
    # [b * h / pack, pack, sq] -> [b, h, sq]: folded heads are adjacent
    np.testing.assert_allclose(
        np.asarray(lse).reshape(q.shape[0], heads, q.shape[1]),
        np.asarray(_reference_lse(q32, k32, causal)),
        rtol=0, atol=lse_tol)


@pytest.mark.parametrize("causal", [False, True])
def test_gradients_match_reference(causal):
    # s=256 with block 128 -> 2x2 block grids: exercises cross-step scratch
    # accumulation and the causal diagonal-skip paths in dq/dkv
    q, k, v = _rand_qkv(s=256)
    w = jnp.cos(jnp.arange(q.shape[-1], dtype=jnp.float32))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    fa = lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                         interpret=True)
    g = jax.grad(loss(fa), (0, 1, 2))(q, k, v)
    r = jax.grad(loss(lambda q, k, v: flash_attention_reference(
        q, k, v, causal=causal)), (0, 1, 2))(q, k, v)
    for got, want in zip(g, r):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_causal_cross_length_gradients():
    """sk > sq with causal: the dkv q-block index clamp must stay in range
    and gradients must match the reference."""
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(1, 128, 2, 64).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 256, 2, 64).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 256, 2, 64).astype(np.float32))

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) ** 2)

    g = jax.grad(loss(lambda q, k, v: flash_attention(
        q, k, v, causal=True, interpret=True)), (0, 1, 2))(q, k, v)
    r = jax.grad(loss(lambda q, k, v: flash_attention_reference(
        q, k, v, causal=True)), (0, 1, 2))(q, k, v)
    for got, want in zip(g, r):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


def test_uneven_shapes_fall_back():
    q, k, v = _rand_qkv(s=100)  # 100 % 128 != 0 -> XLA fallback
    out = flash_attention(q, k, v)
    ref = flash_attention_reference(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_attention_layer_trains():
    """End-to-end: the flash_attention op inside a Program, with backward."""
    b, s, h, d = 2, 8, 2, 4
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.layers.data(name="q", shape=[s, h, d], dtype="float32")
        proj = fluid.layers.fc(input=fluid.layers.reshape(
            q, shape=[0, s * h * d]), size=s * h * d)
        qkv = fluid.layers.reshape(proj, shape=[0, s, h, d])
        out = fluid.layers.flash_attention(qkv, qkv, qkv, causal=True)
        loss = fluid.layers.mean(fluid.layers.square(out))
        fluid.SGD(learning_rate=0.1).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"q": np.random.RandomState(0).randn(b, s, h, d).astype("float32")}
    losses = [float(np.asarray(
        exe.run(main, feed=feed, fetch_list=[loss.name])[0]).ravel()[0])
        for _ in range(5)]
    assert losses[-1] < losses[0]


def test_nets_multihead_attention():
    """nets.scaled_dot_product_attention with heads == reference softmax
    composition computed in numpy."""
    b, s, dm, heads = 2, 8, 16, 4
    x = np.random.RandomState(1).randn(b, s, dm).astype("float32")

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        inp = fluid.layers.data(name="x", shape=[s, dm], dtype="float32")
        ctx = fluid.nets.scaled_dot_product_attention(inp, inp, inp,
                                                      num_heads=heads)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    got = exe.run(main, feed={"x": x}, fetch_list=[ctx.name])[0]

    xh = x.reshape(b, s, heads, dm // heads)
    sc = np.einsum("bqhd,bkhd->bhqk", xh, xh) / np.sqrt(dm // heads)
    p = np.exp(sc - sc.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bkhd->bqhd", p, xh).reshape(b, s, dm)
    np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-4)


def test_block_shrink_for_unaligned_seqs():
    """Seqs that are 128-aligned but not multiples of the large default
    blocks (e.g. 2560 vs block_k=1024) shrink to the largest 128-multiple
    divisor instead of falling back to the score-materializing
    composition (ADVICE r2)."""
    from paddle_tpu.kernels.flash_attention import _largest_tile

    assert _largest_tile(2560, 1024) == 640
    assert _largest_tile(3584, 1024) == 896
    assert _largest_tile(4096, 1024) == 1024
    assert _largest_tile(2048, 512) == 512
    assert _largest_tile(640, 512) == 128
    assert _largest_tile(2000, 1024) == 0  # not 128-aligned: no tile
    assert _largest_tile(96, 512) == 0


def test_flash_min_seq_k_flag_rekeys_executor_cache():
    """flash_min_seq_k is read at TRACE time (ops/attention.py), so the
    Executor compile cache must key on it — flipping the flag mid-process
    must produce a fresh executable, not replay the old trace."""
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.core.flags import get_flag, set_flags

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.layers.data(name="q", shape=[16, 2, 8], dtype="float32")
        out = fluid.layers.flash_attention(q, q, q, causal=True)
        loss = fluid.layers.mean(out)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"q": np.random.RandomState(0).randn(2, 16, 2, 8)
            .astype(np.float32)}
    prev = get_flag("flash_min_seq_k")
    try:
        set_flags({"flash_min_seq_k": -1})
        a, = exe.run(main, feed=feed, fetch_list=[loss])
        n1 = len(exe._cache)
        # interpret=None + CPU backend -> both settings take the XLA
        # reference path here, so the VALUES agree; the point is the
        # cache must not conflate the two trace-time configurations
        set_flags({"flash_min_seq_k": 0})
        b, = exe.run(main, feed=feed, fetch_list=[loss])
        n2 = len(exe._cache)
    finally:
        set_flags({"flash_min_seq_k": prev})
    assert n2 > n1, "flag flip must add a cache entry, not reuse"
    np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-5)


# ---------------------------------------------------------------------------
# the op's own gradient: the forward saves LSE, the grad op runs the
# kernel's backward on it (ops/attention.py)
# ---------------------------------------------------------------------------

def _kernel_module():
    # `paddle_tpu.kernels.flash_attention` the ATTRIBUTE is the function
    import importlib

    return importlib.import_module("paddle_tpu.kernels.flash_attention")


@pytest.fixture
def interpreted_op(monkeypatch):
    """The op's lowerings select the kernel from platform and shape
    alone; on the CPU that is never.  Steer them into the Pallas
    interpreter HERE (the program has no option for it) and count what
    they call."""
    import functools

    from paddle_tpu.ops import attention as op_mod

    calls = {"forward": 0, "backward": 0}

    def counted(name, fn):
        @functools.wraps(fn)
        def wrapper(*a, **kw):
            calls[name] += 1
            kw.pop("platform", None)
            return fn(*a, interpret=True, min_seq_k=0,
                      **{k: v for k, v in kw.items() if k != "min_seq_k"})
        return wrapper

    monkeypatch.setattr(op_mod, "flash_attention_forward",
                        counted("forward", op_mod.flash_attention_forward))
    monkeypatch.setattr(op_mod, "flash_attention_backward",
                        counted("backward", op_mod.flash_attention_backward))
    return calls


def _attention_program(q_shape, k_shape, causal, with_lse=True):
    """q, k, v fed; loss = sum(attention * w); grads of all three."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        q = fluid.layers.data(name="q", shape=list(q_shape[1:]),
                              dtype="float32")
        k = fluid.layers.data(name="k", shape=list(k_shape[1:]),
                              dtype="float32")
        v = fluid.layers.data(name="v", shape=list(k_shape[1:]),
                              dtype="float32")
        for x in (q, k, v):
            x.stop_gradient = False
        if with_lse:
            out = fluid.layers.flash_attention(q, k, v, causal=causal)
        else:       # a Program built before the op had the slot
            block = main.global_block()
            out = block.create_var(name="att_out", dtype="float32")
            out.shape = q.shape
            block.append_op("flash_attention",
                            {"Q": [q.name], "K": [k.name], "V": [v.name]},
                            {"Out": [out.name]}, {"causal": causal})
        w = fluid.layers.data(name="w", shape=list(q_shape[1:]),
                              dtype="float32")
        loss = fluid.layers.reduce_sum(
            fluid.layers.elementwise_mul(out, w))
        grads = fluid.backward.calc_gradient(loss, [q, k, v])
    return main, startup, loss, grads


def _reference_grads(q, k, v, w, causal):
    return jax.grad(lambda q, k, v: jnp.sum(flash_attention_reference(
        q, k, v, causal=causal) * w), (0, 1, 2))(q, k, v)


@pytest.mark.parametrize("causal,q_shape,k_shape", [
    (False, (2, 256, 2, 64), (2, 256, 2, 64)),    # pack 2
    (True, (2, 256, 4, 64), (2, 256, 4, 64)),     # two head pairs a row
    (True, (1, 256, 2, 128), (1, 256, 2, 128)),   # pack 1
    (True, (1, 128, 2, 64), (1, 256, 2, 64)),     # sk > sq
    (True, (2, 128, 2, 32), (2, 128, 2, 32)),     # half-filled lanes: fold
])
def test_op_gradient_runs_the_kernels_backward_on_saved_lse(
        interpreted_op, causal, q_shape, k_shape):
    rng = np.random.RandomState(5)
    feed = {"q": rng.randn(*q_shape).astype("float32"),
            "k": rng.randn(*k_shape).astype("float32"),
            "v": rng.randn(*k_shape).astype("float32"),
            "w": rng.randn(*q_shape).astype("float32")}
    main, startup, loss, grads = _attention_program(q_shape, k_shape,
                                                    causal)
    fwd_op = next(op for op in main.global_block().ops
                  if op.type == "flash_attention")
    grad_op = next(op for op in main.global_block().ops
                   if op.type == "flash_attention_grad")
    assert grad_op.input("LSE") == fwd_op.output("LSE")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    interpreted_op.update(forward=0, backward=0)  # shape inference's
    got = exe.run(main, feed=feed, fetch_list=[g.name for g in grads]
                  + fwd_op.output("LSE"))
    # the forward lowering once, the kernel's backward once: no re-run
    assert interpreted_op == {"forward": 1, "backward": 1}
    b, sq, h, d = q_shape
    pack = 2 if d == 64 else 1
    assert _kernel_module()._plan(
        *(jnp.zeros(x) for x in (q_shape, k_shape, k_shape)), causal, None,
        None, None, True, 0, None).groups == (h // pack if d != 32 else 1)
    lse = np.asarray(got[3])
    assert lse.shape == (b * h // pack, pack, sq)
    assert lse.dtype == np.float32
    want = _reference_grads(*(jnp.asarray(feed[n]) for n in "qkvw"),
                            causal)
    for g, r in zip(got[:3], want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("kernel", [False, True])
def test_op_without_lse_slot_differentiates_through_the_generic_path(
        kernel, request):
    """A Program built or saved before the op had `LSE`: the grad op has
    no statistics to use and takes the generic VJP over the forward
    lowering, with the kernel (its custom_vjp) or without."""
    calls = request.getfixturevalue("interpreted_op") if kernel else None
    shape = (1, 128, 2, 64)
    rng = np.random.RandomState(6)
    feed = {n: rng.randn(*shape).astype("float32") for n in "qkvw"}
    main, startup, loss, grads = _attention_program(shape, shape, True,
                                                    with_lse=False)
    grad_op = next(op for op in main.global_block().ops
                   if op.type == "flash_attention_grad")
    assert not grad_op.input("LSE")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    if kernel:
        calls.update(forward=0, backward=0)     # shape inference's
    got = exe.run(main, feed=feed, fetch_list=[g.name for g in grads])
    if kernel:  # forward, and the forward again under jax.vjp
        assert calls == {"forward": 2, "backward": 0}
    want = _reference_grads(*(jnp.asarray(feed[n]) for n in "qkvw"), True)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("causal,q_shape,k_shape", [
    (False, (2, 256, 2, 64), (2, 256, 2, 64)),
    (True, (2, 256, 4, 64), (2, 256, 4, 64)),
    (True, (1, 256, 2, 128), (1, 256, 2, 128)),
    (True, (1, 128, 2, 64), (1, 256, 2, 64)),
    (True, (2, 256, 2, 32), (2, 256, 2, 32)),
])
def test_fused_backward_matches_the_two_kernels(monkeypatch, causal,
                                                q_shape, k_shape):
    """One kernel for dq, dk and dv against the dq kernel and the dk/dv
    kernel on the same inputs (blocks of 128: 2 x 2 grids, so dq
    accumulates across K blocks in the whole-sequence scratch)."""
    fa = _kernel_module()
    rng = np.random.RandomState(7)
    q = jnp.asarray(rng.randn(*q_shape).astype(np.float32))
    k = jnp.asarray(rng.randn(*k_shape).astype(np.float32))
    v = jnp.asarray(rng.randn(*k_shape).astype(np.float32))
    w = jnp.asarray(rng.randn(*q_shape).astype(np.float32))

    def grads():
        return jax.grad(lambda q, k, v: jnp.sum(flash_attention(
            q, k, v, causal=causal, interpret=True, block_q=128,
            block_k=128) * w), (0, 1, 2))(q, k, v)

    assert fa._fused_bwd_fits(q_shape[1], 128, 4)
    fused = grads()
    for got, ref in zip(fused, _reference_grads(q, k, v, w, causal)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                                   rtol=2e-4, atol=2e-4)
    monkeypatch.setattr(fa, "FUSED_BWD_DQ_VMEM_BUDGET", 0)
    two = grads()
    for got, want in zip(fused, two):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("seq,fused", [
    (2048, True), (8192, True), (16384, False), (32768, False)])
def test_backward_is_chosen_by_shape(seq, fused):
    """Fused where the head pair's float32 dq and its resident output
    block fit the stated VMEM budget (bf16, packed width 128), the two
    kernels past it."""
    fa = _kernel_module()
    assert fa._fused_bwd_fits(seq, 128, 2) is fused


def test_forward_and_backward_halves_agree_with_the_function():
    """`flash_attention_forward` + `flash_attention_backward` are the
    function under `jax.grad`, split where the residuals are."""
    fa = _kernel_module()
    q, k, v = _rand_qkv(s=256)
    w = jnp.cos(jnp.arange(q.shape[-1], dtype=jnp.float32))
    out, lse = fa.flash_attention_forward(q, k, v, causal=True,
                                          interpret=True)
    assert lse.shape == (2 * 2 // 2, 2, 256) and lse.dtype == jnp.float32
    halves = fa.flash_attention_backward(
        q, k, v, out, lse, jnp.broadcast_to(w, out.shape), causal=True,
        interpret=True)
    whole = jax.grad(lambda q, k, v: jnp.sum(flash_attention(
        q, k, v, causal=True, interpret=True) * w), (0, 1, 2))(q, k, v)
    for got, want in zip(halves, whole):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-6, atol=1e-6)
    # not selected (no TPU, no interpreter): both halves say so
    assert fa.flash_attention_forward(q, k, v) is None
    assert fa.flash_attention_backward(q, k, v, out, lse, out) is None


# ---------------------------------------------------------------------------
# a causal tile against the diagonal (PR 47: `_causal_keys`): the
# backward's kernels leave out a tile's sub-tiles above it
# ---------------------------------------------------------------------------

# id: sq, sk, heads, d_head, block_q, block_k, causal, fused backward
_DIAGONAL = {
    # block_k = 2 x block_q, the cell's 4 x 2 tiles a head: a query block
    # in each half of its diagonal key block, and one wholly below
    "ratio2-pairs-of-64": (512, 512, 2, 64, 128, 256, True, True),
    "ratio2-head-of-128": (512, 512, 1, 128, 128, 256, True, True),
    "ratio2-two-kernels": (512, 512, 2, 64, 128, 256, True, False),
    # block_k = 4 x block_q: a query block in each quarter
    "ratio4": (1024, 1024, 2, 64, 128, 512, True, True),
    "ratio4-two-kernels": (1024, 1024, 1, 128, 128, 512, True, False),
    # a tile that is its own one sub-tile
    "ratio1": (384, 384, 2, 64, 128, 128, True, True),
    "block_k-under-block_q": (512, 512, 2, 64, 256, 128, True, True),
    "block_k-under-block_q-two-kernels":
        (512, 512, 1, 128, 256, 128, True, False),
    "ratio8": (1024, 1024, 2, 64, 128, 1024, True, True),
    "not-a-multiple": (768, 768, 2, 64, 256, 384, True, True),
    # the diagonal is top-left aligned whatever the lengths
    "sk-over-sq": (256, 512, 2, 64, 128, 256, True, True),
    "sk-over-sq-two-kernels": (256, 512, 2, 64, 128, 256, True, False),
    "sk-under-sq": (512, 256, 2, 64, 128, 256, True, True),
    "not-causal": (512, 512, 2, 64, 128, 256, False, True),
    "not-causal-two-kernels": (256, 512, 1, 128, 128, 256, False, False),
}


@pytest.mark.parametrize("case", sorted(_DIAGONAL))
def test_causal_tiles_match_reference(monkeypatch, case):
    """Forward and all three gradients through the Pallas interpreter
    against the XLA composition, over every way a tile can lie against
    the diagonal."""
    sq, sk, h, d, block_q, block_k, causal, fused = _DIAGONAL[case]
    fa = _kernel_module()
    if not fused:
        monkeypatch.setattr(fa, "FUSED_BWD_DQ_VMEM_BUDGET", 0)
    assert fa._fused_bwd_fits(sq, h * d, 4) is fused
    rng = np.random.RandomState(11)
    q = jnp.asarray(rng.randn(1, sq, h, d).astype(np.float32))
    k = jnp.asarray(rng.randn(1, sk, h, d).astype(np.float32))
    v = jnp.asarray(rng.randn(1, sk, h, d).astype(np.float32))
    w = jnp.asarray(rng.randn(1, sq, h, d).astype(np.float32))

    def run(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out,) + vjp(w)

    got = run(lambda q, k, v: flash_attention(
        q, k, v, causal=causal, interpret=True, block_q=block_q,
        block_k=block_k))
    want = run(lambda q, k, v: flash_attention_reference(
        q, k, v, causal=causal))
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=2e-4, atol=2e-4)


def _subtiles_by_the_mask(sq, sk, block_q, block_k):
    """`causal_subtiles` from the mask `rows >= cols` itself: a forward
    that takes a tile with a kept element whole, a backward that takes
    the tile's sub-tiles with one."""
    keep = np.arange(sq)[:, None] >= np.arange(sk)[None, :]
    divides = block_k % block_q == 0
    unit = block_q if divides else block_k
    sub = block_q if divides and 1 < block_k // block_q <= 4 else block_k
    forward = backward = live = 0
    for r0 in range(0, sq, block_q):
        for c0 in range(0, sk, block_k):
            tile = keep[r0:r0 + block_q, c0:c0 + block_k]
            live += sum(tile[:, c:c + unit].any()
                        for c in range(0, block_k, unit))
            forward += tile.any() * block_k // unit
            alive = [tile[:, c:c + sub].any()
                     for c in range(0, block_k, sub)]
            assert alive == sorted(alive, reverse=True)   # a prefix
            backward += sum(alive) * sub // unit
    return forward, backward, live


@pytest.mark.parametrize("case", sorted(
    c for c in _DIAGONAL if _DIAGONAL[c][6] and _DIAGONAL[c][7]) + [
        "opt-1.3b-train-seq2048", "seq8192"])
def test_causal_subtiles_counts_what_the_mask_says(case):
    fa = _kernel_module()
    sq, sk, _, _, block_q, block_k = {
        "opt-1.3b-train-seq2048": (2048, 2048, 32, 64, 512, 1024),
        "seq8192": (8192, 8192, 32, 64, 1024, 2048),
        **_DIAGONAL}[case][:6]
    got = fa.causal_subtiles(sq, sk, block_q, block_k)
    assert got == _subtiles_by_the_mask(sq, sk, block_q, block_k)
    forward, backward, live = got
    if block_k // block_q <= 4 or block_k % block_q:
        assert backward == live     # no sub-tile of zeros is computed
    if case == "opt-1.3b-train-seq2048":
        assert fa._select_blocks(sq, sk, 64) == (block_q, block_k)
        assert got == (12, 10, 10)
        # of the calls the kernel makes at the cell's shape
        q = jax.ShapeDtypeStruct((4, sq, 32, 64), jnp.bfloat16)
        assert fa.flash_attention_subtiles(
            q, q, q, causal=True, platform="tpu") == got
        assert fa.flash_attention_subtiles(q, q, q, platform="tpu") is None
        assert fa.flash_attention_subtiles(q, q, q, causal=True) is None


def _sub_jaxprs(jaxpr):
    for eqn in jaxpr.eqns:
        for val in eqn.params.values():
            for x in val if isinstance(val, (tuple, list)) else (val,):
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    yield inner


def _case_bodies(fn, *args):
    """The first product's shape in each `pl.when` body of `fn`'s
    kernels that multiplies."""
    bodies = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "cond":
                for br in eqn.params["branches"]:
                    dots = [e for e in br.jaxpr.eqns
                            if e.primitive.name == "dot_general"]
                    if dots:
                        bodies.append(tuple(dots[0].outvars[0].aval.shape))
        for inner in _sub_jaxprs(jaxpr):
            walk(inner)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return bodies


def _eqns(jaxpr):
    """Every equation of `jaxpr` and of the jaxprs inside it, in order."""
    yield from jaxpr.eqns
    for inner in _sub_jaxprs(jaxpr):
        yield from _eqns(inner)


def _lane_reductions(fn, *args):
    """[(primitive, operand shape)] of the reductions over the lanes of
    a 2-D tile of more than 128 columns (a score tile) anywhere in
    `fn`'s traced kernels."""
    found = []
    for eqn in _eqns(jax.make_jaxpr(fn)(*args).jaxpr):
        shape = tuple(getattr(eqn.invars[0].aval, "shape", ())) \
            if eqn.invars else ()
        if (eqn.primitive.name.startswith("reduce_")
                and len(shape) == 2 and shape[1] > 128
                and tuple(eqn.params.get("axes", ())) == (1,)):
            found.append((eqn.primitive.name, shape))
    return found


@pytest.mark.parametrize("kernel", [
    "forward", "forward-head-64-alone", "forward-head-128", "fused", "dq",
    "dkv"])
def test_a_cut_case_multiplies_its_live_keys_only(kernel, monkeypatch):
    """The traced kernel's cases at blocks of 128 x 256: in the
    backward's kernels the tile on the diagonal with one live half
    multiplies 128 keys and every other one all 256; the forward has one
    body, over all 256, and in it a head's reductions over the score
    tile's lanes: the maximum alone where the head's values leave lanes
    of the product free for the denominator's column of ones (a head of
    64, paired or alone), the maximum and the sum at a head of 128."""
    fa = _kernel_module()
    x = jnp.zeros((1, 512, 128), jnp.float32)       # a packed head pair
    stat = jnp.zeros((1, 2, 512), jnp.float32)
    plan = (0.125, True, 128, 256, False, 2, 1)
    if kernel.startswith("forward"):
        pack, width = {"forward": (2, 128), "forward-head-64-alone": (1, 64),
                       "forward-head-128": (1, 128)}[kernel]
        x = jnp.zeros((1, 512, width), jnp.float32)

        def forward(q, k, v):
            return fa._fwd_pallas(q, k, v, *plan[:5], pack, 1)

        assert _case_bodies(forward, x, x, x) == [(128, 256)]
        tile = (128, 256)
        assert _lane_reductions(forward, x, x, x) == (
            [("reduce_max", tile), ("reduce_sum", tile)]
            if kernel == "forward-head-128"
            else [("reduce_max", tile)] * pack)
        # the product's result a head: its values' columns, or all 128
        # with the denominator among them
        assert [tuple(e.outvars[0].aval.shape) for e in _eqns(
            jax.make_jaxpr(forward)(x, x, x).jaxpr)
            if e.primitive.name == "dot_general"] == [
                tile, (128, 128)] * pack
        return
    if kernel != "fused":
        monkeypatch.setattr(fa, "FUSED_BWD_DQ_VMEM_BUDGET", 0)

    def backward(causal):
        return _case_bodies(
            lambda q, k, v, lse, delta, do: fa._bwd_pallas(
                q, k, v, lse, delta, do, plan[0], causal, *plan[2:]),
            x, x, x, stat, stat, x)

    bodies = backward(True)
    if kernel == "dq":
        bodies = bodies[2:]         # the dk/dv kernel's come first
    elif kernel == "dkv":
        bodies = bodies[:2]
    # the score tile: [queries, keys] in the q-major kernel, [keys,
    # queries] in the k-major one
    assert bodies == ([(128, 128), (128, 256)] if kernel == "dq"
                      else [(128, 128), (256, 128)])
    # a call that is not causal has no cases
    assert backward(False) == []


def _cell_run(monkeypatch, reader):
    """(the reader `perf/metrics/<reader>.py`, a run of the training
    cell for it to read: configuration and traffic, nothing else)."""
    import os
    import sys
    import types

    perf = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perf")
    monkeypatch.syspath_prepend(perf)
    monkeypatch.delitem(sys.modules, "common", raising=False)
    import common

    cell = types.SimpleNamespace(
        config=common.load_json(os.path.join(
            perf, "configs", "opt-1.3b-depth8.json")),
        traffic=common.load_json(os.path.join(
            perf, "traffic", "pretrain-seq2048.json")))
    return (common.load_module(os.path.join(perf, "metrics",
                                            reader + ".py")),
            types.SimpleNamespace(cell=cell))


def test_subtiles_share_reads_the_cells_shape(monkeypatch):
    """`train_attention_subtiles_share`: the kernel module's count of
    the calls it makes at the cell's shape, forward and backward over
    the live ones twice; nothing where the kernel is not what runs."""
    reader, run = _cell_run(monkeypatch, "train_attention_subtiles_share")
    assert reader.compute(run) is None      # no TPU here: the composition
    fa = _kernel_module()
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    assert reader.compute(run) == pytest.approx(100.0 * (12 + 10) / 20)
    # a kernel that cuts nothing; a module without the count
    monkeypatch.setattr(fa, "_causal_keys",
                        lambda bq, bk: [(1 - bq, None, bk)])
    assert reader.compute(run) == pytest.approx(120.0)
    monkeypatch.delattr(fa, "flash_attention_subtiles")
    assert reader.compute(run) is None


@pytest.mark.parametrize("shape,want", [
    ((4, 2048, 32, 64), 1),     # opt-1.3b-train-seq2048: the sum rides
    ((1, 8192, 32, 64), 1),     # flash-seq8192
    ((1, 8192, 31, 64), 1),     # heads of 64 that do not pair
    ((1, 8192, 16, 128), 2),    # flash-seq8192-d128: maximum and sum
    ((4, 1024, 32, 64), None),  # under the crossover: the composition
])
def test_row_reductions_counts_what_the_forward_would_run(
        shape, want, monkeypatch):
    """`flash_attention_row_reductions`: the reductions over a score
    tile's lanes in the forward call `flash_attention` makes of the same
    arguments; the reader `train_attention_row_reductions` gives the
    cell's, and nothing without the function or the kernel."""
    fa = _kernel_module()
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert fa.flash_attention_row_reductions(
        x, x, x, causal=True, platform="tpu") == want
    assert fa.flash_attention_row_reductions(x, x, x, causal=True) is None
    if shape != (4, 2048, 32, 64):
        return
    reader, run = _cell_run(monkeypatch, "train_attention_row_reductions")
    assert reader.compute(run) is None      # no TPU here: the composition
    monkeypatch.setattr(fa.jax, "default_backend", lambda: "tpu")
    assert reader.compute(run) == want
    monkeypatch.delattr(fa, "flash_attention_row_reductions")
    assert reader.compute(run) is None


_STEP_HLO = """\
HloModule jit_fn

ENTRY %main (a: bf16[4,2048,2048]) -> bf16[4,2048,2048] {
  %a = bf16[4,2048,2048]{2,1,0} parameter(0)
  %flash_attention_0.tmp_0.1 = (bf16[4,2048,2048]{2,1,0}, f32[64,2,2048]{2,1,0}) custom-call(%a, %a, %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(fn)/flash_attention:flash_attention_0.tmp_0/pallas_call"}
  %flash_attention_1.tmp_0.1 = (bf16[4,2048,2048]{2,1,0}, f32[64,2,2048]{2,1,0}) custom-call(%a, %a, %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(fn)/flash_attention:flash_attention_1.tmp_0/pallas_call"}
  %flash_attention_2.tmp_0.1 = (bf16[4,2048,2048]{2,1,0}, f32[64,2,2048]{2,1,0}) custom-call(%a, %a, %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(fn)/flash_attention:flash_attention_2.tmp_0/pallas_call"}
  %flash_attention_grad_reshape_0.tmp_0.1 = bf16[4,2048,2048]{2,1,0} custom-call(%a, %a, %a), custom_call_target="tpu_custom_call", metadata={op_name="jit(fn)/flash_attention_grad:reshape_0.tmp_0@GRAD/pallas_call"}
  %grouped.1 = bf16[4,2048,2048]{2,1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(fn)/mul:fc_0.tmp_0/pallas_call"}
  ROOT %fusion.1 = bf16[4,2048,2048]{2,1,0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(fn)/flash_attention:flash_attention_0.tmp_0/mul"}
}
"""


def test_forward_ms_reader_on_hand_made_text(monkeypatch):
    """`train_attention_forward_ms`: the slice's seconds of the Mosaic
    calls under the Program op type `flash_attention` that it timed (not
    the grad op's, another op's kernel or a fusion under the same op),
    over their count and over the steps its BUSY seconds hold, so a
    slice with a stall in it reads the kernel's own time.  `None`
    without a device plane, a registered text, a step or such a call."""
    from paddle_tpu import profiler

    reader, run = _cell_run(monkeypatch, "train_attention_forward_ms")
    timed = {"flash_attention_0.tmp_0.1": 0.0170,
             "flash_attention_1.tmp_0.1": 0.0160,
             "flash_attention_grad_reshape_0.tmp_0.1": 0.0250,
             "grouped.1": 0.5, "fusion.1": 0.5}
    run.trace = {"op_seconds": timed, "window_s": 3.0, "busy_s": 2.0}
    run.samples = {"step_done": [0.2 * n for n in range(16)]}
    profiler.reset_profiler()
    try:
        assert reader.compute(run) is None      # no text registered
        profiler._register_hlo_text("executor.block",
                                    lambda: "ENTRY %m {\n}")
        assert reader.compute(run) is None      # no such call in it
        profiler._register_hlo_text("executor.block", lambda: _STEP_HLO)
        profiler._register_hlo_text("paged_decoder.step",
                                    lambda: _STEP_HLO)
        # two timed calls, ten steps of 200 ms in two busy seconds
        assert reader.compute(run) == pytest.approx(
            1e3 * (0.0170 + 0.0160) / 2 / 10)
        run.samples = {"step_done": [0.0]}
        assert reader.compute(run) is None      # no step was measured
        run.samples = {"step_done": [0.0, 0.2]}
        run.trace = {"op_seconds": {}, "window_s": 3.0, "busy_s": 0.0}
        assert reader.compute(run) is None      # no device plane
        run.trace = None
        assert reader.compute(run) is None
    finally:
        profiler.reset_profiler()


@pytest.mark.parametrize("variant", [
    "whole", "uncut", "no_mask", "no_row_sum", "no_row_max"])
def test_kernel_pace_rehearses_the_training_cells_flash_kernels(
        tmp_path, variant):
    """`tools/kernel_pace.py --shape opt-1.3b-train-seq2048 --rehearse
    --check`: the cell's 4 x 2 tiles a head at toy blocks through the
    interpreter, forward and backward each alone; `uncut` (the table of
    a kernel that takes a live tile whole) computes the same, `no_mask`
    does not; `no_row_sum` and `no_row_max` take the forward's
    reductions over a score tile's lanes out of the trace (the sum is
    there at a head of 128 alone: at 64 it rides the product); off a TPU
    the tool gives a time for nothing else."""
    import importlib.util
    import json
    import os

    path = os.path.join(os.path.dirname(__file__), "..", "tools",
                        "kernel_pace.py")
    spec = importlib.util.spec_from_file_location("kernel_pace", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert set(tool.FLASH_VARIANTS) == {
        "whole", "uncut", "no_mask", "no_row_sum", "no_row_max"}
    fa = _kernel_module()
    toy = dict(tool.SHAPES["opt-1.3b-train-seq2048"], batch=1, heads=2,
               seq=512, block_q=128, block_k=256)
    toy128 = dict(toy, heads=1, d_head=128)

    def reductions(shape):
        fwd, _, (q, k, v, _), _, _ = tool.build_flash(shape, fa, True)
        return sorted(name for name, _ in _lane_reductions(fwd, q, k, v))

    if variant == "whole":
        out = tmp_path / "pace.json"
        res = tool.main(["--shape", "opt-1.3b-train-seq2048", "--rehearse",
                         "--check", "--out", str(out)])
        assert res == json.loads(out.read_text())
        assert (res["rehearsal"] and res["fwd.whole"] > 0
                and res["bwd.whole"] > 0)
        assert res["blocks"] == [128, 256] and res["subtiles"] == [12, 10, 10]
        assert res["check"] < 1e-2
        with pytest.raises(SystemExit, match="no TPU"):
            tool.run("opt-1.3b-train-seq2048")
        return
    with tool.flash_removed(variant, fa):
        if variant == "uncut":
            assert fa.causal_subtiles(512, 512, 128, 256) == (12, 12, 10)
            assert tool.check_flash(toy, fa, True) < 1e-2
        elif variant == "no_mask":
            assert tool.check_flash(toy, fa, True) > 1e-1
        elif variant == "no_row_sum":
            # nothing to remove where the sum rides; a head of 128
            # without its sum is WRONG
            assert reductions(toy) == ["reduce_max"] * 2
            assert reductions(toy128) == ["reduce_max"]
            assert tool.check_flash(toy, fa, True) < 1e-2
            assert tool.check_flash(toy128, fa, True) > 1e-1
        else:
            # softmax does not move with what is subtracted, short of an
            # overflow: these scores are small
            assert reductions(toy) == [] and reductions(toy128) == [
                "reduce_sum"]
            assert tool.check_flash(toy, fa, True) < 1e-2
    assert fa.causal_subtiles(512, 512, 128, 256) == (12, 10, 10)
    assert reductions(toy) == ["reduce_max"] * 2
    assert reductions(toy128) == ["reduce_max", "reduce_sum"]
    assert tool.check_flash(toy, fa, True) < 1e-2
