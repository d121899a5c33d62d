"""Whole-program memory layer (memory_optimization_transpiler + the
executors): liveness donation plan, build-time rejection of unsafe
donations, dead-var freeing, the memory_optimize flag's bit-identical
guarantee, the remat/jit_granularity knobs, and the
LoD-bucketing recompile pin (the BOOK_MATRIX_r05 recommender compile
outlier)."""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import framework as fw
from paddle_tpu.core.flags import flag_defaults, get_flag, set_flags
from paddle_tpu.memory_optimization_transpiler import (
    DonationError,
    memory_optimize,
    plan_dead_frees,
    plan_donation,
)


@pytest.fixture(autouse=True)
def _restore_flags():
    keep = {k: get_flag(k) for k in ("memory_optimize", "remat",
                                     "jit_granularity")}
    yield
    set_flags(keep)


def _build_mlp(donate_x=False):
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32",
                              donate=donate_x)
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        h2 = fluid.layers.fc(input=h, size=16, act="relu")
        pred = fluid.layers.fc(input=h2, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.SGD(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _build_conv():
    """Book-builder-shaped conv net (recognize_digits)."""
    from paddle_tpu import nets

    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[1, 8, 8],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        cp = nets.simple_img_conv_pool(
            input=img, filter_size=3, num_filters=4, pool_size=2,
            pool_stride=2, act="relu")
        pred = fluid.layers.fc(input=cp, size=10, act="softmax")
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=pred, label=label))
        fluid.Adam(learning_rate=0.01).minimize(loss)
    return main, startup, loss


# ---------------------------------------------------------------------------
# donation plan
# ---------------------------------------------------------------------------


def test_plan_donation_feeds_and_states():
    main, _, loss = _build_mlp()
    plan = plan_donation(main, ["x", "y"], [loss.name],
                         state_rw_names=["w0"])
    assert {"x", "y"} <= plan.feeds
    assert "w0" in plan.states
    assert not plan.rejected
    # a fetched feed is NOT donatable
    plan = plan_donation(main, ["x", "y"], [loss.name, "x"])
    assert "x" not in plan.feeds and "y" in plan.feeds


def test_plan_rejects_unsafe_requests():
    main, _, loss = _build_mlp()
    # fetched
    plan = plan_donation(main, ["x"], ["x"], requested=["x"])
    assert "x" in plan.rejected
    with pytest.raises(DonationError, match="fetched"):
        plan.check()
    # read-only persistable (a parameter that is never rewritten here:
    # pretend by asking for a param of the unoptimized fwd program)
    pname = main.global_block().all_parameters()[0].name
    plan = plan_donation(main, ["x"], [loss.name], requested=[pname])
    with pytest.raises(DonationError, match="persistable"):
        plan.check()
    # never consumed
    main.global_block().create_var(name="orphan", shape=[1],
                                   dtype="float32")
    with pytest.raises(DonationError, match="never consumed"):
        plan_donation(main, ["orphan"], [], requested=["orphan"]).check()


def test_donated_then_reused_raises_at_build_time():
    """A donate=True feed that is also fetched must fail BEFORE tracing
    (DonationError from the plan — or, when PADDLE_TPU_VERIFY=error is
    armed, the donation-safety pass's ProgramVerificationError, which
    preflights first), never as a deleted-buffer crash."""
    from paddle_tpu.analysis import ProgramVerificationError

    main, startup, loss = _build_mlp(donate_x=True)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.random.rand(4, 16).astype(np.float32),
            "y": np.random.rand(4, 1).astype(np.float32)}
    with pytest.raises((DonationError, ProgramVerificationError),
                       match="donat"):
        exe.run(main, feed=feed, fetch_list=[loss, "x"], scope=scope)
    # the guarantee holds on the interpreter path too: a donation can't
    # be fulfilled there, but the unsafe hint must not wait for the
    # compiled path to fail
    with pytest.raises((DonationError, ProgramVerificationError),
                       match="donat"):
        exe.run(main, feed=feed, fetch_list=[loss, "x"], scope=scope,
                compiled=False)
    # the same program with a safe fetch list runs fine (hint honored)
    out, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert np.isfinite(np.asarray(out)).all()


def test_donation_safety_analysis_pass():
    """The donation-safety pass reports the same invariants as error
    diagnostics at verify time (docs/analysis.md)."""
    main, _, loss = _build_mlp(donate_x=True)
    diags = main.verify(level=None, passes=["donation-safety"],
                        fetch_names={"x"})
    assert any(d.severity == "error" and "donate" in d.message
               for d in diags)
    # without the fetch the hint is clean
    diags = main.verify(level=None, passes=["donation-safety"],
                        fetch_names={loss.name})
    assert not [d for d in diags if d.severity == "error"]
    # persistable donation hint is an error regardless of fetch context
    p = main.global_block().all_parameters()[0]
    p.donate = True
    diags = main.verify(level=None, passes=["donation-safety"])
    assert any(d.severity == "error" and p.name in d.message
               for d in diags)


def test_parallel_executor_rejects_unsafe_hint():
    from paddle_tpu.analysis import ProgramVerificationError

    main, startup, loss = _build_mlp(donate_x=True)
    with pytest.raises((DonationError, ProgramVerificationError),
                       match="fetch"):
        fluid.ParallelExecutor(main, ["x", "y"], [loss, "x"],
                               mesh={"dp": 1}, startup_program=startup)


# ---------------------------------------------------------------------------
# dead-var freeing
# ---------------------------------------------------------------------------


def test_plan_dead_frees_protections():
    main, _, loss = _build_mlp()
    frees = plan_dead_frees(main, [loss.name])
    freed = {n for ns in frees.values() for n in ns}
    assert freed, "no dead vars found in an MLP train program"
    # fetch targets and persistables never freed
    assert loss.name not in freed
    for p in main.global_block().all_parameters():
        assert p.name not in freed
    # every freed name is freed at its LAST touch
    for idx, names in frees.items():
        for later in main.global_block().ops[idx + 1:]:
            for n in names:
                assert n not in later.input_names()
                assert n not in later.output_names()


def test_dead_var_freeing_shrinks_live_scope():
    """With memory_optimize on, the interpreter drops local-scope refs
    mid-run: spy on Scope.erase to see the frees actually happen."""
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.random.rand(4, 16).astype(np.float32),
            "y": np.random.rand(4, 1).astype(np.float32)}
    erased = []
    orig = fluid.Scope.erase

    def spy(self, name):
        erased.append(name)
        return orig(self, name)

    set_flags({"memory_optimize": True})
    fluid.Scope.erase = spy
    try:
        out, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                       compiled=False)
    finally:
        fluid.Scope.erase = orig
    assert erased, "no dead vars were freed on the interpreter path"
    assert loss.name not in erased
    assert np.isfinite(np.asarray(out)).all()


# ---------------------------------------------------------------------------
# memory_optimize flag: end-to-end equivalence
# ---------------------------------------------------------------------------


def _train_params(build, feeds, flag, steps=5):
    set_flags({"memory_optimize": flag})
    fw.reset_unique_names()
    main, startup, loss = build()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    for i in range(steps):
        f = feeds[i % len(feeds)]
        exe.run(main, feed=f, fetch_list=[loss], scope=scope)
        exe.run(main, feed=f, fetch_list=[loss], scope=scope,
                compiled=False)
    return {v.name: np.asarray(scope.find_var(v.name)).copy()
            for v in main.global_block().all_parameters()}


def test_memory_optimize_params_bit_identical():
    """Donation + rename + dead-var freeing must not change a single
    bit of the trained parameters vs the unoptimized step, across the
    book-style builders, in BOTH executor modes."""
    r = np.random.RandomState(0)
    mlp_feeds = [{"x": r.rand(4, 16).astype(np.float32),
                  "y": r.rand(4, 1).astype(np.float32)}
                 for _ in range(3)]
    conv_feeds = [{"img": r.rand(4, 1, 8, 8).astype(np.float32),
                   "label": r.randint(0, 10, (4, 1)).astype(np.int64)}
                  for _ in range(3)]
    for build, feeds in ((_build_mlp, mlp_feeds), (_build_conv,
                                                   conv_feeds)):
        ref = _train_params(build, feeds, False)
        got = _train_params(build, feeds, True)
        assert set(ref) == set(got)
        for name in ref:
            assert ref[name].tobytes() == got[name].tobytes(), name


def test_executor_auto_skips_fetch_vars():
    """memory_optimize invoked from the executor must not rename away
    the CURRENT fetch list (auto-skip), so fetching temporaries works."""
    set_flags({"memory_optimize": True})
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[8], dtype="float32")
        h = fluid.layers.fc(input=x, size=8, act="relu")
        out = fluid.layers.fc(input=h, size=1)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((2, 8), np.float32)}
    # fetch the INTERMEDIATE h on the interpreter path: it must survive
    hv, ov = exe.run(main, feed=feed, fetch_list=[h, out], scope=scope,
                     compiled=False)
    assert np.asarray(hv).shape == (2, 8)
    assert np.asarray(ov).shape == (2, 1)


def test_memory_optimize_skip_vars_mixed_shapes():
    """skip_vars accepts Variables and names uniformly, mixed in one
    list (callers pass both shapes today)."""
    main, _, loss = _build_mlp()
    h_names = [op.output("Out")[0] for op in main.global_block().ops
               if op.type == "relu"]
    memory_optimize(main, skip_vars=[loss, h_names[0]])
    survivors = set()
    for op in main.global_block().ops:
        for ns in op.outputs.values():
            survivors.update(ns)
    assert loss.name in survivors
    assert h_names[0] in survivors


# ---------------------------------------------------------------------------
# compile-churn pin (the recommender 85 s outlier)
# ---------------------------------------------------------------------------


def _lod_program():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        words = fluid.layers.data(name="words", shape=[1], dtype="int64",
                                  lod_level=1)
        emb = fluid.layers.embedding(input=words, size=[16, 8])
        pooled = fluid.layers.sequence_pool(input=emb, pool_type="sum")
        out = fluid.layers.reduce_mean(fluid.layers.fc(input=pooled,
                                                       size=1))
    return main, startup, out


def _lod_batch(r, lens, vocab=16):
    flat = r.randint(0, vocab, (int(np.sum(lens)), 1)).astype(np.int64)
    return {"words": fluid.create_lod_tensor(flat, [list(lens)])}


def test_bucketed_lod_recompiles_after_warmup_zero():
    """The BOOK_MATRIX_r05 recommender paid 85.3 s of compile for 2.3 s
    of training: every batch drew fresh random sequence lengths, and the
    executable cache keys on the LoD, so each batch was a new
    whole-program compile.  With ONE shared length pattern (run_book's
    fix) the steady-state loop must be recompile-free."""
    r = np.random.RandomState(0)
    main, startup, out = _lod_program()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    entries0 = exe.cache_stats()["entries"]  # the startup executable
    lens = r.randint(1, 5, 8)
    batches = [_lod_batch(r, lens) for _ in range(4)]
    for f in batches:  # warmup cycle: ONE executable for all batches
        exe.run(main, feed=f, fetch_list=[out], scope=scope)
    assert exe.cache_stats()["entries"] == entries0 + 1
    for _ in range(2):  # steady state
        for f in batches:
            exe.run(main, feed=f, fetch_list=[out], scope=scope)
    assert exe.cache_stats()["recompiles_after_warmup"] == 0

    # contrast: per-batch random lengths are the churn signature
    churn = [_lod_batch(r, r.randint(1, 5, 8)) for _ in range(3)]
    for f in churn:
        exe.run(main, feed=f, fetch_list=[out], scope=scope)
    assert exe.cache_stats()["recompiles_after_warmup"] >= 2


# ---------------------------------------------------------------------------
# knobs: jit_granularity, remat
# ---------------------------------------------------------------------------


def test_jit_granularity_modes():
    main, startup, loss = _build_mlp()
    feed = {"x": np.random.rand(2, 16).astype(np.float32),
            "y": np.random.rand(2, 1).astype(np.float32)}

    def run_with(gran):
        set_flags({"jit_granularity": gran})
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        v, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        return float(np.asarray(v).reshape(-1)[0]), exe.cache_stats()

    v_block, s_block = run_with("block")
    v_op, s_op = run_with("op")
    v_seg, s_seg = run_with("segment")
    assert s_block["entries"] >= 1    # whole-block executable
    assert s_op["entries"] == 0       # pure interpreter: no executables
    assert s_seg["entries"] >= 1      # segment cache
    np.testing.assert_allclose(v_block, v_op, rtol=1e-5)
    np.testing.assert_allclose(v_block, v_seg, rtol=1e-5)


def test_remat_flag_default_for_builders():
    from paddle_tpu.models.resnet import resnet_cifar10

    def count_recompute(remat_flag):
        set_flags({"remat": remat_flag})
        fw.reset_unique_names()
        main, startup = fluid.Program(), fluid.Program()
        with fluid.program_guard(main, startup):
            img = fluid.layers.data(name="img", shape=[3, 8, 8],
                                    dtype="float32")
            resnet_cifar10(img, class_dim=4, depth=8)
        return sum(op.type == "recompute"
                   for op in main.global_block().ops)

    assert count_recompute(False) == 0
    assert count_recompute(True) > 0


def test_remat_flag_trains():
    """Flag-driven remat must still train (persistable BN stats survive
    the checkpointed segment)."""
    from paddle_tpu.models.resnet import resnet_cifar10

    set_flags({"remat": True})
    fw.reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[3, 8, 8],
                                dtype="float32")
        label = fluid.layers.data(name="label", shape=[1], dtype="int64")
        pred = resnet_cifar10(img, class_dim=4, depth=8)
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=pred, label=label))
        fluid.Momentum(learning_rate=0.1, momentum=0.9).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    r = np.random.RandomState(0)
    feed = {"img": r.rand(4, 3, 8, 8).astype(np.float32),
            "label": r.randint(0, 4, (4, 1)).astype(np.int64)}
    vals = [float(np.asarray(exe.run(main, feed=feed, fetch_list=[loss],
                                     scope=scope)[0]).reshape(-1)[0])
            for _ in range(4)]
    assert all(np.isfinite(v) for v in vals)
    assert vals[-1] < vals[0]


# ---------------------------------------------------------------------------
# ParallelExecutor under the flag
# ---------------------------------------------------------------------------


def test_parallel_executor_memory_optimize_runs():
    set_flags({"memory_optimize": True})
    main, startup, loss = _build_mlp()
    pe = fluid.ParallelExecutor(main, ["x", "y"], [loss],
                                mesh={"dp": 2},
                                startup_program=startup)
    r = np.random.RandomState(0)
    feed = {"x": r.rand(8, 16).astype(np.float32),
            "y": r.rand(8, 1).astype(np.float32)}
    vals = [float(np.asarray(pe.run(feed)[0]).reshape(-1)[0])
            for _ in range(3)]
    assert all(np.isfinite(v) for v in vals)
    assert vals[-1] < vals[0]
    pe.close()
