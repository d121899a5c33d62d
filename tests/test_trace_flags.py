"""The flags that re-key an executable are listed once
(core.flags.trace_flags) and every executor follows that list.

A flag read while a step is traced or built changes the program and
leaves the input avals alone, so an executor that does not key on it
serves a stale executable in silence.  Each case here flips ONE flag of
the list on ONE executor: an executor that keeps a list of its own
fails as soon as the two differ.  The last test pins that the five
flags this list used to carry are gone by name.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import parallel
from paddle_tpu.core import flags
from paddle_tpu.core import framework as fw
from paddle_tpu.core.flags import get_flag, set_flags


@pytest.fixture(autouse=True)
def _restore_flags():
    keep = {k: get_flag(k)
            for k in flags.PARALLEL_TRACE_FLAGS + ("jit_granularity",)}
    yield
    set_flags(keep)


def _flipped(name):
    v = get_flag(name)
    return (not v) if isinstance(v, bool) else v + 1


def _mlp():
    fw.reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=16, act="relu")
        pred = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=pred, label=y))
        fluid.SGD(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _feed(batch=8):
    r = np.random.RandomState(0)
    return {"x": r.rand(batch, 16).astype(np.float32),
            "y": r.rand(batch, 1).astype(np.float32)}


def test_the_list_is_the_flags_it_names():
    assert set(flags.TRACE_FLAGS) <= set(flags.PARALLEL_TRACE_FLAGS)
    assert set(flags.PARALLEL_TRACE_FLAGS) <= set(flags.flag_defaults())
    assert flags.trace_flags() == tuple(
        get_flag(n) for n in flags.TRACE_FLAGS)
    assert flags.trace_flags(parallel=True) == tuple(
        get_flag(n) for n in flags.PARALLEL_TRACE_FLAGS)


@pytest.mark.parametrize("granularity", ["block", "segment"])
@pytest.mark.parametrize("name", flags.TRACE_FLAGS)
def test_executor_rekeys_on_each_trace_flag(name, granularity):
    """Both caches of the serial Executor: a flip is a miss and a new
    entry, the flip back finds the old executable again."""
    set_flags({"jit_granularity": granularity})
    main, startup, loss = _mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)

    def run():
        v, = exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
        assert np.isfinite(np.asarray(v)).all()
        st = exe.cache_stats()
        return st["misses"], st["entries"]

    before = get_flag(name)
    misses0, entries0 = run()
    assert run() == (misses0, entries0)           # steady state
    set_flags({name: _flipped(name)})
    misses1, entries1 = run()
    assert misses1 > misses0 and entries1 > entries0
    set_flags({name: before})
    assert run() == (misses1, entries1)           # the old one, found


@pytest.mark.parametrize("name", flags.PARALLEL_TRACE_FLAGS)
def test_parallel_executor_rebuilds_on_each_trace_flag(name):
    main, startup, loss = _mlp()
    pe = fluid.ParallelExecutor(main, ["x", "y"], [loss],
                                mesh={"dp": 2}, startup_program=startup)
    try:
        def run():
            assert np.isfinite(np.asarray(pe.run(_feed())[0])).all()
            return pe._jit_step

        step0 = run()
        assert run() is step0
        before = get_flag(name)
        set_flags({name: _flipped(name)})
        step1 = run()
        assert step1 is not step0
        set_flags({name: before})
        assert run() is not step1
    finally:
        pe.close()


@pytest.fixture(scope="module")
def pipeline_executor():
    from paddle_tpu.models.transformer import transformer_lm

    fw.reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[8], dtype="int64")
        lab = fluid.layers.data(name="lab", shape=[8, 1], dtype="int64")
        lg = transformer_lm(ids, 32, d_model=16, n_heads=2, n_layers=2,
                            max_len=8, return_logits=True,
                            dropout_rate=0.0, pipeline_stages=2)
        loss = fluid.layers.mean(
            fluid.layers.softmax_with_cross_entropy(
                fluid.layers.reshape(lg, shape=[-1, 32]),
                fluid.layers.reshape(lab, shape=[-1, 1])))
        fluid.SGD(learning_rate=0.05).minimize(loss)
    return parallel.PipelineExecutor(
        main, ["ids", "lab"], [loss], mesh={"dp": 1, "pp": 2},
        startup_program=startup, n_micro=2)


@pytest.mark.parametrize("name", flags.PARALLEL_TRACE_FLAGS)
def test_pipeline_executor_rebuilds_on_each_trace_flag(
        name, pipeline_executor):
    """`_refresh_trace_flags` is the first thing `run` does; called
    here on its own, so that no pipeline is compiled four times."""
    pe = pipeline_executor
    pe._refresh_trace_flags()
    step0 = pe._jit_step
    pe._refresh_trace_flags()
    assert pe._jit_step is step0
    set_flags({name: _flipped(name)})
    pe._refresh_trace_flags()
    assert pe._jit_step is not step0


@pytest.mark.parametrize("name", [
    "serving_kernels", "flash_pack_heads", "flash_block_q",
    "flash_block_k", "conv_layout", "serving_kv_dtype",
    "serving_spec_k"])
def test_deleted_flags_are_refused_by_name(name):
    """What each decided is now worked out from shape and platform
    where the kernel or the op lives (the last two: an argument of the
    builder and of the server, which every caller passes); nothing is
    left to set."""
    assert name not in flags.flag_defaults()
    with pytest.raises(KeyError, match=name):
        set_flags({name: get_flag("benchmark")})
    with pytest.raises(KeyError):
        get_flag(name)
