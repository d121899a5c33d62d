"""Executor compiles each (program, shapes, amp) config EXACTLY once.

Regression for the r2 double-compile: jax.jit's internal cache keys on
argument committed-ness, and startup outputs (uncommitted) vs donated
step outputs (committed) differed, so the second `exe.run` of an
identical config re-traced and re-compiled the whole program on every
training loop's startup.  The fix
(`core/executor.py:_commit`) normalizes state commitment before calling
the jitted fn; these tests pin one-compile-per-config across numpy
feeds, device-array feeds, and amp on/off.
"""
import os
import time

import jax
import numpy as np
import pytest

import paddle_tpu as fluid


def _build_mlp():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=32, act="relu")
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=p, label=y))
        fluid.SGD(learning_rate=0.01).minimize(loss)
    return main, startup, loss


def _feed(seed=0):
    r = np.random.RandomState(seed)
    return {"x": r.rand(8, 16).astype(np.float32),
            "y": r.rand(8, 1).astype(np.float32)}


def _jit_cache_sizes(exe):
    """Per-executable trace/compile counts inside jax.jit's own cache —
    the executor-level dict can look correct while jit silently
    re-compiles underneath it."""
    return [fn._cache_size() for fn in exe._cache.values()
            if hasattr(fn, "_cache_size")]


def _run_steps(exe, main, loss, scope, feeds):
    times = []
    for feed in feeds:
        t0 = time.perf_counter()
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        times.append(time.perf_counter() - t0)
    return times


@pytest.mark.parametrize("device_feeds", [False, True],
                         ids=["numpy_feeds", "device_feeds"])
def test_single_compile_per_config(device_feeds):
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)

    feed = _feed()
    if device_feeds:
        feed = {k: jax.device_put(v) for k, v in feed.items()}
    times = _run_steps(exe, main, loss, scope, [feed] * 4)

    # one executor cache entry for main (startup has its own), and every
    # jitted fn traced/compiled exactly once
    assert all(size == 1 for size in _jit_cache_sizes(exe)), \
        _jit_cache_sizes(exe)
    # wall-clock corroboration: steps 1..3 are steady-state dispatches,
    # not recompiles (step 0 pays the only compile)
    assert max(times[1:]) < times[0]


def test_single_compile_amp():
    """The amp (bf16 compute, f32 master weights) config also compiles
    exactly once — amp must be enabled at BUILD time (layer_helper
    creates the master params), so this builds a fresh program under
    amp rather than toggling the flag on an existing one."""
    fluid.amp.enable_bf16()
    try:
        main, startup, loss = _build_mlp()
        exe = fluid.Executor(fluid.CPUPlace())
        scope = fluid.Scope()
        exe.run(startup, scope=scope)
        _run_steps(exe, main, loss, scope, [_feed()] * 3)
        assert all(size == 1 for size in _jit_cache_sizes(exe)), \
            _jit_cache_sizes(exe)
    finally:
        fluid.amp.disable_bf16()


def test_single_compile_fresh_executor_same_scope():
    """A second Executor over the same trained scope (committed device
    arrays) also compiles once — covers the states-already-on-device
    entry path."""
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    _run_steps(exe, main, loss, scope, [_feed()] * 2)

    exe2 = fluid.Executor(fluid.CPUPlace())
    _run_steps(exe2, main, loss, scope, [_feed()] * 3)
    assert all(size == 1 for size in _jit_cache_sizes(exe2)), \
        _jit_cache_sizes(exe2)


# ---------------------------------------------------------------------------
# cache_stats telemetry
# ---------------------------------------------------------------------------


def test_cache_stats_counters_and_steady_state():
    """hits/misses/compile_s accounting: startup + main each miss once,
    every further step of the same config is a hit, and the steady-state
    training loop adds ZERO misses."""
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    assert exe.cache_stats() == {"hits": 0, "misses": 0, "compile_s": 0.0,
                                 "recompiles_after_warmup": 0,
                                 "entries": 0}
    exe.run(startup, scope=scope)
    _run_steps(exe, main, loss, scope, [_feed()] * 5)
    s = exe.cache_stats()
    assert s["misses"] == 2, s      # startup + first main step
    assert s["hits"] == 4, s        # steps 2..5
    assert s["entries"] == 2, s
    assert s["compile_s"] > 0, s
    assert s["recompiles_after_warmup"] == 0, s
    # steady state: more identical steps are pure hits — no misses
    _run_steps(exe, main, loss, scope, [_feed()] * 3)
    s2 = exe.cache_stats()
    assert s2["misses"] == 2, s2
    assert s2["hits"] == 7, s2
    assert s2["compile_s"] == s["compile_s"], s2


def test_recompile_after_warmup_counted_and_warned():
    """A shape change on a warm program counts as a post-warmup recompile
    and (with PADDLE_TPU_LOG_RECOMPILES) emits a RuntimeWarning naming
    the cache-key divergence."""
    from paddle_tpu.core.flags import set_flags

    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    _run_steps(exe, main, loss, scope, [_feed()] * 3)  # warm
    r = np.random.RandomState(1)
    odd_feed = {"x": r.rand(3, 16).astype(np.float32),  # new batch size
                "y": r.rand(3, 1).astype(np.float32)}
    set_flags({"log_recompiles": True})
    try:
        with pytest.warns(RuntimeWarning, match="recompile after warmup"):
            exe.run(main, feed=odd_feed, fetch_list=[loss], scope=scope)
    finally:
        set_flags({"log_recompiles": False})
    s = exe.cache_stats()
    assert s["recompiles_after_warmup"] == 1, s
    # without the flag the event is still counted, silently
    even_odder = {"x": r.rand(5, 16).astype(np.float32),
                  "y": r.rand(5, 1).astype(np.float32)}
    exe.run(main, feed=even_odder, fetch_list=[loss], scope=scope)
    assert exe.cache_stats()["recompiles_after_warmup"] == 2


def test_recompile_counter_segmented_counts_once_per_run():
    """A segmented program (host op between device segments) looks up
    one executable per segment, but one odd-shaped batch is ONE hot-path
    re-trace — the counter must not inflate to k."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        h = fluid.layers.fc(input=x, size=4)
        h = fluid.layers.Print(h)  # host op -> 2 device segments
        fluid.layers.mean(h)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    feed_a = {"x": np.ones((2, 4), np.float32)}
    exe.run(main, feed=feed_a, scope=scope)
    exe.run(main, feed=feed_a, scope=scope)  # warm (segment hits)
    before = exe.cache_stats()
    assert before["recompiles_after_warmup"] == 0, before
    exe.run(main, feed={"x": np.ones((3, 4), np.float32)}, scope=scope)
    after = exe.cache_stats()
    assert after["entries"] - before["entries"] >= 2, (before, after)
    assert after["recompiles_after_warmup"] == 1, after


def test_compile_cache_placed_from_outside_is_left_alone(compile_cache):
    """With JAX_COMPILATION_CACHE_DIR set the resolver names that
    directory and assigns nothing: JAX's own setting stands, and the
    Executor's compiles land there — executables survive restarts."""
    from paddle_tpu.core.compile_cache import compile_cache_dir

    assert compile_cache_dir() == compile_cache
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=_feed(), fetch_list=[loss], scope=scope)
    assert jax.config.jax_compilation_cache_dir == compile_cache
    assert os.listdir(compile_cache), "no persistent cache entries written"


def test_compile_cache_default_is_the_fixed_checkout_path(monkeypatch):
    """Unset, the one resolver arms <checkout>/.jax_cache — a fixed,
    git-ignored path beside the package, the same for every process
    that should share executables."""
    import paddle_tpu
    from paddle_tpu.core import compile_cache

    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(paddle_tpu.__file__)))
    prev = jax.config.jax_compilation_cache_dir
    try:
        assert compile_cache.compile_cache_dir() == os.path.join(
            checkout, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == os.path.join(
            checkout, ".jax_cache")
        with open(os.path.join(checkout, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_tpu_place_has_no_cpu_stand_in():
    """On a process with no TPU backend TPUPlace raises, naming the
    reason; the CPU is reached only through CPUPlace."""
    with pytest.raises(RuntimeError, match="no TPU backend"):
        fluid.TPUPlace().jax_device()
    assert fluid.CPUPlace().jax_device().platform == "cpu"


# ---------------------------------------------------------------------------
# satellite regressions: fp-cache lifetime + local-scope leak
# ---------------------------------------------------------------------------


def test_fp_cache_dropped_with_program():
    """The fingerprint cache is weakref-keyed: once the program (and the
    executables closing over its blocks) are gone, no stale entry keyed
    by a reusable id() survives."""
    import gc

    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    main, startup, loss = _build_mlp()
    exe.run(startup, scope=scope)
    _run_steps(exe, main, loss, scope, [_feed()] * 2)
    assert len(exe._fp_cache) >= 1
    exe.close()  # drop the executables (their closures hold the blocks)
    # ... and the few that profiler.hlo_scopes() keeps past close() so
    # that a trace can be joined to their scopes after the run (PR 24)
    from paddle_tpu import profiler

    profiler.reset_profiler()
    del main, startup, loss
    gc.collect()
    assert len(exe._fp_cache) == 0


def test_failed_run_does_not_leak_local_scope():
    """A raising run must not accumulate kid scopes — interpreted mode."""
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    for _ in range(3):
        with pytest.raises(KeyError, match="never produced"):
            exe.run(main, feed=_feed(), fetch_list=["no_such_var"],
                    scope=scope, compiled=False)
    assert scope.kids == [], "interpreted mode leaked local scopes"


def test_failed_run_does_not_leak_local_scope_segmented():
    """Same regression on the segmented path (host op in the program
    forces it): the failing fetch must release the per-run scope."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[4], dtype="float32")
        h = fluid.layers.fc(input=x, size=4)
        h = fluid.layers.Print(h)  # host op -> segmented execution
        fluid.layers.mean(h)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.ones((2, 4), np.float32)}
    for _ in range(3):
        with pytest.raises(KeyError, match="never produced"):
            exe.run(main, feed=feed, fetch_list=["no_such_var"],
                    scope=scope)
    assert scope.kids == [], "segmented mode leaked local scopes"
