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
                                 "state_commits": 0, "aux_dispatches": 0,
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


# ---------------------------------------------------------------------------
# the record of a compiled step's states (PR 39): a state the scope holds
# as the last step left it costs a dictionary read, everything else goes
# through `_commit` and `_aval_key` as before, for that state alone
# ---------------------------------------------------------------------------


def _records(exe, program=None):
    """The executor's step records: all (the startup program's run left
    one too), or those of `program`."""
    return [rec for by_program in exe._step_records.values()
            for prog, recs in by_program.items() for rec in recs.values()
            if program is None or prog is program]


def _warm(n=3, feed=None):
    main, startup, loss = _build_mlp()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    feed = _feed() if feed is None else feed
    for _ in range(n):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    return main, loss, exe, scope, feed


def _step_commits(exe, main, loss, scope, feed):
    """(loss, states the step put through `_commit`)."""
    before = exe.cache_stats()["state_commits"]
    out, = exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    return out, exe.cache_stats()["state_commits"] - before


_STATES = ("fc_0.b_0", "fc_0.w_0", "fc_1.b_0", "fc_1.w_0",
           "learning_rate_0")


@pytest.mark.parametrize("numpy_held", [(), ("learning_rate_0",)],
                         ids=["device_states", "numpy_lr"])
@pytest.mark.parametrize("device_feeds", [False, True],
                         ids=["numpy_feeds", "device_feeds"])
def test_warm_step_commits_only_what_may_have_changed(monkeypatch,
                                                      device_feeds,
                                                      numpy_held):
    """After warm-up a step recommits no state the scope holds as a jax
    array, and `jax.device_put` is called once a feed and once a state
    held as a NumPy array (whose owner may have written into it: the
    learning rate, which no step writes, can stay one)."""
    feed = _feed()
    if device_feeds:
        feed = {k: jax.device_put(v) for k, v in feed.items()}
    main, loss, exe, scope, feed = _warm(feed=feed)
    assert sorted(_records(exe, main)[-1].state_in_names) == sorted(_STATES)
    for n in numpy_held:
        scope.set_var(n, np.asarray(scope.find_var(n)))
    _step_commits(exe, main, loss, scope, feed)  # sees the NumPy values
    calls = []
    real = jax.device_put
    monkeypatch.setattr(jax, "device_put",
                        lambda *a, **k: calls.append(1) or real(*a, **k))
    for _ in range(3):
        del calls[:]
        _, commits = _step_commits(exe, main, loss, scope, feed)
        assert commits == len(numpy_held)
        assert len(calls) == len(feed) + len(numpy_held)
    monkeypatch.undo()
    s = exe.cache_stats()
    assert s["recompiles_after_warmup"] == 0, s
    assert s["entries"] == 2, s
    assert all(size == 1 for size in _jit_cache_sizes(exe))


def _build_momentum_dropout():
    main, startup = fluid.Program(), fluid.Program()
    main.seed = startup.seed = 7
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=32, act="relu")
        h = fluid.layers.dropout(h, dropout_prob=0.3)
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=p, label=y))
        fluid.Momentum(learning_rate=0.05, momentum=0.9).minimize(loss)
    return main, startup, loss


@pytest.mark.parametrize("build", [_build_mlp, _build_momentum_dropout],
                         ids=["sgd", "momentum_dropout"])
def test_ten_steps_bit_identical_to_a_fresh_executor_every_step(build):
    """The record changes no number: ten steps through one Executor give
    the losses and the parameters, bit for bit, of ten steps that each
    take a new Executor on the same scope (no record: every state
    through `_commit` and `_aval_key`)."""
    from paddle_tpu.core.framework import reset_unique_names

    def run(fresh_every_step):
        reset_unique_names()
        main, startup, loss = build()
        scope = fluid.Scope()
        exe = fluid.Executor(fluid.CPUPlace())
        exe.run(startup, scope=scope)
        losses = []
        for i in range(10):
            if fresh_every_step:
                exe = fluid.Executor(fluid.CPUPlace())
                exe._step = i + 1  # the step's PRNG key follows the count
            losses.append(exe.run(main, feed=_feed(i), fetch_list=[loss],
                                  scope=scope)[0])
        if not fresh_every_step:
            assert exe.cache_stats()["state_commits"] == \
                len(_records(exe, main)[-1].state_in_names)
        return losses, {n: np.asarray(scope.find_var(n))
                        for n in scope.local_names()}

    losses, params = run(False)
    want_losses, want_params = run(True)
    assert [v.tobytes() for v in losses] == \
        [v.tobytes() for v in want_losses]
    assert params.keys() == want_params.keys() and len(params) >= 5
    for n, v in params.items():
        assert v.tobytes() == want_params[n].tobytes(), n


@pytest.mark.parametrize("as_numpy", [False, True],
                         ids=["jax_array", "numpy_array"])
def test_replaced_state_is_used_and_alone_recommitted(as_numpy):
    """`scope.set_var(name, new)` with the same shape: the next step
    computes with the new value, puts that one state through `_commit`
    and compiles nothing."""
    main, loss, exe, scope, feed = _warm()
    zero = np.zeros((32, 1), np.float32)
    scope.set_var("fc_1.w_0", zero if as_numpy else jax.numpy.asarray(zero))
    bias = np.asarray(scope.find_var("fc_1.b_0"))
    out, commits = _step_commits(exe, main, loss, scope, feed)
    assert commits == 1
    # with the last layer's weight zero the prediction is its bias
    want = np.mean((bias.reshape(1, 1) - feed["y"]) ** 2)
    np.testing.assert_allclose(out, want, rtol=1e-6)
    # the step wrote a device array in its place: nothing to commit
    assert _step_commits(exe, main, loss, scope, feed)[1] == 0
    s = exe.cache_stats()
    assert s["recompiles_after_warmup"] == 0 and s["entries"] == 2, s


def test_state_replaced_by_another_shape_recompiles_once_and_warns():
    from paddle_tpu.core.flags import set_flags

    main, loss, exe, scope, feed = _warm()
    lr = np.asarray(scope.find_var("learning_rate_0"))
    assert lr.shape == (1,)
    scope.set_var("learning_rate_0", jax.numpy.asarray(lr.reshape(())))
    set_flags({"log_recompiles": True})
    try:
        with pytest.warns(RuntimeWarning, match="recompile after warmup"):
            _, commits = _step_commits(exe, main, loss, scope, feed)
    finally:
        set_flags({"log_recompiles": False})
    assert commits == 1
    assert _step_commits(exe, main, loss, scope, feed)[1] == 0
    s = exe.cache_stats()
    assert s["recompiles_after_warmup"] == 1 and s["entries"] == 3, s


def test_numpy_state_written_in_place_is_seen_at_the_next_step():
    """A NumPy array in the scope is read anew at every step: writing
    0 into the learning rate IN PLACE stops the parameters."""
    main, loss, exe, scope, feed = _warm()
    lr = np.array(np.asarray(scope.find_var("learning_rate_0")))
    scope.set_var("learning_rate_0", lr)
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    moved = np.asarray(scope.find_var("fc_0.w_0")).copy()
    lr[...] = 0.0
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert scope.find_var("learning_rate_0") is lr
    np.testing.assert_array_equal(np.asarray(scope.find_var("fc_0.w_0")),
                                  moved)


@pytest.mark.parametrize("how", ["erased", "none"])
def test_removed_state_raises_run_the_startup_program_first(how):
    main, loss, exe, scope, feed = _warm()
    if how == "erased":
        scope.erase("fc_0.w_0")
    else:
        scope.set_var("fc_0.w_0", None)
    with pytest.raises(RuntimeError, match="'fc_0.w_0' has no value in "
                       "scope — run the startup program first"):
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
    assert _records(exe, main) == []


def _mutate_program(main, scope):
    main.bump_version()
    return scope


def _second_scope(main, scope):
    other = fluid.Scope()
    for n in scope.local_names():
        other.set_var(n, jax.numpy.array(scope.find_var(n)))
    return other


def _toggle_memory_optimize(main, scope):
    from paddle_tpu.core.flags import set_flags

    set_flags({"memory_optimize": True})
    return scope


def _change_trace_flags(main, scope):
    from paddle_tpu.core.flags import set_flags

    set_flags({"flash_min_seq_k": 12345})
    return scope


@pytest.mark.parametrize("change", [_mutate_program, _second_scope,
                                    _toggle_memory_optimize,
                                    _change_trace_flags])
def test_what_differs_gets_a_record_of_its_own(change):
    """One record a (program version, scope, flags): the old one stays
    as it was and serves again when its call comes back."""
    from paddle_tpu.core.flags import get_flag, set_flags

    saved = {n: get_flag(n) for n in ("memory_optimize", "flash_min_seq_k")}
    main, loss, exe, scope, feed = _warm()
    (first,) = _records(exe, main)
    try:
        scope2 = change(main, scope)
        _, commits = _step_commits(exe, main, loss, scope2, feed)
        assert commits == len(_STATES)   # a new record knows no state
        assert len(_records(exe, main)) == 2
        assert first in _records(exe, main)
        assert _step_commits(exe, main, loss, scope2, feed)[1] == 0
    finally:
        set_flags(saved)
    if change is not _mutate_program:    # a version never comes back
        # the first call again: its record is still there, and holds what
        # the scope holds unless the other call wrote the same scope
        _, commits = _step_commits(exe, main, loss, scope, feed)
        assert commits == (0 if change is _second_scope else 4)
        assert len(_records(exe, main)) == 2
        assert first in _records(exe, main)


@pytest.mark.parametrize("failure", ["trace", "call"])
def test_failed_compiled_run_leaves_no_record(failure):
    """Any exception inside the step drops its record: the next good
    run starts from the scope, as a first run does, and still hits the
    executable it compiled before."""
    main, loss, exe, scope, feed = _warm()
    assert len(_records(exe, main)) == 1
    # a feed the trace refuses (a width the first layer cannot take), or
    # one the jitted call itself refuses (no array at all)
    bad = {"x": np.ones((8, 5), np.float32) if failure == "trace"
           else object(), "y": feed["y"]}
    with pytest.raises(Exception):
        exe.run(main, feed=bad, fetch_list=[loss], scope=scope)
    assert _records(exe, main) == []
    assert scope.kids == []
    misses = exe.cache_stats()["misses"]
    _, commits = _step_commits(exe, main, loss, scope, feed)
    assert commits == len(_STATES)
    assert exe.cache_stats()["misses"] == misses
    assert _step_commits(exe, main, loss, scope, feed)[1] == 0


@pytest.mark.parametrize("what", ["scope", "program"])
def test_executor_keeps_neither_scope_nor_program_alive(what):
    """The table of records holds scope and program weakly, and a
    record names neither: a scope out of reach goes with its arrays;
    a program goes once `close()` has dropped the executables that
    close over its blocks (as `test_fp_cache_dropped_with_program`)."""
    import gc
    import weakref

    main, loss, exe, scope, feed = _warm()
    assert len(_records(exe, main)) == 1
    if what == "scope":
        ref = weakref.ref(scope)
        arr = weakref.ref(scope.find_var("fc_0.w_0"))
        del scope
    else:
        from paddle_tpu import profiler

        ref, arr = weakref.ref(main), None
        exe._cache.clear()
        profiler.reset_profiler()
        del main, loss
    gc.collect()
    assert ref() is None
    assert arr is None or arr() is None
    assert _records(exe) == []


# ---------------------------------------------------------------------------
# the serial section of a step (PR 61): a compiled step makes its key from
# two traced scalars, the fetched arrays' readback starts at dispatch, and
# the step's arguments go to the jitted call as tuples
# ---------------------------------------------------------------------------


def _reference_steps(main, loss, states, seeds, feeds):
    """The steps with the key made OUTSIDE the step, as `run` made it
    before PR 61: `fold_in(key(seed), step)` on the host's side, handed
    to a jitted `program_to_fn` as an argument.  Step i (from 1: the
    startup program's run was step 0) takes `seeds[i - 1]`."""
    from paddle_tpu.core.executor import program_to_fn

    fn = program_to_fn(main, ["x", "y"], [loss.name])
    step = jax.jit(fn)
    losses = []
    for i, (seed, feed) in enumerate(zip(seeds, feeds), start=1):
        key = jax.random.fold_in(jax.random.key(seed), i)
        fetches, states = step(feed, states, key)
        losses.append(np.asarray(fetches[loss.name]))
    return losses, {n: np.asarray(v) for n, v in states.items()}


@pytest.mark.parametrize("seeding", ["program_seed", "executor_seed",
                                     "seed_changed_between_runs",
                                     "seed_past_32_bits"])
def test_compiled_step_makes_the_key_the_host_made(seeding):
    """Ten compiled steps of a dropout + momentum Program, their key made
    inside the step from the seed and the count, are bit for bit the
    steps whose key is made outside: same masks, same losses, same
    parameters.  Seed and count are traced: ten steps, and a seed that
    changes on the way, are ONE executable."""
    from paddle_tpu.core.framework import reset_unique_names

    reset_unique_names()
    main, startup, loss = _build_momentum_dropout()
    exe_seed = 0
    if seeding == "executor_seed":
        main.seed, exe_seed = 0, 1234
    elif seeding == "seed_past_32_bits":
        main.seed = 2 ** 32 + 2 ** 31 + 5
    exe = fluid.Executor(fluid.CPUPlace(), seed=exe_seed)
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    states = {n: np.asarray(scope.find_var(n))
              for n in scope.local_names()}
    feeds = [_feed(i) for i in range(10)]
    seeds, losses = [], []
    for i, feed in enumerate(feeds):
        if seeding == "seed_changed_between_runs" and i == 5:
            main.seed = 99          # no part of the executable's key
        seeds.append(main.seed or exe_seed)
        losses.append(exe.run(main, feed=feed, fetch_list=[loss],
                              scope=scope)[0])
    want_losses, want_states = _reference_steps(main, loss, states, seeds,
                                                feeds)
    assert [v.tobytes() for v in losses] == \
        [v.tobytes() for v in want_losses]
    assert len(want_states) >= 9
    for n, v in want_states.items():
        assert np.asarray(scope.find_var(n)).tobytes() == v.tobytes(), n
    # a seed and a count are arguments, not constants of the trace
    s = exe.cache_stats()
    assert (s["misses"], s["hits"]) == (2, 9), s     # startup + main
    assert s["recompiles_after_warmup"] == 0, s
    assert all(size == 1 for size in _jit_cache_sizes(exe))
    assert s["aux_dispatches"] == 0, s
    # and the masks differ from step to step (the count reaches the key)
    assert len({v.tobytes() for v in losses}) == 10


def _dropout_of_ones():
    """A Program whose fetch IS its dropout mask."""
    main, startup = fluid.Program(), fluid.Program()
    main.seed = startup.seed = 11
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[64], dtype="float32")
        out = fluid.layers.dropout(x, dropout_prob=0.5)
    return main, out


@pytest.mark.parametrize("mode", ["interpreted", "segmented"])
def test_eager_modes_draw_the_stream_they_drew(mode):
    """The interpreted and the segmented paths still make the step key
    on the host's side (two dispatches a run, counted), and it is the
    key a compiled step makes inside itself: the same mask at the same
    step, a different one at the next."""
    from paddle_tpu.core.flags import set_flags

    main, out = _dropout_of_ones()
    feed = {"x": np.ones((4, 64), np.float32)}

    def masks(compiled, granularity="block"):
        exe = fluid.Executor(fluid.CPUPlace())
        set_flags({"jit_granularity": granularity})
        try:
            got = [exe.run(main, feed=feed, fetch_list=[out],
                           scope=fluid.Scope(), compiled=compiled)[0]
                   for _ in range(3)]
        finally:
            set_flags({"jit_granularity": "block"})
        return got, exe.cache_stats()["aux_dispatches"]

    want, aux = masks(True)
    assert aux == 0
    got, aux = (masks(False) if mode == "interpreted"
                else masks(True, "segment"))
    assert aux == 2 * 3
    assert [v.tobytes() for v in got] == [v.tobytes() for v in want]
    assert len({v.tobytes() for v in want}) == 3
    assert set(np.unique(want[0])) == {0.0, 1.0}     # it IS the mask


def test_warm_compiled_step_sends_nothing_but_the_step(monkeypatch):
    """`aux_dispatches`: 0 in `cache_stats()` and on the `executor.run`
    span of a compiled step (warm or first), 2 on an interpreted one;
    and a warm step calls neither `jax.random.key` nor `fold_in`."""
    from paddle_tpu.observability import tracing

    main, loss, exe, scope, feed = _warm()
    assert exe.cache_stats()["aux_dispatches"] == 0
    calls = []
    for name in ("key", "fold_in"):
        real = getattr(jax.random, name)
        monkeypatch.setattr(
            jax.random, name,
            lambda *a, _real=real, _n=name, **k: calls.append(_n)
            or _real(*a, **k))
    spans = []
    tracing.add_span_listener(spans.append)
    try:
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope)
        assert calls == []
        exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                compiled=False)
        assert sorted(set(calls)) == ["fold_in", "key"]
    finally:
        tracing.remove_span_listener(spans.append)
        tracing.clear()
    runs = [(s["attrs"]["mode"], s["attrs"]["aux_dispatches"])
            for s in spans if s["name"] == "executor.run"]
    assert runs == [("compiled", 0), ("interpreted", 2)]
    assert exe.cache_stats()["aux_dispatches"] == 2


@pytest.mark.parametrize("return_numpy", [True, False])
def test_a_fetch_is_the_devices_bytes_either_way(return_numpy):
    """`return_numpy=True` returns NumPy arrays of the device values'
    dtype and bytes, `False` the device arrays themselves, a large
    fetch (4 MiB) like a scalar: the step's arguments and its key
    changed their form in PR 61, what it returns did not."""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[512], dtype="float32")
        h = fluid.layers.fc(input=x, size=2048, act="relu")   # 4 MiB out
        small = fluid.layers.mean(h)
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    feed = {"x": np.random.RandomState(3).rand(512, 512).astype(np.float32)}
    w, b = (np.asarray(scope.find_var(n)) for n in ("fc_0.w_0", "fc_0.b_0"))
    got = exe.run(main, feed=feed, fetch_list=[h, small], scope=scope,
                  return_numpy=return_numpy)
    if return_numpy:
        assert all(type(v) is np.ndarray for v in got)
    else:
        assert all(isinstance(v, jax.Array) for v in got)
    assert got[0].shape == (512, 2048) and got[0].nbytes == 4 << 20
    assert all(v.dtype == np.float32 for v in got)
    want = np.maximum(feed["x"] @ w + b, 0)
    np.testing.assert_allclose(np.asarray(got[0]), want, rtol=1e-4,
                               atol=1e-5)
    other = exe.run(main, feed=feed, fetch_list=[h, small], scope=scope,
                    return_numpy=not return_numpy)
    for g, o in zip(got, other):
        assert np.asarray(g).tobytes() == np.asarray(o).tobytes()
