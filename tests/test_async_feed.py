"""Async training hot path (reader/pipeline.py + Trainer lazy fetches).

Covers the prefetch pipeline's contract (ordering, the bounded
read-ahead, exception propagation, clean shutdown), the LazyFetch
handle, `Trainer.train`'s one loop (one batch ahead by construction,
bit-identical to a plain serial `Executor.run` loop over the same reader,
the worker joined on every way out), and the host-bound overlap
microbench (perf marker): prefetch + lazy fetch must beat the serial
loop by >= 20% steps/s without a single post-warmup recompile.
"""
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import trainer as trainer_mod
from paddle_tpu.core.framework import reset_unique_names
from paddle_tpu.data_feeder import DataFeeder
from paddle_tpu.reader.pipeline import PrefetchIterator, prefetch_feeder


def _dict_reader(n, produced=None):
    """Reader of ready-made feed dicts (feeder=None mode)."""

    def reader():
        for i in range(n):
            if produced is not None:
                produced.append(i)
            yield {"i": np.full((2, 2), i, np.float32)}

    return reader


class TestPrefetchIterator:
    def test_order_and_values_match_serial(self):
        it = prefetch_feeder(_dict_reader(20), feeder=None,
                             place=fluid.CPUPlace(), depth=3)()
        got = [np.asarray(feed["i"]) for feed in it]
        assert len(got) == 20
        for i, arr in enumerate(got):
            np.testing.assert_array_equal(arr, np.full((2, 2), i,
                                                       np.float32))

    def test_reader_exception_propagates_after_good_batches(self):
        def bad():
            yield {"i": np.zeros(1, np.float32)}
            yield {"i": np.ones(1, np.float32)}
            raise IOError("source gone")

        it = PrefetchIterator(bad, feeder=None, place=fluid.CPUPlace(),
                              depth=2)
        assert float(np.asarray(next(it)["i"])[0]) == 0.0
        assert float(np.asarray(next(it)["i"])[0]) == 1.0
        with pytest.raises(IOError, match="source gone"):
            next(it)
        it.thread.join(timeout=5)
        assert not it.thread.is_alive()

    def test_feeder_exception_propagates(self):
        class BadFeeder:
            place = fluid.CPUPlace()

            def feed(self, batch):
                raise ValueError("cannot pack")

        it = PrefetchIterator(_dict_reader(3), feeder=BadFeeder(), depth=2)
        with pytest.raises(ValueError, match="cannot pack"):
            next(it)

    @pytest.mark.parametrize("depth", [1, 2, 5])
    def test_reads_exactly_depth_ahead_of_the_take(self, depth):
        """The reader is asked for batch n + depth at the take of batch
        n and no earlier: prepared or in preparation, `depth` batches
        are all the worker ever holds."""
        produced = []
        it = PrefetchIterator(_dict_reader(50, produced), feeder=None,
                              place=fluid.CPUPlace(), depth=depth)
        for taken in range(1, 4):
            next(it)
            deadline = time.monotonic() + 5.0
            while len(produced) < taken + depth \
                    and time.monotonic() < deadline:
                time.sleep(0.005)
            time.sleep(0.1)  # time to run further ahead, if it could
            assert produced == list(range(taken + depth)), produced
        it.close()
        assert not it.thread.is_alive()

    def test_handoff_holds_under_contention(self):
        """More streams than cores at a shortened switch interval: every
        consumer still sees its batches in order, and no reader is ever
        asked for more than one batch past its consumer's takes."""
        import os
        import sys

        n_streams, n_items = 2 * (os.cpu_count() or 4), 150
        errors = []

        def stream(k):
            taken = [0]

            def reader():
                for i in range(n_items):
                    if i > taken[0] + 1:  # asked for i with < i - 1 taken
                        errors.append((k, "ahead", i, taken[0]))
                    yield i

            it = PrefetchIterator(reader, feeder=None, device_put=False)
            try:
                for want in range(n_items):
                    got = next(it)
                    taken[0] = want + 1
                    if got != want:
                        errors.append((k, "order", want, got))
                if next(it, None) is not None:
                    errors.append((k, "no end"))
            except BaseException as e:  # surfaced below, not lost
                errors.append((k, repr(e)))
            finally:
                it.close()

        was = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            threads = [threading.Thread(target=stream, args=(k,))
                       for k in range(n_streams)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=60)
        finally:
            sys.setswitchinterval(was)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors[:5]
        assert not _workers()

    def test_take_reports_ready_and_wait(self):
        gate = threading.Event()

        def reader():
            yield {"i": np.zeros(1, np.float32)}
            gate.wait(5)
            yield {"i": np.ones(1, np.float32)}

        it = PrefetchIterator(reader, feeder=None, device_put=False)
        next(it)
        threading.Timer(0.1, gate.set).start()
        next(it)  # the worker was still inside the reader: a wait
        assert not it.last_ready and it.last_wait_s >= 0.03
        assert it.wait_s >= it.last_wait_s
        deadline = time.monotonic() + 5.0
        while it._q.empty() and time.monotonic() < deadline:
            time.sleep(0.005)
        with pytest.raises(StopIteration):
            next(it)  # the end was waiting for us
        assert it.last_ready and it.last_wait_s < 0.03

    def test_close_stops_worker_promptly(self):
        it = PrefetchIterator(_dict_reader(10_000), feeder=None,
                              place=fluid.CPUPlace(), depth=2)
        next(it)
        it.close()
        assert not it.thread.is_alive()
        with pytest.raises(StopIteration):
            next(it)

    def test_exhaustion_joins_worker(self):
        it = PrefetchIterator(_dict_reader(5), feeder=None,
                              place=fluid.CPUPlace(), depth=2)
        assert sum(1 for _ in it) == 5
        assert not it.thread.is_alive()

    def test_no_thread_leak_across_epochs(self):
        before = threading.active_count()
        feeds = prefetch_feeder(_dict_reader(8), feeder=None,
                                place=fluid.CPUPlace(), depth=2)
        for _ in range(3):  # one fresh iterator (thread) per epoch
            assert sum(1 for _ in feeds()) == 8
        assert threading.active_count() <= before + 1

    def test_depth_validation(self):
        with pytest.raises(ValueError, match="depth"):
            PrefetchIterator(_dict_reader(1), feeder=None, depth=0)

    def test_prefetch_feeder_is_lazy(self):
        """compose()/zip call every reader before consuming: a
        side-effecting source must not be drained at call time."""
        produced = []
        feeds = prefetch_feeder(_dict_reader(10, produced), feeder=None,
                                place=fluid.CPUPlace(), depth=2)()
        time.sleep(0.2)
        assert produced == [], produced  # nothing until first next()
        next(feeds)
        feeds.close()

    def test_abandoned_reader_is_collected(self):
        """Dropping the PrefetchReader without close() must stop the
        worker: the inner iterator is pinned by its own thread, the
        wrapper is not."""
        import gc

        workers = _workers

        feeds = prefetch_feeder(_dict_reader(10_000), feeder=None,
                                place=fluid.CPUPlace(), depth=2)()
        next(feeds)
        assert len(workers()) == 1
        del feeds  # abandoned mid-stream, no close()
        gc.collect()
        deadline = time.monotonic() + 2.0
        while workers() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert not workers(), "abandoned prefetch worker leaked"


class TestLazyFetch:
    def test_reads_and_formatting(self):
        lf = trainer_mod.LazyFetch(np.asarray([[2.5]], np.float32))
        assert "in flight" in repr(lf)
        assert float(lf) == 2.5
        assert np.asarray(lf).shape == (1, 1)
        assert f"{lf:.2f}" == "2.50"
        assert "2.5" in repr(lf)  # materialized now
        # plain interpolation is a read too: format(x, "") == str(x)
        lf2 = trainer_mod.LazyFetch(np.asarray([1.25], np.float32))
        assert f"{lf2}" == "1.25"

    def test_value_does_not_materialize(self):
        import jax.numpy as jnp

        dev = jnp.ones((2,))
        lf = trainer_mod.LazyFetch(dev)
        assert lf.value() is dev
        assert "in flight" in repr(lf)
        # materialization releases the device buffer (a pass of retained
        # handles must not pin one device array per step)
        lf.numpy()
        assert lf._device_value is None
        np.testing.assert_array_equal(np.asarray(lf.value()),
                                      np.ones((2,)))

    def test_float_like_protocol(self):
        """Existing handlers compare/accumulate/print event.cost — the
        operators must work, each one being a materialization point."""
        lf = trainer_mod.LazyFetch(np.asarray([3.0], np.float32))
        other = trainer_mod.LazyFetch(np.asarray([1.5], np.float32))
        assert lf < 4.0 and lf <= 3.0 and lf > 2.0 and lf >= 3.0
        assert lf == 3.0 and lf != 2.0
        assert lf < trainer_mod.LazyFetch(np.asarray([5.0], np.float32))
        assert lf + 1.0 == 4.0 and 1.0 + lf == 4.0
        assert lf - other == 1.5 and 4.5 - lf == 1.5
        assert lf * 2 == 6.0 and lf / 2 == 1.5 and 6.0 / lf == 2.0
        assert -lf == -3.0 and abs(-lf) == 3.0  # noqa: B002
        assert str(lf) == "3.0"
        assert bool(lf) and hash(lf) == hash(3.0)
        total = sum([lf, other])  # the classic pass-cost accumulator
        assert total == 4.5


# ---------------------------------------------------------------------------
# Trainer integration
# ---------------------------------------------------------------------------


def _deterministic_data(n_batches=6, bs=8, dim=16, seed=7):
    r = np.random.RandomState(seed)
    return [[(r.rand(dim).astype(np.float32),
              r.rand(1).astype(np.float32)) for _ in range(bs)]
            for _ in range(n_batches)]


def _mlp(dim=16):
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[dim], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=24, act="relu")
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=p, label=y))
        fluid.SGD(learning_rate=0.05).minimize(loss)
    return main, startup, x, y, loss


def _params(main, scope):
    return {v.name: np.asarray(scope.find_var(v.name))
            for v in main.list_vars() if v.persistable}


def _train_mlp(data, passes=2, dim=16, event_handler=None, reader=None,
               **train_kwargs):
    """Build + train a fresh MLP in an isolated scope; returns (params,
    per-iteration costs, trainer)."""
    main, startup, x, y, loss = _mlp(dim)
    costs = []

    def on_event(e):
        if isinstance(e, trainer_mod.EndIteration):
            costs.append(float(e.cost))
        if event_handler is not None:
            event_handler(e)

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        t = trainer_mod.Trainer(loss, place=fluid.CPUPlace(),
                                feed_list=[x, y], main_program=main,
                                startup_program=startup)
        t.train(passes, reader or (lambda: iter(data)),
                event_handler=on_event, **train_kwargs)
        params = _params(main, scope)
    return params, costs, t


def _serial_executor_loop(data, passes=2, dim=16):
    """The oracle: a plain `Executor.run` loop over the same reader, each
    batch packed and fed inside its own step."""
    main, startup, x, y, loss = _mlp(dim)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    feeder = DataFeeder([x, y], fluid.CPUPlace())
    costs = []
    for _ in range(passes):
        for batch in data:
            out, = exe.run(main, feed=feeder.feed(batch),
                           fetch_list=[loss], scope=scope)
            costs.append(float(np.asarray(out).reshape(-1)[0]))
    return _params(main, scope), costs


def _workers():
    return [t for t in threading.enumerate()
            if t.name == "paddle-tpu-prefetch"]


class _Boom(Exception):
    pass


class _BadFeeder(DataFeeder):
    """Packs `good` batches, then raises."""

    def __init__(self, feed_list, good):
        super().__init__(feed_list, fluid.CPUPlace())
        self.good = good

    def feed(self, batch):
        if self.good == 0:
            raise _Boom("cannot pack")
        self.good -= 1
        return super().feed(batch)


class TestTrainerAsync:
    @pytest.mark.parametrize("sync_every_n", [1, 4])
    def test_params_bit_identical_to_serial_executor_loop(
            self, sync_every_n):
        data = _deterministic_data()
        want_params, want_costs = _serial_executor_loop(data)
        params, costs, _ = _train_mlp(data, sync_every_n=sync_every_n)
        assert set(want_params) == set(params)
        for name, arr in want_params.items():
            other = params[name]
            assert arr.dtype == other.dtype, name
            assert np.array_equal(arr, other), \
                f"param {name} diverged from the serial Executor.run loop"
        # the observable training trajectory matches too
        np.testing.assert_array_equal(np.asarray(want_costs),
                                      np.asarray(costs))

    def test_async_cost_is_lazy_fetch(self):
        data = _deterministic_data(n_batches=3)
        seen = []

        def on_event(e):
            if isinstance(e, trainer_mod.EndIteration):
                seen.append((e.cost, e.metrics))

        main, startup, x, y, loss = _mlp()
        with fluid.scope_guard(fluid.Scope()):
            t = trainer_mod.Trainer(loss, place=fluid.CPUPlace(),
                                    feed_list=[x, y], main_program=main,
                                    startup_program=startup)
            t.train(1, lambda: iter(data), event_handler=on_event,
                    sync_every_n=2)
        assert len(seen) == 3
        for cost, _metrics in seen:
            assert isinstance(cost, trainer_mod.LazyFetch)
            assert np.isfinite(float(cost))

    def test_default_loop_is_one_batch_ahead(self):
        """With no option set, `Trainer.train` asks the reader for batch
        n+1 after it takes batch n and before EndIteration n (the worker
        prepares it under step n), and never for batch n+2 before it
        takes n+1; every EndIteration carries a float."""
        import inspect

        from paddle_tpu.core.flags import get_flag

        assert "prefetch" not in inspect.signature(
            trainer_mod.Trainer.train).parameters
        assert get_flag("sync_every_n") == 1
        data = _deterministic_data(n_batches=5)
        log, lock = [], threading.Lock()
        asked = [threading.Event() for _ in range(len(data) + 1)]

        def note(*what):
            with lock:
                log.append(what)

        def reader():
            for i, batch in enumerate(data):
                note("ask", i, threading.current_thread().name)
                asked[i].set()
                yield batch
            asked[len(data)].set()  # the pull that finds the end

        def on_event(e):
            if isinstance(e, trainer_mod.BeginIteration):
                note("begin", e.batch_id)
            elif isinstance(e, trainer_mod.EndIteration):
                # the serial loop would ask only after this handler
                assert asked[e.batch_id + 1].wait(5), \
                    f"batch {e.batch_id + 1} not asked for under step " \
                    f"{e.batch_id}"
                time.sleep(0.05)  # time to run two ahead, if it could
                note("end", e.batch_id)

        _, costs, _ = _train_mlp(data, passes=1, reader=reader,
                                 event_handler=on_event)
        assert len(costs) == len(data)
        assert all(isinstance(c, float) for c in costs)
        at = {(w[0], w[1]): i for i, w in enumerate(log)}
        for n in range(len(data)):
            assert log[at["ask", n]][2] == "paddle-tpu-prefetch"
            if n >= 1:
                # batch n is asked for no earlier than the take of n-1,
                # which follows EndIteration n-2 ...
                assert at["ask", n] < at["end", n - 1]
            if n >= 2:
                # ... and never while n-2 is still the step in hand
                assert at["ask", n] > at["end", n - 2]
        assert not _workers()

    def test_resume_fast_forward_skips_feed_packing(self, tmp_path):
        """Resume replays the RAW reader past already-trained batches:
        restart latency must not pay feed packing/H2D for the prefix."""

        class CountingFeeder(DataFeeder):
            calls = 0

            def feed(self, batch):
                CountingFeeder.calls += 1
                return super().feed(batch)

        data = _deterministic_data(n_batches=6)
        main, startup, x, y, loss = _mlp()
        ckpt = str(tmp_path / "ckpt")
        with fluid.scope_guard(fluid.Scope()):
            t = trainer_mod.Trainer(loss, place=fluid.CPUPlace(),
                                    feed_list=[x, y], main_program=main,
                                    startup_program=startup)
            t.train(1, lambda: iter(data), checkpoint_dir=ckpt,
                    checkpoint_every_n_iters=4,
                    checkpoint_every_n_passes=0)
        # fresh trainer resumes at batch 4: only batches 4 and 5 may be
        # packed, the 4 skipped ones must cost zero feeder.feed calls
        with fluid.scope_guard(fluid.Scope()):
            t2 = trainer_mod.Trainer(loss, place=fluid.CPUPlace(),
                                     feed_list=[x, y], main_program=main,
                                     startup_program=startup)
            feeder = CountingFeeder([x, y], fluid.CPUPlace())
            t2.train(1, lambda: iter(data), feeder=feeder,
                     resume_from=ckpt, checkpoint_every_n_passes=0,
                     sync_every_n=2)
            assert t2.step == 6
        assert CountingFeeder.calls == 2, CountingFeeder.calls

    def test_checkpoint_cursor_counts_trained_batches(self, tmp_path):
        """The worker has read batch n+1 when step n's snapshot is
        taken: the cursor says n+1 batches are done, not n+2."""
        from paddle_tpu import io as pio

        data = _deterministic_data(n_batches=5)
        main, startup, x, y, loss = _mlp()
        ckpt = str(tmp_path / "ckpt")
        with fluid.scope_guard(fluid.Scope()):
            t = trainer_mod.Trainer(loss, place=fluid.CPUPlace(),
                                    feed_list=[x, y], main_program=main,
                                    startup_program=startup)
            t.train(1, lambda: iter(data), checkpoint_dir=ckpt,
                    checkpoint_every_n_iters=3,
                    checkpoint_every_n_passes=0)
            meta = pio.load_checkpoint(t.exe, ckpt, main_program=main)
        assert meta["trainer_args"] == {
            "next_pass_id": 0, "next_batch_id": 3, "step": 3}

    @pytest.mark.parametrize("way_out", [
        "reader_raises", "feeder_raises", "handler_raises",
        "fault_injector_fires", "pass_abandoned"])
    def test_every_way_out_joins_the_worker(self, way_out):
        """The error surfaces after the good batches have trained, and
        no way out of a pass leaves the worker thread behind."""
        from paddle_tpu.core.resilience import fault_injector

        data = _deterministic_data(n_batches=6)
        good = 2  # batches that train before the way out is taken
        kwargs, exc = {}, _Boom

        def reader():
            for i, batch in enumerate(data):
                if way_out == "reader_raises" and i == good:
                    raise _Boom("stream died")
                yield batch

        def on_event(e):
            if isinstance(e, trainer_mod.BeginIteration) \
                    and e.batch_id == good:
                if way_out == "handler_raises":
                    raise _Boom("handler")
                if way_out == "pass_abandoned":
                    raise KeyboardInterrupt  # with batch `good` in hand

        if way_out == "feeder_raises":
            kwargs["feeder"] = _BadFeeder(["x", "y"], good)
        elif way_out == "pass_abandoned":
            exc = KeyboardInterrupt
        inj = fault_injector()
        inj.clear()
        if way_out == "fault_injector_fires":
            inj.inject("trainer.iteration", "error", nth=good + 1,
                       exc=_Boom("SIGKILL stand-in"))
        ends = []

        def handler(e):
            if isinstance(e, trainer_mod.EndIteration):
                ends.append(e.batch_id)
            on_event(e)

        try:
            with pytest.raises(exc):
                _train_mlp(data, passes=1, reader=reader,
                           event_handler=handler, **kwargs)
        finally:
            inj.clear()
        assert ends == list(range(good))
        assert not _workers(), "the prefetch worker outlived its pass"

    def test_step_span_says_whether_the_feed_was_ready(self):
        from paddle_tpu.observability import tracing

        data = _deterministic_data(n_batches=4)
        slow = threading.Event()

        def reader():
            for i, batch in enumerate(data):
                if i == 2:
                    slow.wait(5)  # the worker is the slower side once
                yield batch

        def on_event(e):
            if not isinstance(e, trainer_mod.EndIteration):
                return
            if e.batch_id == 1:
                threading.Timer(0.1, slow.set).start()
            elif e.batch_id == 2:
                time.sleep(0.2)  # the loop is the slower side once

        was = tracing.enabled()
        tracing.set_enabled(True)
        tracing.clear()
        try:
            _train_mlp(data, passes=1, reader=reader,
                       event_handler=on_event)
            steps = [s for s in tracing.finished_spans()
                     if s["name"] == "trainer.step"]
        finally:
            tracing.set_enabled(was)
            tracing.clear()
        assert [s["attrs"]["batch_id"] for s in steps] == [0, 1, 2, 3]
        for s in steps:
            assert s["attrs"]["feed_ready"] in (0, 1)
            assert s["attrs"]["feed_wait_s"] >= 0.0
        # batch 2 came from a reader that slept past the step before
        # it; batch 3 was prepared under a handler that slept
        assert [steps[i]["attrs"]["feed_ready"] for i in (2, 3)] == [0, 1]
        assert steps[2]["attrs"]["feed_wait_s"] >= 0.03
        assert steps[3]["attrs"]["feed_wait_s"] < 0.03


# ---------------------------------------------------------------------------
# the pipeline's host buffers (PR 37): ONE a dense feed name at any depth,
# free again the moment its batch is handed on, because the worker waits
# for the transfer first
# ---------------------------------------------------------------------------


def _placed(offset, made=None):
    """A `data_feeder._allocate` whose arrays start `offset` bytes past
    a 64-byte boundary.  The CPU backend stages a numpy array WITHOUT a
    copy exactly when it is 64-byte aligned, and where `np.empty` puts
    a small array is chance: the tests choose."""

    def allocate(shape, dtype):
        dtype = np.dtype(dtype)
        n = int(np.prod(shape)) * dtype.itemsize
        raw = np.empty(n + 128, np.uint8)
        at = (-raw.ctypes.data) % 64 + offset
        if made is not None:
            made.append(tuple(shape))
        return raw[at:at + n].view(dtype).reshape(shape)

    return allocate


def _xy_feeder(dim=16):
    reset_unique_names()
    with fluid.program_guard(fluid.Program(), fluid.Program()):
        x = fluid.layers.data(name="x", shape=[dim], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
    return DataFeeder([x, y], fluid.CPUPlace())


def _distinct_batches(bs=8, short=3):
    """Eight distinct full batches and the short last one of a pass."""
    data = _deterministic_data(n_batches=9, bs=bs)
    data[-1] = data[-1][:short]
    return data


def _pack_spans(run):
    from paddle_tpu.observability import tracing

    was = tracing.enabled()
    tracing.set_enabled(True)
    tracing.clear()
    try:
        run()
        return [s["attrs"] for s in tracing.finished_spans()
                if s["name"] == "trainer.phase.feed_pack"]
    finally:
        tracing.set_enabled(was)
        tracing.clear()


class TestPackedBuffers:
    @pytest.mark.parametrize("placed", ["aligned", "off_by_16", "numpy"])
    @pytest.mark.parametrize("device_put", [True, False])
    @pytest.mark.parametrize("depth", [1, 2])
    def test_kept_feeds_stay_their_rows(self, depth, device_put, placed,
                                        monkeypatch):
        """The consumer KEEPS every feed it took until the end of the
        pass: each still equals its rows, so no buffer was rewritten
        before its transfer finished or while the consumer held its
        memory (aligned: the staged array IS the host array here)."""
        from paddle_tpu import data_feeder

        if placed != "numpy":
            monkeypatch.setattr(
                data_feeder, "_allocate",
                _placed({"aligned": 0, "off_by_16": 16}[placed]))
        data = _distinct_batches()
        feeds = prefetch_feeder(lambda: iter(data), _xy_feeder(),
                                fluid.CPUPlace(), depth=depth,
                                device_put=device_put)()
        kept = []
        for feed in feeds:
            kept.append(feed)
            time.sleep(0.01)  # the worker runs ahead under the "step"
        assert len(kept) == len(data)
        for batch, feed in zip(data, kept):
            assert isinstance(feed["x"], np.ndarray) != device_put
            np.testing.assert_array_equal(
                np.asarray(feed["x"]), np.asarray([r[0] for r in batch]))
            np.testing.assert_array_equal(
                np.asarray(feed["y"]), np.asarray([r[1] for r in batch]))
        assert not _workers()

    @pytest.mark.parametrize("depth", [1, 2])
    def test_pack_spans_say_bytes_and_reused(self, depth, monkeypatch):
        """`reused` is 0 on the first batch, 1 on every batch once the
        buffers exist, 0 again on the short last batch; the form that
        blocks needs ONE buffer a feed name whatever the depth, so a
        pass allocates twice a name: first batch and short batch."""
        from paddle_tpu import data_feeder

        made = []
        monkeypatch.setattr(data_feeder, "_allocate", _placed(16, made))
        data = _distinct_batches()
        feeds = prefetch_feeder(lambda: iter(data), _xy_feeder(),
                                fluid.CPUPlace(), depth=depth)
        attrs = _pack_spans(lambda: [time.sleep(0.005) for _ in feeds()])
        assert [a["reused"] for a in attrs] == [0] + [1] * 7 + [0]
        full, short = 8 * 16 * 4 + 8 * 4, 3 * 16 * 4 + 3 * 4
        assert [a["bytes"] for a in attrs] == [full] * 8 + [short]
        assert made == [(8, 16), (8, 1), (3, 16), (3, 1)], made

    def test_buffer_the_consumer_holds_is_let_go(self, monkeypatch):
        """On a cpu device an aligned host array is staged without a
        copy: the consumer's array IS that memory, so the iterator lets
        it go and the next batch gets a new one (`reused` stays 0)."""
        from paddle_tpu import data_feeder

        made = []
        monkeypatch.setattr(data_feeder, "_allocate", _placed(0, made))
        data = _distinct_batches(short=8)
        feeds = prefetch_feeder(lambda: iter(data), _xy_feeder(),
                                fluid.CPUPlace())
        attrs = _pack_spans(lambda: list(feeds()))
        assert [a["reused"] for a in attrs] == [0] * 9
        assert made.count((8, 16)) == 9

    def test_host_feeds_are_never_reused(self, monkeypatch):
        """`device_put=False`: the consumer holds the host arrays."""
        from paddle_tpu import data_feeder

        made = []
        monkeypatch.setattr(data_feeder, "_allocate", _placed(16, made))
        data = _distinct_batches(short=8)
        feeds = prefetch_feeder(lambda: iter(data), _xy_feeder(),
                                device_put=False)
        attrs = _pack_spans(lambda: list(feeds()))
        assert [a["reused"] for a in attrs] == [0] * 9
        assert [a["bytes"] for a in attrs] == [8 * 16 * 4 + 8 * 4] * 9
        assert made.count((8, 16)) == 9

    def test_a_feeders_own_feed_gets_no_destination(self):
        """A subclass that overrides `feed(batch)` keeps its signature:
        the pipeline passes `out=` to `DataFeeder.feed` alone."""
        calls = []

        class Own(DataFeeder):
            def feed(self, batch):
                calls.append(len(batch))
                return super().feed(batch)

        inner = _xy_feeder()
        data = _distinct_batches()
        feeds = prefetch_feeder(lambda: iter(data),
                                Own(inner.feed_list, fluid.CPUPlace()),
                                fluid.CPUPlace())
        attrs = _pack_spans(lambda: list(feeds()))
        assert calls == [8] * 8 + [3]
        assert [a["reused"] for a in attrs] == [0] * 9


# ---------------------------------------------------------------------------
# host-bound overlap microbench (tier-1-safe: deterministic sleep-based
# host work; the speedup floor is half the ~2x the construction implies)
# ---------------------------------------------------------------------------


@pytest.mark.perf
def test_prefetch_overlap_speedup_no_recompiles():
    """Host-bound loop: per-batch host work == one device step, so the
    serial loop costs ~2 steps of wall per step and the prefetched+lazy
    loop ~1.  Asserts >= 20% steps/s improvement and ZERO executable-cache
    misses after warmup in both timed loops (cache_stats-enforced)."""
    # the model must be big enough that exe.run wall is mostly XLA
    # compute (GIL released) rather than python dispatch (GIL held) —
    # overlap is impossible against a GIL-bound consumer
    bs, dim, steps = 128, 256, 16
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[dim], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        h = fluid.layers.fc(input=x, size=512, act="relu")
        h = fluid.layers.fc(input=h, size=512, act="relu")
        p = fluid.layers.fc(input=h, size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=p, label=y))
        fluid.SGD(learning_rate=0.01).minimize(loss)

    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    exe.run(startup, scope=scope)
    feeder = DataFeeder([x, y], fluid.CPUPlace())
    r = np.random.RandomState(0)
    rows = [(r.rand(dim).astype(np.float32),
             r.rand(1).astype(np.float32)) for _ in range(bs)]
    warm = feeder.feed(rows)

    # warmup (compile) + measure the steady-state synchronous step time
    for _ in range(3):
        exe.run(main, feed=warm, fetch_list=[loss], scope=scope)
    t0 = time.perf_counter()
    for _ in range(5):
        exe.run(main, feed=warm, fetch_list=[loss], scope=scope)
    step_s = (time.perf_counter() - t0) / 5
    # host work per batch == one device step (floored against timer
    # noise): sleep releases the GIL like a real decoder would
    host_s = max(step_s, 0.002)

    def batches():
        for _ in range(steps):
            time.sleep(host_s)
            yield rows

    warm_misses = exe.cache_stats()["misses"]

    def run_serial():
        t0 = time.perf_counter()
        for b in batches():
            exe.run(main, feed=feeder.feed(b), fetch_list=[loss],
                    scope=scope)
        return time.perf_counter() - t0

    def run_prefetch():
        t0 = time.perf_counter()
        it = prefetch_feeder(batches, feeder, fluid.CPUPlace(),
                             depth=2)()
        last = None
        for i, feed in enumerate(it):
            last, = exe.run(main, feed=feed, fetch_list=[loss],
                            scope=scope, return_numpy=False)
            if (i + 1) % 4 == 0:
                np.asarray(last)  # periodic fence (sync_every_n=4)
        np.asarray(last)  # count only finished work
        return time.perf_counter() - t0

    # best-of-3 per mode: a background scheduler blip in one repeat must
    # not fail the assertion — the MINIMUM is the overlap capability
    serial_wall = min(run_serial() for _ in range(3))
    prefetch_wall = min(run_prefetch() for _ in range(3))

    stats = exe.cache_stats()
    assert stats["misses"] == warm_misses, \
        f"hot loop recompiled: {stats}"
    assert stats["recompiles_after_warmup"] == 0, stats
    speedup = serial_wall / prefetch_wall
    assert speedup >= 1.2, (
        f"prefetch+lazy speedup {speedup:.2f}x < 1.2x "
        f"(serial {serial_wall:.3f}s, prefetch {prefetch_wall:.3f}s, "
        f"step {step_s * 1e3:.2f}ms, host {host_s * 1e3:.2f}ms)")
