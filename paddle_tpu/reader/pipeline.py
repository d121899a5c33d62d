"""Async input pipeline: overlap reader -> feed-pack -> H2D with compute.

The reference overlaps input preparation with compute through the async
PyDataProvider2 pool and the gserver double-buffered data providers
(framework/reader.h double_buffer).  This module runs the batch reader,
the feed packing and an eager `jax.device_put` on a background thread
ahead of the training loop, handing the consumer feed dicts whose values
are already device-resident: `Trainer.train` takes every batch through
it, one batch ahead (batch n+1 is read, packed and staged while the
device runs step n).

Layering: this sits ON TOP of the reader decorators (shuffle/batch/
bucket_by_length/...), not instead of them — `prefetch_feeder(reader,
feeder)` takes any batch reader and returns another zero-arg reader
(the package idiom), whose iterator is a `PrefetchIterator` with clean
shutdown (`close()`), a bounded read-ahead, and exception
propagation (a reader/feeder failure re-raises in the consumer instead
of truncating the stream, same contract as `buffered`).

The H2D staging stage (`stage_to_device`) is shared with the serving
worker's batch assembly (serving.py), so both hot paths emit the same
`pipeline.h2d` profiler events.
"""
from __future__ import annotations

import itertools
import queue
import threading
import time

import numpy as np

__all__ = ["prefetch_feeder", "PrefetchIterator", "PrefetchReader",
           "stage_to_device"]

from . import _Error
from ..observability import attribution as obs_attr
from ..observability import metrics as obs_metrics
from ..observability import tracing as obs_tracing

# pipeline telemetry (gated by PADDLE_TPU_METRICS): queue occupancy
# answers "is the reader keeping up" (at `depth` = yes, at 0 with high
# wait = the reader is the bottleneck; docs/performance.md).
# The gauge is labeled per iterator — concurrent streams must not
# clobber one series — and close() reclaims it, so a finished stream
# does not export a stale depth forever.
_PIPE_IDS = itertools.count()
_M_QUEUE_DEPTH = obs_metrics.gauge(
    "paddle_tpu_pipeline_queue_depth",
    "prefetch queue occupancy (packed device-resident batches ready)",
    ("pipe",))
_M_WAIT_SECONDS = obs_metrics.histogram(
    "paddle_tpu_pipeline_wait_seconds",
    "consumer blocked on an empty prefetch queue per batch")


class _End:
    pass


def stage_to_device(value, device):
    """H2D-stage one feed value (LoDTensor wrappers preserved), emitting a
    `pipeline.h2d` profiler event — the single staging stage shared by the
    training prefetch pipeline and the serving worker's batch assembly."""
    from paddle_tpu import profiler
    from paddle_tpu.core.executor import _to_device_value

    with profiler.record_event("pipeline.h2d"):
        return _to_device_value(value, device)


class PrefetchIterator:
    """One epoch of prefetched feeds: a daemon thread runs
    `reader() -> feeder.feed -> device_put`, at most `depth` batches
    ahead of the consumer.

    * hand-off: the worker asks the reader for a batch only against a
      credit, and there are `depth` of them; the consumer gives one back
      each time it TAKES a batch.  So at most `depth` batches are read
      and not yet taken (prepared or in preparation), and with
      `depth=1` batch n+1 is read no earlier than the take of batch n:
      exactly one ahead.  That bounds host memory, device memory and
      how far a stateful reader runs ahead of its consumer;
    * host buffers: with a `DataFeeder` and `device_put=True` the
      arrays the feeder packs stay this iterator's, ONE a dense feed
      name whatever the depth, and the next batch is packed into them
      (`DataFeeder.feed(batch, out=...)`: no allocation, no page
      faults).  The invariant: a buffer is written again only after the
      transfer that read it has completed, and never while anything the
      consumer can hold refers to its memory.  Kept by the form that
      blocks: the worker waits for the staged arrays
      (`block_until_ready`, which holds no GIL) BEFORE it hands the
      batch over, so the consumer only ever holds device arrays whose
      transfer is over, and a buffer is free the moment its batch is
      handed on.  The form that does not block needs `depth + 1`
      buffers and a wait before each rewrite, to hide a transfer that
      is a seventh of the step it already runs beside.  On a cpu device
      the staged array can BE the buffer (the backend takes an aligned
      numpy array without a copy): then the consumer owns that memory
      and the iterator lets it go.  A batch of another shape gets new
      arrays, which become the buffers; with `device_put=False` the
      consumer holds the host arrays and nothing is reused, as for a
      feeder whose `feed` is its own;
    * errors: any exception in the reader/feeder/transfer re-raises at the
      consumer's next `__next__` (after the good batches before it);
    * shutdown: `close()` (idempotent; also called on exhaustion) stops
      the worker, joins it and drops what it had prepared, so breaking
      out of a pass early leaks neither a thread nor a device buffer.
      NOTE: a live worker holds a reference to this iterator (the
      thread's bound-method target), so an ABANDONED iterator is not
      garbage-collected — consumers that may abandon mid-stream should
      hold the `PrefetchReader` wrapper (what `prefetch_feeder`
      returns), whose `__del__` IS reachable and closes the inner
      iterator.
    """

    def __init__(self, reader, feeder=None, place=None, depth=1,
                 device_put=True):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        # never full: an item (the end and an error too) is put only
        # against a credit, so it holds at most `depth`
        self._q: "queue.Queue" = queue.Queue()
        self._credits = threading.Semaphore(depth)
        self._stop = threading.Event()
        self._done = False
        # cumulative consumer-side blocked time (queue empty): the
        # host-blocked numerator a bench can read without enabling the
        # profiler (whose compiled-mode events fence the device)
        self.wait_s = 0.0
        # the same for the last take alone, and whether its batch was
        # already prepared when the consumer asked (trainer.step's
        # `feed_wait_s` and `feed_ready`)
        self.last_wait_s = 0.0
        self.last_ready = False
        self._feeder = feeder
        self._device_put = device_put
        from ..data_feeder import DataFeeder

        # `out=` is DataFeeder.feed's own; an overriding feed is a
        # caller's, with the one argument it always had
        self._reuse = device_put and \
            getattr(type(feeder), "feed", None) is DataFeeder.feed
        self._bufs = {}  # dense feed name -> host array free to rewrite
        # thread handoff: batches prepared on the worker record under
        # the span that constructed the iterator (e.g. the pass that
        # opened the reader)
        self._trace_ctx = obs_tracing.current_context()
        self._pipe_id = str(next(_PIPE_IDS))
        self._m_depth = _M_QUEUE_DEPTH.labels(pipe=self._pipe_id)
        place = place or getattr(feeder, "place", None)
        self._device = place.jax_device() if place is not None else None
        if device_put and self._device is None:
            import jax

            self._device = jax.devices()[0]
        self.thread = threading.Thread(
            target=self._work, args=(reader,), daemon=True,
            name="paddle-tpu-prefetch")
        self.thread.start()

    # -- worker -------------------------------------------------------------
    def _on_credit(self, batches):
        """`batches`, each pulled only once the consumer has taken one
        of the `depth` before it (close() gives a credit to wake us)."""
        while True:
            self._credits.acquire()
            if self._stop.is_set():
                return
            batch = next(batches, _End)
            if batch is _End:
                return
            yield batch

    def _prepare(self, batch):
        packed = {}
        if self._feeder is not None:
            with obs_attr.phase("trainer", "feed_pack") as span:
                if self._reuse:
                    feed = self._feeder.feed(batch, out=self._bufs)
                else:
                    feed = self._feeder.feed(batch)
                if isinstance(feed, dict):
                    packed = {k: v for k, v in feed.items()
                              if isinstance(v, np.ndarray)}
                if span is not None:
                    span.set_attr("bytes", sum(
                        v.nbytes for v in packed.values()))
                    span.set_attr("reused", int(bool(packed) and all(
                        v is self._bufs.get(k)
                        for k, v in packed.items())))
        else:
            feed = batch  # reader already yields feed dicts
        if not self._device_put:
            return feed
        with obs_attr.phase("trainer", "h2d"):
            if isinstance(feed, dict):
                feed = {k: stage_to_device(v, self._device)
                        for k, v in feed.items()}
            else:
                feed = stage_to_device(feed, self._device)
            if self._reuse:
                import jax

                jax.block_until_ready([feed[k] for k in packed])
                self._bufs = {k: v for k, v in packed.items()
                              if not self._is_staged(v, feed[k])}
        return feed

    def _is_staged(self, host, staged):
        """Whether the staged array's memory is the host array's own:
        only a device whose memory is the host's can do that."""
        return self._device.platform == "cpu" and np.shares_memory(
            host, np.asarray(staged))

    def _work(self, reader):
        try:
            with obs_tracing.activate(self._trace_ctx):
                for batch in self._on_credit(obs_attr.phased_iter(
                        "trainer", "reader", reader())):
                    with obs_tracing.span("pipeline.prepare"):
                        item = self._prepare(batch)
                    if self._stop.is_set():
                        return
                    self._q.put(item)
                    if obs_metrics.enabled():
                        self._m_depth.set(self._q.qsize())
                self._q.put(_End)
        except BaseException as e:  # propagate, don't truncate the stream
            self._q.put(_Error(e))

    # -- consumer -----------------------------------------------------------
    def __iter__(self):
        return self

    def __next__(self):
        from paddle_tpu import profiler

        if self._done:
            raise StopIteration
        with profiler.record_event("pipeline.wait"):
            self.last_ready = not self._q.empty()
            t0 = time.perf_counter()
            item = self._q.get()
            self.last_wait_s = dt = time.perf_counter() - t0
            self.wait_s += dt
        if obs_metrics.enabled():
            _M_WAIT_SECONDS.observe(dt)
            self._m_depth.set(self._q.qsize())
        if item is _End:
            self._done = True
            self.thread.join(timeout=5)
            raise StopIteration
        if isinstance(item, _Error):
            self._done = True
            self._stop.set()
            raise item.exc
        # the take: the worker may read one batch more
        self._credits.release()
        return item

    def close(self):
        """Stop the worker, join it and drop what it prepared (safe to
        call more than once)."""
        self._done = True
        self._stop.set()
        self._credits.release()  # wake a worker waiting for a take
        _M_QUEUE_DEPTH.remove(pipe=self._pipe_id)
        if self.thread.is_alive():
            self.thread.join(timeout=5)
        while True:
            try:
                self._q.get_nowait()
            except queue.Empty:
                break

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class PrefetchReader:
    """Lazy one-epoch handle: the PrefetchIterator (and its worker
    thread) starts at the FIRST `next()`, not at construction — the
    package reader contract (`compose`/`zip` call every reader before
    consuming any; side-effecting sources like `cloud_reader` must not
    drain tasks for a stream nobody iterates).  Because the worker only
    references the INNER iterator, dropping this handle is collectable:
    `__del__` closes the iterator, so an abandoned stream (early `break`
    without `close()`) leaks neither the thread nor the queued
    device-resident batches."""

    def __init__(self, reader, feeder=None, place=None, depth=1,
                 device_put=True):
        self._args = (reader, feeder, place, depth, device_put)
        self._it: "PrefetchIterator | None" = None
        self._closed = False

    def __iter__(self):
        return self

    def __next__(self):
        if self._closed:
            raise StopIteration
        if self._it is None:
            reader, feeder, place, depth, device_put = self._args
            self._it = PrefetchIterator(reader, feeder=feeder,
                                        place=place, depth=depth,
                                        device_put=device_put)
        return next(self._it)

    @property
    def wait_s(self) -> float:
        """Consumer-side blocked seconds (see PrefetchIterator.wait_s)."""
        return self._it.wait_s if self._it is not None else 0.0

    @property
    def last_wait_s(self) -> float:
        return self._it.last_wait_s if self._it is not None else 0.0

    @property
    def last_ready(self) -> bool:
        return self._it.last_ready if self._it is not None else False

    def close(self):
        self._closed = True
        if self._it is not None:
            self._it.close()

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def prefetch_feeder(reader, feeder=None, place=None, depth=1,
                    device_put=True):
    """Reader decorator: batch reader -> reader of DEVICE-RESIDENT feed
    dicts, prepared at most `depth` batches ahead on a background thread
    (the default, one, is what `Trainer.train` runs).

        feeds = prefetch_feeder(train_reader, feeder, place)
        for feed in feeds():
            exe.run(main, feed=feed, fetch_list=[loss])

    `feeder=None` means the reader already yields feed dicts and only the
    device transfer is staged; `device_put=False` keeps values on host
    (pure pack-ahead).  Each call of the returned reader yields a fresh
    `PrefetchReader` (own thread once iterated), so it composes
    with the multi-pass Trainer loop exactly like any other reader.
    """

    def feed_reader():
        return PrefetchReader(reader, feeder=feeder, place=place,
                              depth=depth, device_put=device_put)

    return feed_reader
