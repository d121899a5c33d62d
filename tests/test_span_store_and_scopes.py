"""PR 24: the one span store, the counts on the scheduler's and the
executors' spans, the reader phase, the named scopes inside the paged
decoder's step and `profiler.hlo_scopes()` (docs/observability.md "Span
vocabulary" and "Device time by scope")."""
import contextlib
from collections import deque

import numpy as np
import pytest

import jax

import paddle_tpu as fluid
from paddle_tpu import parallel, profiler
from paddle_tpu import trainer as trainer_mod
from paddle_tpu.core.executor import global_scope
from paddle_tpu.core.framework import reset_unique_names
from paddle_tpu.models.transformer import build_lm_paged_decoder
from paddle_tpu.observability import (attribution, flightrecorder, metrics,
                                      tracing)
from paddle_tpu.serving import GenerationServer
from paddle_tpu.serving.batching import RequestDeadlineExceeded


@pytest.fixture(autouse=True)
def _fresh():
    def reset():
        metrics.set_enabled(False)
        tracing.set_enabled(False)
        tracing.disarm_tail_sampler()
        flightrecorder.uninstall()
        del tracing._listeners[:]
        tracing.clear()
        profiler.reset_profiler()

    reset()
    yield
    reset()


@contextlib.contextmanager
def listening():
    """Spans kept live by a listener alone (full tracing off), as the
    benchmark's tap and the flight recorder do."""
    got = []
    tracing.add_span_listener(got.append)
    try:
        yield got
    finally:
        tracing.remove_span_listener(got.append)


def _named(name):
    return [s for s in tracing.finished_spans() if s["name"] == name]


# ---------------------------------------------------------------------------
# A. the span store
# ---------------------------------------------------------------------------


def test_store_keeps_full_records_under_a_listener_alone():
    assert not tracing.enabled()
    with listening() as got:
        with tracing.span("outer", k=1) as outer:
            with tracing.span("inner") as inner:
                inner.set_attr("n", 3)
        tracing.record_span("late", 12.5, 0.25, parent=outer.context, x="y")
    assert [s["name"] for s in tracing.finished_spans()] == [
        "inner", "outer", "late"]
    assert tracing.finished_spans() == got     # the same records
    rec = tracing.finished_spans()[0]
    assert set(rec) == {"name", "trace_id", "span_id", "parent_id", "ts",
                        "dur", "cpu", "pid", "tid", "thread", "attrs"}
    assert rec["attrs"] == {"n": 3}
    assert rec["parent_id"] == outer.context.span_id
    assert rec["trace_id"] == outer.context.trace_id
    late = tracing.finished_spans()[2]
    assert (late["ts"], late["dur"], late["attrs"]) == (12.5, 0.25,
                                                         {"x": "y"})


def _spin(seconds):
    """Compute until this thread has had `seconds` of a CPU."""
    import time

    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def _nap(seconds):
    import time

    time.sleep(seconds)


@pytest.mark.parametrize("body, working", [(_spin, True), (_nap, False)],
                         ids=["busy_loop", "sleep"])
def test_span_records_its_threads_cpu_seconds(body, working):
    """`cpu` is the span's own thread on a CPU between entry and exit:
    all of what a loop that computes was given (on an idle host most of
    `dur`; the test's host is shared, so the seconds are what is
    asserted), next to none of a sleep (`dur - cpu` is the time the
    thread did not run)."""
    with listening():
        with tracing.span("outer"):
            with tracing.span("inner"):
                body(0.03)
    inner, outer = tracing.finished_spans()
    for rec in (inner, outer):
        assert rec["dur"] >= 0.03 and 0.0 <= rec["cpu"] <= rec["dur"]
        if working:
            assert rec["cpu"] >= 0.03
        else:
            assert rec["cpu"] < rec["dur"] / 3
    assert outer["cpu"] >= inner["cpu"]


def test_record_span_stores_the_cpu_it_is_given_or_none():
    """A range recorded after the fact carries the `cpu` its caller
    read, None where it read none (a request's lifetime lies on no
    thread); `phased_iter` reads its pull's."""
    with listening():
        tracing.record_span("late", 12.5, 0.25)
        tracing.record_span("timed", 12.5, 0.25, cpu=0.125, x="y")
        assert list(attribution.phased_iter(
            "trainer", "reader", (_nap(0.01) for _ in range(2)))) == [
                None, None]
    late, timed, *pulls = tracing.finished_spans()
    assert late["cpu"] is None and late["attrs"] == {}
    assert timed["cpu"] == 0.125 and timed["attrs"] == {"x": "y"}
    assert len(pulls) == 2 and all(
        0.0 <= s["cpu"] < s["dur"] / 3 and s["dur"] >= 0.01 for s in pulls)
    events = {e["name"]: e for e in tracing.chrome_trace_events(
        include_profiler=False)}
    assert events["timed"]["args"]["cpu"] == 0.125
    assert events["late"]["args"]["cpu"] is None


def test_a_noop_span_reads_no_clock(monkeypatch):
    """With tracing off and no listener `span()` is the shared no-op
    after one boolean test: no clock is read, nothing is stored."""
    class NoClock:
        def __getattr__(self, name):
            raise AssertionError(f"time.{name} read by a span that is off")

    monkeypatch.setattr(tracing, "time", NoClock())
    monkeypatch.setattr(attribution, "time", NoClock())
    with tracing.span("x", k=1) as s:
        assert s is None
    with attribution.phase("generation", "build") as s:
        assert s is None
    assert tracing.record_span("y", 0.0, 1.0, cpu=0.5) is None
    assert list(attribution.phased_iter("trainer", "reader", [1])) == [1]
    assert tracing.finished_spans() == []


def test_store_holds_nothing_while_spans_are_off():
    assert tracing.span("x") is tracing._NOOP
    with tracing.span("x", k=1) as s:
        assert s is None
    assert tracing.record_span("y", 0.0, 1.0) is None
    assert tracing.finished_spans() == []
    assert tracing.dropped_spans() == 0


def test_store_is_a_ring_that_drops_the_oldest_and_counts(monkeypatch):
    monkeypatch.setattr(tracing, "_spans", deque(maxlen=4))
    tracing.set_enabled(True)
    for i in range(7):
        with tracing.span(f"s{i}"):
            pass
    assert [s["name"] for s in tracing.finished_spans()] == [
        "s3", "s4", "s5", "s6"]
    assert tracing.dropped_spans() == 3
    assert [s["name"] for s in tracing.finished_spans(last=2)] == [
        "s5", "s6"]
    assert len(tracing.finished_spans(last=9)) == 4
    tracing.clear()
    assert tracing.finished_spans() == [] and tracing.dropped_spans() == 0
    # the busiest cell's window whole, with room: 201 ticks a second of
    # 6 spans, 62 requests a second of 3, 48 s (docs/observability.md)
    assert tracing._MAX_SPANS >= 2 * 48 * (201 * 6 + 62 * 3)


def _deferred_span(name, fn, **attrs):
    """One span with a deferred account, made on a thread of its own:
    -> that thread's ident."""
    import threading

    def body():
        with tracing.span(name, **attrs) as sp:
            sp.defer(fn)

    t = threading.Thread(target=body)
    t.start()
    t.join()
    return t.ident


@pytest.mark.parametrize("read", ["store", "newest", "chrome", "flight",
                                  "tail"])
def test_a_deferred_account_is_made_once_by_the_first_reader(read, tmp_path):
    """`Span.defer(fn)`: the span's thread never calls `fn`; the record
    stands in the store without its attributes until somebody READS it
    by any of the ways a record leaves the process (the store whole, its
    newest, the Chrome export, the flight recorder's dump, the tail
    sampler's flush), and that reader's thread calls `fn` once; the
    dict is merged into `attrs` and the reference dropped."""
    import json
    import threading

    calls = []

    def account():
        calls.append(threading.get_ident())
        return {"pages": 7, "k": 2}

    sampler = (tracing.arm_tail_sampler(threshold_s=0.0,
                                        out_dir=str(tmp_path),
                                        flush_s=1e9)
               if read == "tail" else None)
    tracing.set_enabled(True)
    made_on = _deferred_span("tick", account, k=1)
    assert calls == []
    (raw,) = tracing._spans                 # the record as it was stored
    assert raw["attrs"] == {"k": 1} and raw["deferred"] is account
    if read == "store":
        (rec,) = tracing.finished_spans()
    elif read == "newest":
        (rec,) = tracing.finished_spans(last=5)
    elif read == "chrome":
        (event,) = tracing.chrome_trace_events(include_profiler=False)
        assert event["args"]["pages"] == 7
        rec = raw
    elif read == "flight":
        (rec,) = flightrecorder.FlightRecorder().dump_dict()["spans"]
    else:
        path = sampler.flush(force=True)
        with open(path) as f:
            (event,) = json.load(f)["traceEvents"]
        assert event["args"]["pages"] == 7 and event["args"]["k"] == 2
        rec = raw
    assert rec is raw and "deferred" not in rec
    assert rec["attrs"] == {"k": 2, "pages": 7}      # over the eager k
    assert calls == [threading.get_ident()] and calls[0] != made_on
    assert rec["tid"] == made_on
    # once: every later read finds the attributes and calls nothing
    assert tracing.finished_spans() == [rec]
    assert tracing.finished_spans(last=1) == [rec]
    assert len(calls) == 1
    assert (tracing.dropped_deferred(), tracing.failed_deferred()) == (0, 0)


def test_a_listener_sees_the_record_unresolved_and_unbroken():
    """The three listeners in the tree read name, `ts`, `dur`, `cpu` and
    eager attributes only: a listener is handed the store's own record
    before any reader made its account, whole in everything else, and
    sees the attributes there once a reader has."""
    calls, seen = [], []

    def listener(rec):
        seen.append((dict(rec["attrs"]), "deferred" in rec,
                     {k for k in rec if k != "deferred"}))

    tracing.add_span_listener(listener)
    with listening() as got:
        _deferred_span("tick", lambda: calls.append(1) or {"pages": 7},
                       active=3)
        with tracing.span("plain"):
            pass
    assert calls == []
    attrs, deferred, keys = seen[0]
    assert attrs == {"active": 3} and deferred
    assert keys == {"name", "trace_id", "span_id", "parent_id", "ts",
                    "dur", "cpu", "pid", "tid", "thread", "attrs"}
    assert seen[1][:2] == ({}, False)       # no account: nothing kept
    assert got[0]["dur"] >= 0 and got[0]["cpu"] is not None
    assert tracing.finished_spans() == got  # the same records, resolved
    assert got[0]["attrs"] == {"active": 3, "pages": 7} and calls == [1]


def test_a_deferred_account_that_raises_is_counted_not_raised():
    tracing.set_enabled(True)
    _deferred_span("bad", lambda: 1 / 0, k=1)
    _deferred_span("worse", lambda: 5)              # no dict
    _deferred_span("good", lambda: {"n": 2})
    bad, worse, good = tracing.finished_spans()
    assert bad["attrs"] == {"k": 1} and worse["attrs"] == {}
    assert good["attrs"] == {"n": 2}
    assert not any("deferred" in r for r in (bad, worse, good))
    assert tracing.failed_deferred() == 2
    assert tracing.dropped_deferred() == 0
    tracing.finished_spans()
    assert tracing.failed_deferred() == 2           # not tried again
    tracing.clear()
    assert tracing.failed_deferred() == 0


def test_the_oldest_unread_account_is_dropped_and_counted(monkeypatch):
    """At most `_MAX_DEFERRED` accounts wait for a reader: one more and
    the oldest record loses its account (the record stays), counted as
    `dropped_spans()` counts a record; accounts already made take no
    room."""
    # a window's ticks whole: closed32's ramp and window are 13 000
    # ticks, agent96's 10 000 (docs/observability.md)
    assert tracing._MAX_DEFERRED >= 4 * 13_000
    monkeypatch.setattr(tracing, "_MAX_DEFERRED", 3)
    tracing.set_enabled(True)
    calls = []
    for i in range(5):
        _deferred_span(f"s{i}", lambda i=i: calls.append(i) or {"i": i})
    assert tracing.dropped_deferred() == 2 and calls == []
    spans = tracing.finished_spans()
    assert [s["name"] for s in spans] == [f"s{i}" for i in range(5)]
    assert [s["attrs"] for s in spans] == [{}, {}, {"i": 2}, {"i": 3},
                                           {"i": 4}]
    assert calls == [2, 3, 4] and tracing.dropped_spans() == 0
    # what has been read waits no longer: three more fit
    for i in range(5, 8):
        _deferred_span(f"s{i}", lambda i=i: {"i": i})
    assert tracing.dropped_deferred() == 2
    assert [s["attrs"] for s in tracing.finished_spans()[5:]] == [
        {"i": 5}, {"i": 6}, {"i": 7}]
    tracing.clear()
    assert tracing.dropped_deferred() == 0 and not tracing._deferred


# ---------------------------------------------------------------------------
# B. counts on the scheduler's spans
# ---------------------------------------------------------------------------


def _tiny_server(kv_dtype="fp32", **kw):
    reset_unique_names()
    startup, dec = build_lm_paged_decoder(
        50, 4, 8, d_model=32, n_heads=4, n_layers=2, kv_dtype=kv_dtype)
    fluid.Executor(fluid.CPUPlace()).run(startup)
    states = {n: np.asarray(global_scope().find_var(n))
              for n in dec.state_names}
    return GenerationServer(dec, states, slots=4, kv_blocks=32,
                            place=fluid.CPUPlace(), **kw), dec


def test_decode_tick_and_request_spans_account_for_the_run():
    srv, _ = _tiny_server()
    try:
        with listening():
            streams = [srv.submit(list(range(1, 3 + i)), 5)
                       for i in range(6)]
            outs = [s.result(60) for s in streams]
            # the request span is recorded just before the stream ends
            ticks = [s["attrs"] for s in _named("serving.decode_tick")]
            reqs = _named("serving.request")
    finally:
        srv.close()
    delivered = sum(len(o) for o in outs)
    assert delivered == 30
    assert sum(a["active"] - a["prefill"] for a in ticks) == delivered
    assert all(0 <= a["kv_used"] <= a["kv_total"] == 32 for a in ticks)
    # every attribute has a reader (docs/observability.md): no more
    # (a block without experts sets no `moe_experts_hit`)
    assert all(set(a) == {"active", "prefill", "kv_used", "kv_total",
                          "kv_pages_read", "kv_pages_table",
                          "kv_rows_multiplied", "kv_wait", "ahead",
                          "step_bytes_weights", "step_bytes_cache",
                          "expert_bytes"}
               for a in ticks)
    assert len(reqs) == 6
    for r in reqs:
        a = r["attrs"]
        assert a["queue_s"] >= 0 and a["prefill_s"] >= 0 \
            and a["decode_s"] >= 0 and "error" not in a
        assert a["queue_s"] + a["prefill_s"] + a["decode_s"] == \
            pytest.approx(r["dur"], abs=1e-9)
        assert a["tokens"] == 5 and a["cached_tokens"] <= a["prompt_tokens"]
    assert sorted(r["attrs"]["prompt_tokens"] for r in reqs) == [
        2, 3, 4, 5, 6, 7]
    # the two that waited for a slot queued longer than the four that
    # were admitted at once
    waits = sorted(r["attrs"]["queue_s"] for r in reqs)
    assert waits[4] > waits[3]


def test_shed_request_span_carries_error():
    srv, _ = _tiny_server()
    try:
        with listening():
            busy = [srv.submit([1, 2, 3], 24) for _ in range(4)]
            late = srv.submit([4, 5], 4, deadline_ms=0.01)
            with pytest.raises(RequestDeadlineExceeded):
                late.result(60)
            for s in busy:
                s.result(60)
            shed = [r for r in _named("serving.request")
                    if "error" in r["attrs"]]
    finally:
        srv.close()
    assert len(shed) == 1
    a = shed[0]["attrs"]
    assert a["error"] == "RequestDeadlineExceeded" and a["tokens"] == 0
    assert a["queue_s"] == pytest.approx(shed[0]["dur"], abs=1e-9)
    assert a["prefill_s"] == 0 and a["decode_s"] == 0


# ---------------------------------------------------------------------------
# the nine readers of the scheduler's iteration (perf/metrics/sched_*.py)
# ---------------------------------------------------------------------------

_SCHED_READERS = (
    "sched_host_busy_share", "sched_admit_ms", "sched_build_ms",
    "sched_dispatch_ms", "sched_deliver_ms", "sched_lock_wait_ms",
    "sched_host_unattributed_share", "sched_iteration_max_ms",
    "sched_iteration_max_host_ms")


def _read_sched(monkeypatch, iterations):
    """Record the given iterations as the scheduler would (phase spans
    in the order they end, under a tap like the benchmark's) and give
    what each of the nine readers makes of them.  An iteration is a
    list of (phase, seconds, attrs); 1 ms passes before every
    iteration's first phase, under no span."""
    import os
    import sys

    perf = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perf")
    monkeypatch.syspath_prepend(perf)
    monkeypatch.delitem(sys.modules, "common", raising=False)
    import common

    tap = common.SpanTap()
    tap.arm()
    try:
        t = 1000.0
        for phases in iterations:
            t += 0.001
            for name, dur, attrs in phases:
                tracing.record_span("generation.phase." + name, t, dur,
                                    **attrs)
                t += dur
    finally:
        tap.disarm()
    run = common.Run()
    run.spans = tap.records
    return {name: common.load_module(os.path.join(
        perf, "metrics", name + ".py")).compute(run)
        for name in _SCHED_READERS}


def _iteration(deliver=0.001, admit=0.002, build=0.001, dispatch=0.003,
               sample=0.002, lock=0.0005, kind="decode"):
    return [p for p in (("deliver", deliver, {}),
                        ("admit", admit, {"lock_wait_s": lock}),
                        ("build", build, {}), (kind, dispatch, {}),
                        ("sample", sample, {})) if p[1] is not None]


def test_scheduler_iteration_readers_on_known_spans(monkeypatch):
    """Three counted iterations: a plain one (1 ms of it under no
    span), one stalled 0.5 s in `sample`, one with no delivery and a
    `prefill` dispatch.  Before them the read that opens the window's
    first period; after them two the readers leave out: the loop went
    round twice (an idle poll), and a speculative tick."""
    got = _read_sched(monkeypatch, [
        [("sample", 0.002, {})],
        _iteration(),
        _iteration(sample=0.5, lock=0.0015),
        _iteration(deliver=None, sample=0.003, lock=0.001,
                   kind="prefill"),
        [("admit", 0.3, {})] + _iteration(),
        [("draft_verify", 0.2, {})] + _iteration()])
    # periods 10, 508 and 10 ms, a millisecond of each under no span
    assert got["sched_iteration_max_ms"] == pytest.approx(508.0)
    assert got["sched_iteration_max_host_ms"] == pytest.approx(8.0)
    assert got["sched_host_busy_share"] == pytest.approx(
        100 * (1 - 0.505 / 0.528))
    assert got["sched_admit_ms"] == pytest.approx(2.0)
    assert got["sched_build_ms"] == pytest.approx(1.0)
    assert got["sched_dispatch_ms"] == pytest.approx(3.0)
    assert got["sched_deliver_ms"] == pytest.approx(2.0 / 3)
    assert got["sched_host_unattributed_share"] == pytest.approx(
        100 * 0.003 / 0.023)
    # every admit of the window, the left-out iterations' too
    assert got["sched_lock_wait_ms"] == pytest.approx(
        1e3 * (0.0005 * 3 + 0.0015 + 0.001) / 5)


def test_scheduler_iteration_readers_say_nothing_when_they_cannot(
        monkeypatch):
    """A program whose phases do not tile the iteration (no `build`:
    the parent of PR 36) gives none of the nine; a span store that has
    dropped records gives no `sched_lock_wait_ms` and leaves the
    readers of durations, which read the tap's own list, alone."""
    old = [_iteration(build=None, lock=None)[:1]
           + [("admit", 0.002, {})] + _iteration(build=None)[2:]
           for _ in range(3)]
    assert set(_read_sched(monkeypatch, old).values()) == {None}
    tracing.clear()
    monkeypatch.setattr(tracing, "_dropped", 1)
    got = _read_sched(monkeypatch, [_iteration() for _ in range(3)])
    assert got.pop("sched_lock_wait_ms") is None
    assert None not in got.values()
    assert got["sched_iteration_max_ms"] == pytest.approx(10.0)


# ---------------------------------------------------------------------------
# the six readers of a span's `cpu` (PR 51: perf/metrics/span_cpu.py)
# ---------------------------------------------------------------------------

_CPU_READERS = ("sched_host_offcpu_share", "sched_dispatch_offcpu_ms",
                "sched_iteration_max_offcpu_ms", "reader_pack_offcpu_share",
                "reader_run_offcpu_share", "train_step_max_ms")


def _read_cpu(monkeypatch, body):
    return _read_executor(monkeypatch, body, names=_CPU_READERS)


def _spans_by_hand(with_cpu):
    """Three scheduler iterations after the read that opens the first
    period (phases of 1, 2, 1, 4 and 2 ms; the second's dispatch takes
    104 ms), four packs of 20 ms on the worker and four steps of an
    executor.  `off` is the part of a span its thread did not run."""
    def record(name, t, dur, off, **attrs):
        if with_cpu:
            attrs["cpu"] = dur - off
        tracing.record_span(name, t, dur, **attrs)
        return t + dur

    def body():
        t = record("generation.phase.sample", 1000.0, 0.002, 0.002)
        for dispatch, off in ((0.004, 0.001), (0.104, 0.100),
                              (0.004, 0.001)):
            t = record("generation.phase.deliver", t, 0.001, 0.0)
            t = record("generation.phase.admit", t, 0.002, 0.0005)
            t = record("generation.phase.build", t, 0.001, 0.0)
            t = record("generation.phase.decode", t, dispatch, off)
            t = record("generation.phase.sample", t, 0.002, 0.002)
        for i in range(4):
            record("trainer.phase.feed_pack", t, 0.020, 0.005)
            record("executor.feed", t, 0.002, 0.0)
            record("executor.dispatch", t + 0.002, 0.006, 0.002)
            record("executor.fetch", t + 0.008, 0.090, 0.090)
            period = {} if i == 0 or not with_cpu else {
                "period_s": 0.3 if i == 2 else 0.1}
            t = record("executor.run", t, 0.1, 0.09, mode="compiled",
                       **period)
    return body


def test_cpu_readers_on_known_spans(monkeypatch):
    got = _read_cpu(monkeypatch, _spans_by_hand(with_cpu=True))
    # host phases: 3 x (1 + 2 + 1) + 4 + 104 + 4 = 124 ms, of which
    # 3 x 0.5 + 1 + 100 + 1 = 103.5 ms off the CPU
    assert got["sched_host_offcpu_share"] == pytest.approx(
        100 * 0.1035 / 0.124)
    assert got["sched_dispatch_offcpu_ms"] == pytest.approx(102.0 / 3)
    # the longest iteration is the second: its admit's 0.5 ms and its
    # dispatch's 100
    assert got["sched_iteration_max_offcpu_ms"] == pytest.approx(100.5)
    assert got["reader_pack_offcpu_share"] == pytest.approx(25.0)
    assert got["reader_run_offcpu_share"] == pytest.approx(25.0)
    assert got["train_step_max_ms"] == pytest.approx(300.0)


def test_cpu_readers_say_nothing_when_they_cannot(monkeypatch):
    """A program whose records carry no `cpu` and whose `executor.run`
    has no `period_s` (the parent of PR 51) gives none of the six, and
    neither does a span store that has dropped records."""
    got = _read_cpu(monkeypatch, _spans_by_hand(with_cpu=False))
    assert set(got.values()) == {None}
    tracing.clear()
    monkeypatch.setattr(tracing, "_dropped", 1)
    got = _read_cpu(monkeypatch, _spans_by_hand(with_cpu=True))
    assert set(got.values()) == {None}
    tracing.clear()
    monkeypatch.setattr(tracing, "_dropped", 0)
    assert set(_read_cpu(monkeypatch, lambda: None).values()) == {None}


def test_cpu_readers_on_the_programs_own_spans(monkeypatch):
    """Steps of a Program recorded by `Executor.run` itself: every step
    but the executor's first carries `period_s`, and the run's host
    work reads as a share between 0 and 100."""
    main, startup, loss = _classifier()
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    feed = _batch()

    def body():
        exe.run(startup, scope=scope)
        for _ in range(5):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)

    got = _read_cpu(monkeypatch, body)
    runs = _named("executor.run")
    assert ["period_s" in s["attrs"] for s in runs] == [False] + [True] * 5
    ends = [s["ts"] + s["dur"] for s in runs]
    for s, a, b in zip(runs[1:], ends, ends[1:]):
        assert s["attrs"]["period_s"] == pytest.approx(b - a, abs=2e-3)
    assert got["train_step_max_ms"] == pytest.approx(
        1e3 * max(s["attrs"]["period_s"] for s in runs[1:]))
    assert 0.0 <= got["reader_run_offcpu_share"] <= 100.0
    exe.close()


# ---------------------------------------------------------------------------
# the two readers of `Executor.run`'s record of its states (PR 39:
# perf/metrics/reader_state_reuse_share.py, reader_dispatch_share.py)
# ---------------------------------------------------------------------------


def _read_executor(monkeypatch, body, names=("reader_state_reuse_share",
                                             "reader_dispatch_share")):
    """Run `body()` under a tap like the benchmark's and give what the
    readers called `names` make of the spans it left."""
    import os
    import sys

    perf = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perf")
    monkeypatch.syspath_prepend(perf)
    monkeypatch.delitem(sys.modules, "common", raising=False)
    import common

    tap = common.SpanTap()
    tap.arm()
    try:
        body()
    finally:
        tap.disarm()
    run = common.Run()
    run.spans = tap.records
    return {name: common.load_module(os.path.join(
        perf, "metrics", name + ".py")).compute(run)
        for name in names}


def _steps_by_hand(attrs):
    """Four steps of 10 ms as `Executor.run` records them: 2 ms under
    `executor.feed` (with each step's `attrs`), 1 ms under
    `executor.dispatch`, 6 ms under `executor.fetch`."""
    def body():
        t = 1000.0
        for a in attrs:
            tracing.record_span("executor.feed", t + 0.001, 0.002, **a)
            tracing.record_span("executor.dispatch", t + 0.003, 0.001)
            tracing.record_span("executor.fetch", t + 0.004, 0.006)
            tracing.record_span("executor.run", t, 0.010, mode="compiled")
            t += 0.010
    return body


@pytest.mark.parametrize("attrs, reuse", [
    ([{"states": 429, "recommitted": 0}] * 4, 100.0),
    ([{"states": 429, "recommitted": 429}]
     + [{"states": 429, "recommitted": 1}] * 3,
     100.0 * (1 - 432 / 1716)),
    # a program that commits every state at every step says nothing of it
    ([{}] * 4, None),
    ([{"states": 0, "recommitted": 0}] * 4, None),
], ids=["all_kept", "one_replaced_a_step", "no_attributes", "no_states"])
def test_state_reuse_and_dispatch_readers_on_known_spans(monkeypatch,
                                                         attrs, reuse):
    got = _read_executor(monkeypatch, _steps_by_hand(attrs))
    assert got["reader_state_reuse_share"] == (
        None if reuse is None else pytest.approx(reuse))
    # the dispatch's share needs no attribute: 4 ms of the 40 spanned
    assert got["reader_dispatch_share"] == pytest.approx(10.0)


def test_state_reuse_and_dispatch_readers_on_the_programs_own_spans(
        monkeypatch):
    """Five steps of a Program with five states, recorded by
    `Executor.run` itself: the first finds none of them in its record,
    the others all; without spans the readers say nothing."""
    main, startup, loss = _classifier()
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    feed = _batch()

    def body():
        for _ in range(5):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)

    got = _read_executor(monkeypatch, body)
    feeds = _named("executor.feed")
    assert [(s["attrs"]["states"], s["attrs"]["recommitted"])
            for s in feeds] == [(5, 5)] + [(5, 0)] * 4
    assert got["reader_state_reuse_share"] == pytest.approx(80.0)
    assert 0 < got["reader_dispatch_share"] < 100
    assert exe.cache_stats()["state_commits"] == 5
    exe.close()
    tracing.clear()
    assert set(_read_executor(monkeypatch, lambda: None).values()) == {None}


# ---------------------------------------------------------------------------
# the two readers of a synchronous step's serial section (PR 61:
# perf/metrics/reader_serial_host_ms.py, reader_aux_dispatches.py)
# ---------------------------------------------------------------------------

_SERIAL = ("reader_serial_host_ms", "reader_aux_dispatches")


def _sync_steps_by_hand(run_attrs, fetch=True, tail_ms=(4.0,) * 4):
    """Steps as a loop that reads its loss records them: the read ends
    with the step, and `tail_ms[i]` later the next step's dispatch
    ends (1 ms of it the dispatch's own span)."""
    def body():
        t = 1000.0
        for attrs, tail in zip(run_attrs, tail_ms):
            tracing.record_span("executor.dispatch", t + tail / 1e3 - 0.001,
                                0.001)
            if fetch:
                tracing.record_span("executor.fetch", t + tail / 1e3, 0.006)
            tracing.record_span("executor.run", t, tail / 1e3 + 0.006,
                                mode="compiled", **attrs)
            t += tail / 1e3 + 0.006
    return body


@pytest.mark.parametrize("run_attrs, fetch, tail_ms, serial, aux", [
    ([{"aux_dispatches": 0}] * 4, True, (4.0,) * 4, 4.0, 0.0),
    # the first dispatch follows no read; the median of 5, 3, 2
    ([{"aux_dispatches": 2}] * 4, True, (9.0, 5.0, 3.0, 2.0), 3.0, 2.0),
    ([{"aux_dispatches": 2}] + [{"aux_dispatches": 0}] * 3, True,
     (4.0,) * 4, 4.0, 0.5),
    # a parent's spans carry no such attribute: the gap reads, the count
    # says nothing
    ([{}] * 4, True, (4.0,) * 4, 4.0, None),
    # a loop that leaves its results on the device records no read
    ([{"aux_dispatches": 0}] * 4, False, (4.0,) * 4, None, 0.0),
], ids=["steady", "known_gaps", "one_eager_run", "parent_without_attribute",
        "store_without_fetch"])
def test_serial_section_readers_on_known_spans(monkeypatch, run_attrs, fetch,
                                               tail_ms, serial, aux):
    got = _read_executor(
        monkeypatch, _sync_steps_by_hand(run_attrs, fetch, tail_ms),
        names=_SERIAL)
    assert got["reader_serial_host_ms"] == (
        None if serial is None else pytest.approx(serial, abs=1e-3))
    assert got["reader_aux_dispatches"] == (
        None if aux is None else pytest.approx(aux))


def test_serial_section_pairs_a_dispatch_with_its_own_threads_read(
        monkeypatch):
    """A read that ended on another thread (an evaluation beside the
    loop) opens no gap on this one."""
    import threading

    def body():
        tracing.record_span("executor.fetch", 1000.000, 0.002)

        def other():
            tracing.record_span("executor.fetch", 1000.004, 0.001)
        th = threading.Thread(target=other)
        th.start()
        th.join(timeout=10)
        assert not th.is_alive()
        tracing.record_span("executor.dispatch", 1000.006, 0.001)
    got = _read_executor(monkeypatch, body, names=_SERIAL)
    assert got["reader_serial_host_ms"] == pytest.approx(5.0, abs=1e-3)


def test_serial_section_readers_on_the_programs_own_spans(monkeypatch):
    """Five compiled steps and one interpreted, recorded by
    `Executor.run` itself: a gap a step after the first, all positive
    and under the period; no key is made outside a compiled step, two
    dispatches make one for the interpreter."""
    main, startup, loss = _classifier()
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    feed = _batch()

    def body():
        for _ in range(5):
            exe.run(main, feed=feed, fetch_list=[loss], scope=scope)

    got = _read_executor(monkeypatch, body, names=_SERIAL)
    assert got["reader_aux_dispatches"] == 0.0
    runs = _named("executor.run")
    periods = [b["ts"] + b["dur"] - a["ts"] - a["dur"]
               for a, b in zip(runs, runs[1:])]
    assert 0 < got["reader_serial_host_ms"] < 1e3 * max(periods)
    assert [s["attrs"]["aux_dispatches"] for s in runs] == [0] * 5
    tracing.clear()
    got = _read_executor(
        monkeypatch,
        lambda: exe.run(main, feed=feed, fetch_list=[loss], scope=scope,
                        compiled=False), names=_SERIAL)
    assert got["reader_aux_dispatches"] == 2.0
    assert exe.cache_stats()["aux_dispatches"] == 2
    exe.close()
    tracing.clear()
    assert set(_read_executor(monkeypatch, lambda: None,
                              names=_SERIAL).values()) == {None}


# ---------------------------------------------------------------------------
# B. the executors' three children, the trainer's reader phase
# ---------------------------------------------------------------------------


def _classifier():
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="int64")
        h = fluid.layers.fc(input=x, size=32, act="relu")
        loss = fluid.layers.mean(fluid.layers.softmax_with_cross_entropy(
            fluid.layers.fc(input=h, size=4), y))
        fluid.SGD(learning_rate=0.1).minimize(loss)
    return main, startup, loss


def _batch():
    r = np.random.RandomState(0)
    return {"x": r.randn(32, 16).astype(np.float32),
            "y": r.randint(0, 4, (32, 1)).astype(np.int64)}


def _run_serial(feed, **kw):
    main, startup, loss = _classifier()
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    tracing.clear()
    exe.run(main, feed=feed, fetch_list=[loss], scope=scope, **kw)
    exe.close()


def _run_parallel(feed, **kw):
    main, startup, loss = _classifier()
    pe = parallel.ParallelExecutor(main, ["x", "y"], [loss],
                                   mesh={"dp": 8},
                                   startup_program=startup)
    tracing.clear()
    pe.run(feed, **kw)
    pe.close()


@pytest.mark.parametrize("run", [_run_serial, _run_parallel])
def test_executor_run_has_feed_dispatch_fetch_children(run):
    feed = _batch()
    with listening():
        run(feed)
        (parent,) = _named("executor.run")
        kids = {n: _named(f"executor.{n}")
                for n in ("feed", "dispatch", "fetch")}
        assert all(len(v) == 1 for v in kids.values()), kids
        for (k,) in kids.values():
            assert k["parent_id"] == parent["span_id"]
            assert k["trace_id"] == parent["trace_id"]
            assert k["ts"] >= parent["ts"]
        assert sum(k[0]["dur"] for k in kids.values()) <= parent["dur"]
        # with the results left on the device there is no wait to record
        run(feed, return_numpy=False)
        assert len(_named("executor.run")) == 1
        assert _named("executor.fetch") == []
        assert len(_named("executor.feed")) == 1


class _SleepyFeed(dict):
    """A feed whose arrays take `nap` seconds to hand over, once."""
    nap = 0.0

    def items(self):
        import time

        nap, self.nap = self.nap, 0.0
        time.sleep(nap)
        return super().items()


def _serial_stepper():
    main, startup, loss = _classifier()
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    return exe, lambda feed: exe.run(main, feed=feed, fetch_list=[loss],
                                     scope=scope)


def _parallel_stepper():
    main, startup, loss = _classifier()
    pe = parallel.ParallelExecutor(main, ["x", "y"], [loss],
                                   mesh={"dp": 8},
                                   startup_program=startup)
    return pe, pe.run


@pytest.mark.parametrize("where", ["outside", "feed"])
@pytest.mark.parametrize("stepper", [_serial_stepper, _parallel_stepper],
                         ids=["serial", "parallel"])
def test_slow_executor_step_is_kept_noted_and_on_the_span(stepper, where,
                                                          monkeypatch):
    """The executors time every `run` with tracing on or off: one that
    a 50 ms sleep makes slow, between two calls or inside the feed, is
    kept in `slow_steps()` under that part with the thread's CPU
    seconds beside the wall's, noted to the flight recorder as
    `executor.slow_step`, and a live `executor.run` span carries the
    seconds since the previous `run` ended."""
    import time

    notes = []
    monkeypatch.setattr(flightrecorder, "note",
                        lambda event, **data: notes.append((event, data)))
    exe, step = stepper()
    feed = _SleepyFeed(_batch())

    def slow_step():
        if where == "outside":
            time.sleep(0.05)
        else:
            feed.nap = 0.05
        step(feed)

    # 64 iterations give the clock its reference period
    for _ in range(70):
        step(feed)
    assert exe._clock.reference is not None
    assert tracing.finished_spans() == []
    def kept():
        return [r for r in exe.slow_steps()
                if r["phase"] == where and r["phase_ms"] >= 50.0]

    # (a loaded host may make another part of that step longer still:
    # then once more)
    for _ in range(3):
        slow_step()
        if kept():
            break
    rec = kept()[-1]
    assert rec["ms"] > 2 * rec["reference_ms"] > 0
    assert rec["cpu_ms"] < 25.0 <= rec["offcpu_ms"]
    assert rec["vol_switches"] >= 1
    assert {"process_cpu_ms", "invol_switches", "minor_faults",
            "major_faults", "gen2_collections"} <= set(rec)
    assert ("executor.slow_step", rec) in notes
    assert len(exe.slow_steps()) <= 8
    with listening():
        step(feed)
        slow_step()
    quick, slow = _named("executor.run")[-2:]
    assert quick["attrs"]["period_s"] > 0.0
    assert slow["attrs"]["period_s"] >= 0.05
    assert exe.slow_steps()[-1]["ms"] >= 50.0
    exe.close()


@pytest.mark.parametrize("sync_every_n", [1, 2])
def test_trainer_reader_phase_fires_once_a_batch(sync_every_n):
    r = np.random.RandomState(7)
    data = [[(r.rand(16).astype(np.float32), r.rand(1).astype(np.float32))
             for _ in range(8)] for _ in range(5)]
    reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data(name="x", shape=[16], dtype="float32")
        y = fluid.layers.data(name="y", shape=[1], dtype="float32")
        p = fluid.layers.fc(input=fluid.layers.fc(input=x, size=8,
                                                  act="relu"), size=1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(input=p, label=y))
        fluid.SGD(learning_rate=0.05).minimize(loss)
    assert "reader" in attribution.PHASES["trainer"]
    with fluid.scope_guard(fluid.Scope()), listening():
        t = trainer_mod.Trainer(loss, place=fluid.CPUPlace(),
                                feed_list=[x, y], main_program=main,
                                startup_program=startup)
        tracing.clear()
        t.train(2, lambda: iter(data), sync_every_n=sync_every_n)
        reads = _named("trainer.phase.reader")
        assert len(reads) == 2 * len(data)
        packs = _named("trainer.phase.feed_pack")
        assert len(packs) == len(_named("trainer.phase.h2d")) == len(reads)
        steps = _named("trainer.step")
        assert len(steps) == len(reads)
        assert all("error" not in s["attrs"] for s in reads)
        # the batch is read, packed and staged on the worker's thread,
        # the step runs on the caller's; one trace holds both
        assert {s["thread"] for s in reads + packs} == {
            "paddle-tpu-prefetch"}
        assert {s["thread"] for s in steps}.isdisjoint(
            {"paddle-tpu-prefetch"})
        assert all({"feed_ready", "feed_wait_s"} <= set(s["attrs"])
                   for s in steps)


def test_phased_iter_times_the_pull_and_is_plain_when_off():
    def slow():
        import time

        for i in range(3):
            time.sleep(0.002)
            yield i

    assert list(attribution.phased_iter("trainer", "reader", slow())) == [
        0, 1, 2]
    assert tracing.finished_spans() == []
    with listening():
        with tracing.span("epoch") as ep:
            assert list(attribution.phased_iter(
                "trainer", "reader", slow())) == [0, 1, 2]
    reads = _named("trainer.phase.reader")
    assert len(reads) == 3 and all(s["dur"] >= 0.002 for s in reads)
    assert all(s["parent_id"] == ep.context.span_id for s in reads)


# ---------------------------------------------------------------------------
# C. names on the device's time
# ---------------------------------------------------------------------------

_PARTS = ("embed", "qkv", "kv_write", "kv_gather", "attention", "attn_out",
          "mlp", "head", "sample")


def _greedy(dec, states, steps=6):
    pool_k, pool_v = dec.init_pool(9)
    tables = np.arange(1, 9, dtype=np.int32).reshape(2, 4).repeat(2, 1)
    tables = np.ascontiguousarray(tables[:, :8])
    toks = np.array([3, 7], np.int32)
    out = []
    for pos in range(steps):
        nxt, pool_k, pool_v = dec.step(
            states, pool_k, pool_v, tables, np.full(2, pos, np.int32), toks,
            np.zeros(2, np.uint32), np.zeros(2, np.float32),
            np.ones(2, bool))
        toks = np.asarray(nxt)
        out.append(toks.tolist())
    return out


@pytest.mark.parametrize("kv_dtype", ["fp32", "bf16", "int8"])
def test_paged_decoder_step_carries_scopes_and_same_tokens(kv_dtype,
                                                           monkeypatch):
    srv, dec = _tiny_server(kv_dtype)
    states = dict(srv._states)
    srv.close()
    table = profiler.hlo_scopes("paged_decoder.step")["paged_decoder.step"]
    scopes = set(table.values())
    for part in ("kv_gather", "attention"):
        assert any(f"paged_decoder/{part}" in s for s in scopes), part
    # the traced module names every part; XLA may fuse a small one away
    lowered = dec.step.lower(*profiler._arg_specs(
        states, *dec.init_pool(9), np.zeros((4, 8), np.int32),
        *[np.zeros(4, t) for t in (np.int32, np.int32, np.uint32,
                                   np.float32, bool)]))
    text = profiler.lowered_ir_text(lowered)
    for part in _PARTS:
        assert f"paged_decoder/{part}" in text, part
    # metadata only: the same decoder traced without any scope gives
    # the same greedy tokens (the parent's step, as it was)
    want = _greedy(dec, states)
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    reset_unique_names()
    _, bare = build_lm_paged_decoder(50, 4, 8, d_model=32, n_heads=4,
                                     n_layers=2, kv_dtype=kv_dtype)
    bare_text = profiler.lowered_ir_text(bare.step.lower(
        *profiler._arg_specs(
            states, *bare.init_pool(9), np.zeros((4, 8), np.int32),
            *[np.zeros(4, t) for t in (np.int32, np.int32, np.uint32,
                                       np.float32, bool)])))
    assert "paged_decoder/" not in bare_text
    assert _greedy(bare, states) == want


def test_hlo_scopes_outlive_executor_close_and_join_a_trace():
    _run_serial(_batch())               # startup and main, then close()
    tables = profiler.hlo_scopes()
    assert sorted(tables) == ["executor.block", "executor.block#2"]
    main_table = tables["executor.block#2"]
    assert main_table and profiler.hlo_scopes("nothing") == {}
    types = {c.split(":")[0] for s in main_table.values()
             for c in s.split("/") if ":" in c}
    assert {"mul", "softmax_with_cross_entropy", "sgd"} <= types
    # the join: seconds by instruction -> seconds by scope, on the table
    # that covers the most of them
    op = next(k for k, s in main_table.items() if "mul:" in s)
    by_scope = profiler.scope_seconds({op: 2.0, "not-an-op": 0.5},
                                      "executor.block")
    assert by_scope[main_table[op]] == 2.0 and by_scope[""] == 0.5
    profiler.reset_profiler()
    assert profiler.hlo_scopes() == {}


def test_registry_keeps_only_its_cap_of_programs_alive_past_close():
    """What `hlo_scopes()` costs a process that never asks: the last
    `_HLO_PROVIDERS_CAP` step functions (with their Programs) stay alive
    past `Executor.close()`; an older one goes with its executor."""
    import gc
    import weakref

    main, startup, loss = _classifier()
    exe, scope = fluid.Executor(fluid.CPUPlace()), fluid.Scope()
    exe.run(startup, scope=scope)
    exe.run(main, feed=_batch(), fetch_list=[loss], scope=scope)
    exe.close()
    block = weakref.ref(main.global_block())
    del main, startup, loss, scope
    gc.collect()
    assert block() is not None          # held by the registry alone
    assert len(profiler._hlo_text_providers) == 2
    for _ in range(profiler._HLO_PROVIDERS_CAP):     # later executables
        profiler.register_jitted("x", jax.jit(lambda: 0))
    assert len(profiler._hlo_text_providers) == profiler._HLO_PROVIDERS_CAP
    gc.collect()
    assert block() is None


def test_compiled_text_names_this_builds_scopes(compile_cache):
    """JAX keys its compile cache without metadata: the executable a
    build left there comes back with THAT build's scope names, also for
    the build that runs now.  `_compiled_text` keys its compile with
    metadata (for its own thread, not the process), so its table names
    this build's."""
    import jax.numpy as jnp

    def build(scope):
        @jax.jit
        def f(x):
            with jax.named_scope(scope):
                return jnp.tanh(x @ x) + 1
        return f

    x = jnp.ones((8, 8))
    build("old_name")(x)                        # fills the cache
    new = build("new_name")
    new(x)                                      # a hit: the old names
    assert "old_name/" in new.lower(x).compile().as_text()
    text = profiler._compiled_text(lambda: new.lower(x))
    assert "new_name/" in text and "old_name/" not in text
    assert not jax.config.jax_compilation_cache_include_metadata_in_key


def test_a_jax_without_the_metadata_key_fails_loudly(monkeypatch):
    monkeypatch.setattr(profiler, "_METADATA_KEY_OPTION", "no_such_option")
    profiler.register_jitted("f", jax.jit(lambda x: x + 1), np.ones(2))
    with pytest.raises(profiler.ScopeNamesUnavailable):
        profiler.hlo_scopes()
    with pytest.raises(profiler.ScopeNamesUnavailable):
        profiler.scope_seconds({"add.1": 1.0}, "f")


def test_scope_map_gives_compiler_made_instructions_their_producers_scope():
    hlo = '''
  %fusion.3 = f32[4,8]{1,0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(step)/paged_decoder/kv_gather/gather" stack_frame_id=3}
  %reshape.100 = f32[4,2,4]{2,1,0} reshape(%fusion.3), backend_config={"flag_configs":[]}
  %copy-start.1 = (f32[4,2,4], u32[]) copy-start(%reshape.100)
  %copy-done.1 = f32[4,2,4] copy-done(%copy-start.1)
  %lonely.7 = f32[] constant(0)
'''
    table, inherited = profiler._scope_tables(hlo)
    want = "jit(step)/paged_decoder/kv_gather/gather"
    assert table == profiler._scope_map(hlo) == {
        "fusion.3": want, "reshape.100": want,
        "copy-start.1": want, "copy-done.1": want}
    assert inherited == {"reshape.100", "copy-start.1", "copy-done.1"}
    # a reader can tell the compiler's word from the heuristic
    profiler._register_hlo_text("step", lambda: hlo)
    ops = {"fusion.3": 1.0, "reshape.100": 2.0, "copy-done.1": 0.5,
           "elsewhere": 4.0}
    assert profiler.scope_seconds(ops, "step") == {want: 3.5, "": 4.0}
    assert profiler.scope_seconds(ops, "step", inherited_only=True) == {
        want: 2.5}


# ---------------------------------------------------------------------------
# F. what it costs when everything is off
# ---------------------------------------------------------------------------


def test_every_new_site_is_the_shared_noop_when_all_is_off(monkeypatch):
    assert not (metrics.enabled() or tracing.enabled()
                or tracing._listeners)
    made = []
    monkeypatch.setattr(tracing.Span, "__init__",
                        lambda self, *a, **k: made.append(a))
    monkeypatch.setattr(tracing.Span, "set_attr",
                        lambda self, *a: made.append(a))
    monkeypatch.setattr(tracing, "_store", made.append)
    for name in ("executor.feed", "executor.dispatch", "executor.fetch",
                 "serving.decode_tick"):
        assert tracing.span(name) is tracing._NOOP
    assert attribution.phase("trainer", "reader") is attribution._NOOP
    feed = _batch()
    _run_serial(feed)
    _run_parallel(feed)
    srv, _ = _tiny_server()
    try:
        assert len(srv.submit([1, 2, 3], 4).result(60)) == 4
        assert srv._request_span(object(), 0.0) is None
    finally:
        srv.close()
    assert list(attribution.phased_iter("trainer", "reader", [1, 2])) == [
        1, 2]
    assert made == [] and tracing.finished_spans() == []


# ---------------------------------------------------------------------------
# the exponentials over [tokens, vocab] a traced training step makes (PR 52:
# perf/metrics/train_head_exp_passes.py)
# ---------------------------------------------------------------------------

_HEAD_HLO = """\
HloModule jit_fn

%fused_computation.sum (p: bf16[8,32]) -> f32[8] {
  %p = bf16[8,32]{1,0} parameter(0)
  %e.1 = f32[8,32]{1,0} exponential(%p)
  ROOT %r = f32[8]{0} reduce(%e.1), dimensions={1}
}

%fused_computation.d (p: bf16[8,32]) -> bf16[8,32] {
  %p = bf16[8,32]{1,0} parameter(0)
  ROOT %e.2 = bf16[8,32]{1,0:T(8,128)(2,1)} exponential(%p)
}

%fused_computation.product (p: bf16[8,32], w: bf16[32,4]) -> bf16[8,4] {
  %p = bf16[8,32]{1,0} parameter(0)
  %w = bf16[32,4]{1,0} parameter(1)
  %clone.1 = bf16[8,32]{1,0} fusion(%p), kind=kLoop, calls=%fused_computation.d
  ROOT %dot = bf16[8,4]{1,0} convolution(%clone.1, %w)
}

%fused_computation.plain (p: bf16[8,32], w: bf16[32,4]) -> bf16[8,4] {
  %p = bf16[8,32]{1,0} parameter(0)
  %w = bf16[32,4]{1,0} parameter(1)
  ROOT %dot.2 = bf16[8,4]{1,0} convolution(%p, %w)
}

%fused_computation.rows (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %e.3 = f32[8]{0} exponential(%p)
}

ENTRY %main (a: bf16[8,32], w: bf16[32,4]) -> bf16[8,4] {
  %a = bf16[8,32]{1,0} parameter(0)
  %w = bf16[32,4]{1,0} parameter(1)
  %fusion.5 = f32[8]{0} fusion(%a), kind=kInput, calls=%fused_computation.sum
  %rows = f32[8]{0} fusion(%fusion.5), kind=kLoop, calls=%fused_computation.rows
  %written = bf16[8,32]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.d
  %product = bf16[8,4]{1,0} fusion(%a, %w), kind=kOutput, calls=%fused_computation.product
  %plain = bf16[8,4]{1,0} fusion(%written, %w), kind=kOutput, calls=%fused_computation.plain
  %alone = f32[2,4,32]{2,1,0} exponential(%a)
  ROOT %untimed = bf16[8,4]{1,0} fusion(%a, %w), kind=kOutput, calls=%fused_computation.product
}
"""


def test_head_exp_passes_reader_on_hand_made_text(monkeypatch):
    """`train_head_exp_passes` names the timed instructions that
    evaluate an exponential over tokens x vocab elements: a fusion
    whose body holds one, a fusion whose body holds a NESTED fusion
    that does (a producer cloned into its consumer), an exponential
    that stands alone; not a product that reads a written operand, not
    an exponential over the rows alone, not the inner clone itself, and
    not an instruction the slice did not time.  `None` without a device
    plane or a registered text."""
    import os
    import sys
    import types

    perf = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "perf")
    monkeypatch.syspath_prepend(perf)
    monkeypatch.delitem(sys.modules, "common", raising=False)
    import common

    reader = common.load_module(os.path.join(
        perf, "metrics", "train_head_exp_passes.py"))
    assert reader.exponential_passes(_HEAD_HLO, 8 * 32) == [
        "fusion.5", "written", "product", "alone", "untimed"]
    assert reader.exponential_passes(_HEAD_HLO, 8 * 32 + 1) == []

    cell = types.SimpleNamespace(
        traffic={"sequence_length": 4, "sequences_per_step": 2},
        config={"vocab_size": 32})
    timed = {name: 0.01 for name in
             ("fusion.5", "rows", "written", "product", "plain", "alone")}
    run = types.SimpleNamespace(cell=cell, trace={"op_seconds": timed})
    assert reader.compute(run) is None          # no text registered
    profiler._register_hlo_text("executor.block", lambda: "ENTRY %m {\n}")
    profiler._register_hlo_text("executor.block", lambda: _HEAD_HLO)
    profiler._register_hlo_text("paged_decoder.step", lambda: _HEAD_HLO * 2)
    assert reader.compute(run) == 4             # `untimed` is left out
    del timed["product"], timed["alone"]
    assert reader.compute(run) == 2             # the change's reading
    run.trace = {"op_seconds": {}}
    assert reader.compute(run) is None          # no device plane
    run.trace = None
    assert reader.compute(run) is None
