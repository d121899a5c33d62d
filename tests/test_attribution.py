"""Time-attribution plane: phase instrumentation, why-tables,
histogram exemplars (record -> export -> federate -> trace-of),
tail-sampled traces, straggler scores and calibration drift
(docs/observability.md "Time attribution")."""
import json
import os
import time

import pytest

from paddle_tpu import cli
from paddle_tpu.observability import (attribution, collector, exemplars,
                                      exporters, flightrecorder, metrics,
                                      timeseries, tracing)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_observability():
    metrics.set_enabled(False)
    tracing.set_enabled(False)
    tracing.clear()
    tracing.disarm_tail_sampler()
    exemplars.set_armed(False)
    flightrecorder.uninstall()
    # several attribution surfaces (phase_family, publish_static_floor)
    # write into the GLOBAL registry; snapshot/restore its family dict
    # so tests neither see each other's observations nor orphan the
    # module-level instruments other test files assert on
    reg = metrics.registry()
    with reg._lock:
        saved = dict(reg._metrics)
        # evict attribution-owned families so each test observes into a
        # fresh one (earlier test files may have run whole servers with
        # metrics on, leaving counts in the shared family); the restore
        # below puts the originals back and the phase-child cache
        # self-heals on family-identity mismatch either way
        for name in list(reg._metrics):
            if ("_phase_seconds" in name or "_phase_static_seconds" in name
                    or name in (attribution.STRAGGLER_METRIC,
                                attribution.CALIBRATION_METRIC)):
                del reg._metrics[name]
    yield
    with reg._lock:
        reg._metrics.clear()
        reg._metrics.update(saved)
    metrics.set_enabled(False)
    tracing.set_enabled(False)
    tracing.clear()
    tracing.disarm_tail_sampler()
    exemplars.set_armed(False)
    flightrecorder.uninstall()


def _clocked_store(reg):
    clk = {"t": 0.0}
    store = timeseries.TimeSeriesStore(registry=reg,
                                       clock=lambda: clk["t"])
    return store, clk


# ---------------------------------------------------------------------------
# phase() / observe_phase
# ---------------------------------------------------------------------------


def test_phase_is_noop_when_whole_stack_off():
    """With metrics, tracing and listeners all off, phase() must hand
    back the shared no-op — no per-tick allocation on hot paths."""
    assert attribution.phase("generation", "decode") is attribution._NOOP
    with attribution.phase("generation", "decode"):
        pass  # and it must still be a working context manager


def test_phase_observes_histogram_and_emits_child_span():
    metrics.set_enabled(True)
    tracing.set_enabled(True)
    with tracing.span("serving.decode_tick"):
        with attribution.phase("generation", "decode"):
            time.sleep(0.002)
    fam = attribution.phase_family("generation")
    child = fam.labels(phase="decode")
    assert child.count == 1
    assert child.sum >= 0.002
    spans = [s for s in tracing.finished_spans()
             if s["name"] == "generation.phase.decode"]
    assert len(spans) == 1
    parents = [s for s in tracing.finished_spans()
               if s["name"] == "serving.decode_tick"]
    assert spans[0]["parent_id"] == parents[0]["span_id"]
    assert spans[0]["trace_id"] == parents[0]["trace_id"]


def test_phase_error_attr_marks_span():
    tracing.set_enabled(True)
    with pytest.raises(ValueError):
        with attribution.phase("pserver", "optimize"):
            raise ValueError("boom")
    rec = [s for s in tracing.finished_spans()
           if s["name"] == "pserver.phase.optimize"][0]
    assert rec["attrs"]["error"] == "ValueError"


def test_observe_phase_survives_registry_clear():
    """registry().clear() mints a new family: the child cache must
    re-resolve instead of observing into the orphan (review pin)."""
    metrics.set_enabled(True)
    attribution.observe_phase("trainer", "compute", 0.5)
    metrics.registry().clear()
    attribution.observe_phase("trainer", "compute", 0.25)
    child = attribution.phase_family("trainer").labels(phase="compute")
    assert child.count == 1 and child.sum == pytest.approx(0.25)


def test_publish_static_floor_skips_nonpositive():
    metrics.set_enabled(True)
    attribution.publish_static_floor("generation",
                                     {"decode": 0.004, "sample": 0.0})
    fam = metrics.gauge("paddle_tpu_generation_phase_static_seconds",
                        labelnames=("phase",))
    series = {lbl["phase"]: child.value
              for lbl, child in fam.samples()}
    assert series == {"decode": pytest.approx(0.004)}


# ---------------------------------------------------------------------------
# why-tables
# ---------------------------------------------------------------------------


def _observe_phases(obs):
    for phase_name, seconds in obs:
        attribution.observe_phase("generation", phase_name, seconds)


def test_why_rows_from_parsed_shares_and_table():
    metrics.set_enabled(True)
    _observe_phases([("decode", 0.03), ("decode", 0.03),
                     ("sample", 0.02), ("deliver", 0.02)])
    parsed = collector.parse_prometheus_text(exporters.prometheus_text())
    rows = attribution.why_rows_from_parsed(parsed, "generation")
    by_phase = {r["phase"]: r for r in rows}
    assert by_phase["decode"]["seconds"] == pytest.approx(0.06)
    assert by_phase["decode"]["count"] == 2
    assert by_phase["decode"]["share"] == pytest.approx(0.6)
    assert sum(r["share"] for r in rows) == pytest.approx(1.0)
    # highest share sorts first within the member
    assert rows[0]["phase"] == "decode"
    table = attribution.format_why_table(rows)
    assert "phase" in table.splitlines()[0]
    assert "decode" in table and "60.0%" in table
    assert attribution.format_why_table([]).startswith("no phase data")


def test_why_rows_live_windowed_rates():
    metrics.set_enabled(True)
    reg = metrics.registry()
    store, clk = _clocked_store(reg)
    attribution.observe_phase("generation", "decode", 0.0)
    store.sample_once()
    clk["t"] = 10.0
    for _ in range(10):
        attribution.observe_phase("generation", "decode", 0.5)
    store.sample_once()
    rows = attribution.why_rows(store, "generation", window_s=60.0,
                                now=10.0)
    decode = [r for r in rows if r["phase"] == "decode"][0]
    # 5 s of decode over 10 wall seconds
    assert decode["seconds_per_s"] == pytest.approx(0.5)
    assert decode["calls_per_s"] == pytest.approx(1.0)
    assert decode["mean_s"] == pytest.approx(0.5)


# ---------------------------------------------------------------------------
# straggler detection + calibration drift
# ---------------------------------------------------------------------------


def _endpoint_rounds(reg, rounds):
    h = metrics.histogram(attribution.ENDPOINT_ROUND_METRIC, "",
                          ("endpoint",), registry=reg)
    for ep, vals in rounds.items():
        for v in vals:
            h.labels(endpoint=ep).observe(v)


def test_straggler_scores_flags_slow_endpoint_only():
    metrics.set_enabled(True)
    reg = metrics.MetricsRegistry()
    store, clk = _clocked_store(reg)
    _endpoint_rounds(reg, {"a:1": [], "b:1": [], "c:1": []})
    store.sample_once()
    clk["t"] = 30.0
    _endpoint_rounds(reg, {"a:1": [0.01] * 10, "b:1": [0.011] * 10,
                           "c:1": [0.1] * 10})
    store.sample_once()
    scores = attribution.straggler_scores(store, window_s=60.0,
                                          now=30.0)
    assert scores["c:1"] > 3.0
    assert scores["a:1"] < 1.0 and scores["b:1"] < 1.0


def test_straggler_scores_need_two_endpoints_and_clamp():
    metrics.set_enabled(True)
    reg = metrics.MetricsRegistry()
    store, clk = _clocked_store(reg)
    _endpoint_rounds(reg, {"solo:1": []})
    store.sample_once()
    clk["t"] = 10.0
    _endpoint_rounds(reg, {"solo:1": [0.5]})
    store.sample_once()
    assert attribution.straggler_scores(store, now=10.0) == {}


def test_run_detectors_synthesizes_gauge_families():
    metrics.set_enabled(True)
    reg = metrics.MetricsRegistry()
    store, clk = _clocked_store(reg)
    _endpoint_rounds(reg, {"a:1": [], "b:1": []})
    h = metrics.histogram("paddle_tpu_trainer_phase_seconds", "",
                          ("phase",), registry=reg)
    metrics.gauge("paddle_tpu_trainer_phase_static_seconds", "",
                  ("phase",), registry=reg) \
        .labels(phase="compute").set(0.01)
    store.sample_once()
    clk["t"] = 130.0
    _endpoint_rounds(reg, {"a:1": [0.01] * 5, "b:1": [0.2] * 5})
    for _ in range(5):
        h.labels(phase="compute").observe(0.03)
    store.sample_once()
    synth = attribution.run_detectors(store, window_s=130.0, now=130.0)
    strag = synth[attribution.STRAGGLER_METRIC]
    assert strag["type"] == "gauge"
    scores = {s["labels"]["endpoint"]: s["value"]
              for s in strag["samples"]}
    assert scores["b:1"] > 3.0 and scores["a:1"] == 0.0
    cal = synth[attribution.CALIBRATION_METRIC]
    ratios = {(s["labels"]["kind"], s["labels"]["phase"]): s["value"]
              for s in cal["samples"]}
    assert ratios[("trainer", "compute")] == pytest.approx(3.0)


# ---------------------------------------------------------------------------
# exemplars: record -> export -> parse -> pick
# ---------------------------------------------------------------------------


def _observe_in_span(h, value):
    with tracing.span("req"):
        tid = tracing.current_trace_id()
        h.observe(value)
    return tid


def test_exemplars_recorded_exported_and_picked():
    metrics.set_enabled(True)
    tracing.set_enabled(True)
    exemplars.set_armed(True)
    reg = metrics.MetricsRegistry()
    h = metrics.histogram("paddle_tpu_req_seconds", "",
                          buckets=(0.01, 0.1, 1.0), registry=reg)
    _observe_in_span(h, 0.005)
    for _ in range(20):
        h.observe(0.005)  # bulk traffic outside any span: no exemplar
    slow_tid = _observe_in_span(h, 0.5)
    text = exporters.prometheus_text(reg)
    assert "# {trace_id=" in text
    parsed = collector.parse_prometheus_text(text)
    exs = parsed["paddle_tpu_req_seconds"]["samples"][0]["value"][
        "exemplars"]
    assert exs[1.0]["labels"]["trace_id"] == slow_tid
    ex = attribution.pick_exemplar(parsed, "paddle_tpu_req_seconds",
                                   q=0.99)
    assert ex["trace_id"] == slow_tid
    assert ex["value"] == pytest.approx(0.5)
    assert ex["quantile_s"] is not None
    assert attribution.pick_exemplar(parsed, "nope_seconds") is None


def test_exemplar_reservoir_bounded_latest_k():
    res = exemplars.ExemplarReservoir(k=2)
    for i in range(50):
        res.record(0, float(i), f"t{i}")
    snap = res.snapshot()
    assert [e.trace_id for e in snap[0]] == ["t48", "t49"]


def test_exemplar_wire_format_roundtrip():
    ex = exemplars.Exemplar("4bf92f3577b34da6", 0.25, 1700000000.0)
    parsed = exemplars.parse_exemplar(
        exemplars.format_exemplar(ex)[2:])
    assert parsed["labels"]["trace_id"] == "4bf92f3577b34da6"
    assert parsed["value"] == 0.25 and parsed["ts"] == 1700000000.0
    assert exemplars.render_exemplar(parsed) == \
        exemplars.format_exemplar(ex)
    value, ex2 = exemplars.split_sample_line(
        '7 # {trace_id="abc"} 0.04 1700000000')
    assert value == "7" and ex2["labels"]["trace_id"] == "abc"
    assert exemplars.split_sample_line("42")[1] is None


def _member(coll, kind, series_fn, member=""):
    reg = metrics.MetricsRegistry()
    series_fn(reg)
    ann = collector.announce(coll.registry_addr, kind, member=member,
                             metrics_registry=reg)
    return reg, ann


def test_collector_federates_exemplars_and_reclaims_on_churn():
    """ISSUE satellite: the collector must scrape exemplar-bearing
    text, re-emit the exemplar in its federation output (so a fleet
    p99 resolves to a member trace id), and still reclaim the series
    when the member churns out."""
    metrics.set_enabled(True)
    tracing.set_enabled(True)
    exemplars.set_armed(True)
    coll = collector.TelemetryCollector(period_s=0.05,
                                        scrape_timeout_s=1.0,
                                        fail_limit=1)
    try:
        tids = {}

        def series(reg):
            h = metrics.histogram(
                "paddle_tpu_generation_request_seconds", "",
                buckets=(0.1, 1.0), registry=reg)
            with tracing.span("router.request"):
                tids["slow"] = tracing.current_trace_id()
                h.observe(0.7)

        _, ann = _member(coll, "generation", series)
        assert coll.scrape_once() == {ann.member: True}
        text = coll.federation_text()
        assert f'trace_id="{tids["slow"]}"' in text
        # the federated text itself parses back with the exemplar
        fed = collector.parse_prometheus_text(text)
        ex = attribution.pick_exemplar(
            fed, "paddle_tpu_generation_request_seconds")
        assert ex["trace_id"] == tids["slow"]
        assert ex["labels"]["member"] == ann.member
        # churn: endpoint dies -> series reclaimed, exemplar gone
        ann.http.close()
        coll.scrape_once()
        assert coll.series.points(
            "paddle_tpu_generation_request_seconds",
            {"member": ann.member}) == []
        assert tids["slow"] not in coll.federation_text()
        ann.lease.release()
        coll.scrape_once()
        assert all(x["member"] != ann.member for x in coll.members())
    finally:
        coll.close()


# ---------------------------------------------------------------------------
# tail sampler
# ---------------------------------------------------------------------------


def _span_rec(tid, sid, parent, dur, name="s", **attrs):
    return {"name": name, "trace_id": tid, "span_id": sid,
            "parent_id": parent, "ts": 0.0, "dur": dur,
            "pid": 1, "tid": 2, "attrs": attrs}


def test_tail_sampler_keeps_only_slow_or_errored():
    ts = tracing.TailSampler(threshold_s=0.25)
    # fast, clean trace: root completes -> dropped entirely
    ts(_span_rec("fast", "f1", "f0", 0.01))
    ts(_span_rec("fast", "f0", None, 0.02))
    # slow child marks the trace before its root finishes
    ts(_span_rec("slow", "s1", "s0", 0.5))
    ts(_span_rec("slow", "s0", None, 0.6))
    # errored trace qualifies regardless of duration
    ts(_span_rec("err", "e1", "e0", 0.001, error="ValueError"))
    ts(_span_rec("err", "e0", None, 0.002))
    assert sorted(ts.kept_trace_ids()) == ["err", "slow"]
    assert ts.stats()["open_traces"] == 0


def test_tail_sampler_bounded_under_span_storm():
    """ISSUE satellite: a span storm (every trace slow, none rooted)
    must leave memory flat — open traces, spans per trace and kept
    traces all capped by construction."""
    ts = tracing.TailSampler(threshold_s=0.0, max_open=16,
                             max_spans_per_trace=8, max_kept=4)
    for i in range(400):
        tid = f"t{i}"
        for j in range(32):  # 4x the per-trace span cap
            ts(_span_rec(tid, f"{tid}.{j}", "remote-root", 0.5))
    st = ts.stats()
    assert st["open_traces"] <= 16
    assert st["kept_traces"] <= 4
    assert st["open_spans"] <= 16 * 8
    assert st["kept_spans"] <= 4 * 8
    assert st["evicted_open"] == 400 - st["open_traces"]
    # a second identical storm must not grow the retained footprint
    for i in range(400, 800):
        tid = f"t{i}"
        for j in range(32):
            ts(_span_rec(tid, f"{tid}.{j}", "remote-root", 0.5))
    st2 = ts.stats()
    assert st2["open_spans"] <= st["open_spans"]
    assert st2["kept_spans"] <= st["kept_spans"]


def test_tail_sampler_flush_joins_via_assemble_traces(tmp_path):
    tracing.set_enabled(False)  # tap must work with tracing off
    sampler = tracing.arm_tail_sampler(threshold_s=0.0,
                                       out_dir=str(tmp_path))
    try:
        with tracing.span("router.request"):
            tid = tracing.current_trace_id()
            with attribution.phase("generation", "decode"):
                pass
        assert tid is not None  # the listener tap kept span() live
        out = sampler.flush(force=True)
        assert out and os.path.basename(out).startswith("trace_tail_")
        joined = collector.assemble_traces(str(tmp_path))
        assert tid in joined
        with open(joined[tid]) as f:
            names = {e["name"] for e in json.load(f)["traceEvents"]}
        assert {"router.request", "generation.phase.decode"} <= names
    finally:
        tracing.disarm_tail_sampler()


# ---------------------------------------------------------------------------
# bucket overrides (PADDLE_TPU_HIST_BUCKETS)
# ---------------------------------------------------------------------------


def test_hist_buckets_env_override(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_HIST_BUCKETS",
                       "paddle_tpu_slow_seconds=1,30,120; bad==oops;"
                       "typo_seconds=a,b")
    metrics.reset_env_bucket_overrides()
    try:
        metrics.set_enabled(True)
        reg = metrics.MetricsRegistry()
        h = metrics.histogram("paddle_tpu_slow_seconds", "",
                              buckets=(0.1, 1.0), registry=reg)
        assert h.buckets == (1.0, 30.0, 120.0)
        # families without an override keep their call-site ladder
        h2 = metrics.histogram("paddle_tpu_other_seconds", "",
                               buckets=(0.1, 1.0), registry=reg)
        assert h2.buckets == (0.1, 1.0)
        # malformed entries were dropped, not fatal
        h3 = metrics.histogram("typo_seconds", "", buckets=(5.0,),
                               registry=reg)
        assert h3.buckets == (5.0,)
    finally:
        monkeypatch.delenv("PADDLE_TPU_HIST_BUCKETS")
        metrics.reset_env_bucket_overrides()


# ---------------------------------------------------------------------------
# cli why / trace-of (snapshot mode)
# ---------------------------------------------------------------------------


def _dump_with_phases_and_exemplars(tmp_path):
    metrics.set_enabled(True)
    tracing.set_enabled(True)
    exemplars.set_armed(True)
    attribution.observe_phase("generation", "decode", 0.08)
    attribution.observe_phase("generation", "sample", 0.02)
    h = metrics.histogram("paddle_tpu_generation_request_seconds", "",
                          buckets=(0.1, 1.0))
    with tracing.span("router.request"):
        tid = tracing.current_trace_id()
        h.observe(0.7)
    p = tmp_path / "fleet.prom"
    p.write_text(exporters.prometheus_text())
    return p, tid


def test_cli_why_snapshot(tmp_path, capsys):
    p, _ = _dump_with_phases_and_exemplars(tmp_path)
    assert cli.cmd_why(["--prom", str(p), "--kind", "generation"]) == 0
    out = capsys.readouterr().out
    assert "decode" in out and "80.0%" in out
    with pytest.raises(SystemExit):
        cli.cmd_why([])  # neither --prom nor --registry


def test_cli_trace_of_resolves_exemplar_to_trace(tmp_path, capsys):
    p, tid = _dump_with_phases_and_exemplars(tmp_path)
    # no trace dir: prints the trace id, exits 0
    rc = cli.cmd_trace_of(
        ["--metric", "paddle_tpu_generation_request_seconds",
         "--prom", str(p), "--p99"])
    assert rc == 0
    assert tid in capsys.readouterr().out
    # with the trace dir holding the span dump, the join is written
    trace_dir = tmp_path / "traces"
    trace_dir.mkdir()
    tracing.write_chrome_trace(str(trace_dir / "trace_fleet.json"))
    rc = cli.cmd_trace_of(
        ["--metric", "paddle_tpu_generation_request_seconds",
         "--prom", str(p), "--trace-dir", str(trace_dir)])
    out = capsys.readouterr().out
    assert rc == 0
    assert f"trace_join_{tid}.json" in out
    # a metric with no exemplars is a distinct, actionable failure
    rc = cli.cmd_trace_of(
        ["--metric", "paddle_tpu_generation_phase_seconds",
         "--prom", str(p)])
    assert rc == 1
    assert "no exemplars" in capsys.readouterr().out


# ---------------------------------------------------------------------------
# IterationClock: a loop's iteration split into parts, work and waiting
# ---------------------------------------------------------------------------


def _spin(seconds):
    """Compute until this thread has had `seconds` of a CPU."""
    end = time.thread_time() + seconds
    while time.thread_time() < end:
        pass


def _clock_with_reference(**kw):
    """A clock whose 64 iterations of 1 ms have given it a reference."""
    clock = attribution.IterationClock(("first", "second"), **kw)
    clock.begin()
    for _ in range(64):
        time.sleep(0.001)
        clock.mark("first")
        clock.mark("second")
        clock.end()
    assert 0.001 <= clock.reference < 0.01
    clock.slow = []     # (the 64th is judged already: a loaded host)
    return clock


@pytest.mark.parametrize("body, part, working", [
    (_spin, "first", True), (time.sleep, "second", False),
    (time.sleep, "idle", False)], ids=["computes", "sleeps", "waits"])
def test_iteration_clock_names_the_part_and_splits_work_from_waiting(
        body, part, working):
    """An iteration over `slow_factor` reference periods comes back as
    a record naming its longest part; `cpu_ms` is the thread at work
    in the host parts, `offcpu_ms` the host time it did not run, and a
    part not marked took no time."""
    clock = _clock_with_reference(wait="idle", slow_factor=3.0)
    if part == "first":
        body(0.04)
    clock.mark("first")
    if part == "second":
        body(0.04)
    clock.mark("second")
    if part == "idle":
        body(0.04)
    rec = clock.end(active=3)
    assert rec is not None and clock.slow == [rec]
    assert rec["phase"] == part and rec["phase_ms"] >= 40.0
    assert rec["ms"] > 3 * rec["reference_ms"] and rec["active"] == 3
    # the process's counters are read every 32 ms or more and at a slow
    # iteration's end: they cover it and at most that much before it
    # (and one of the quick iterations, which a loaded host stretches)
    assert rec["ms"] <= rec["counted_ms"] <= rec["ms"] + 1e3 * (
        clock.PROCESS_EVERY_S + 0.1)
    assert (rec["wait_ms"] >= 40.0) == (part == "idle")
    if working:
        # (on a loaded host the thread may also wait for a CPU)
        assert rec["cpu_ms"] >= 35.0 and rec["process_cpu_ms"] >= 35.0
        assert rec["offcpu_ms"] <= rec["phase_ms"] - 35.0
    elif part == "idle":
        # inside the wait: neither host work nor host time off the CPU
        assert rec["cpu_ms"] < 20.0 and rec["offcpu_ms"] < 20.0
        assert rec["wait_cpu_ms"] < 20.0
    else:
        assert rec["cpu_ms"] < 20.0 <= rec["offcpu_ms"]
    # the next iteration marks nothing: its parts took no time and the
    # stale marks do not count
    time.sleep(0.04)
    rec = clock.end()
    assert rec["phase"] == "idle" and rec["wait_ms"] >= 40.0
    assert rec["cpu_ms"] == 0.0 and len(clock.slow) == 2
    for _ in range(10):
        time.sleep(0.01)
        clock.end()
    assert len(clock.slow) == clock.KEEP == 8


def test_iteration_clock_without_the_machines_files(tmp_path):
    """Where the CPU group's `cpu.stat` or `/proc/pressure/cpu` is not
    there the record leaves their keys out; where they are, it reads
    both versions' names."""
    missing = str(tmp_path / "none")
    clock = _clock_with_reference(cpu_stat_path=missing,
                                  pressure_path=missing)
    time.sleep(0.03)
    rec = clock.end()
    assert rec["phase"] == "wait" and rec["offcpu_ms"] == 0.0
    assert not {"throttled_ms", "throttled_count",
                "cpu_pressure_ms"} & set(rec)
    v1, v2, psi = (tmp_path / n for n in ("v1", "v2", "psi"))
    v1.write_text("nr_periods 9\nnr_throttled 4\nthrottled_time 2500000\n")
    v2.write_text("usage_usec 77\nnr_throttled 5\nthrottled_usec 1500\n")
    psi.write_text("some avg10=0.00 avg60=0.00 avg300=0.00 total=8250\n"
                   "full avg10=0.00 avg60=0.00 avg300=0.00 total=0\n")
    for stat, ms, count in ((v1, 2.5, 4), (v2, 1.5, 5)):
        clock = attribution.IterationClock((), cpu_stat_path=str(stat),
                                           pressure_path=str(psi))
        assert clock._machine() == {"throttled_ms": ms,
                                    "throttled_count": count,
                                    "cpu_pressure_ms": 8.25}


def test_iteration_clock_restarts_on_another_thread():
    """The counters belong to a thread: an iteration that ends on
    another thread than the one it began on gives no record, however
    long it was, and the next one on that thread is timed again."""
    import threading

    clock = _clock_with_reference()
    got = []

    def other():
        time.sleep(0.03)
        got.append(clock.end())
        time.sleep(0.03)
        got.append(clock.end())

    t = threading.Thread(target=other)
    t.start()
    t.join(timeout=30)
    assert not t.is_alive()
    assert got[0] is None and got[1]["ms"] >= 30.0
    time.sleep(0.03)
    assert clock.end() is None
