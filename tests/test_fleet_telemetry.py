"""Fleet telemetry plane: histogram quantiles, time-series windows,
central collection + federation, flight recorder, SLO burn-rate
alerting, `cli top`/`cli slo`/`cli metrics --diff`, and the 2-member
fleet acceptance (docs/observability.md "Fleet telemetry")."""
import json
import math
import os
import signal
import subprocess
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import cli
from paddle_tpu.observability import (collector, exporters,
                                      flightrecorder, metrics, slo,
                                      timeseries, tracing)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_observability():
    metrics.set_enabled(False)
    tracing.set_enabled(False)
    tracing.clear()
    flightrecorder.uninstall()
    yield
    metrics.set_enabled(False)
    tracing.set_enabled(False)
    tracing.clear()
    flightrecorder.uninstall()


# ---------------------------------------------------------------------------
# Histogram.quantile / registry.quantile goldens
# ---------------------------------------------------------------------------


def test_quantile_golden_uniform():
    """A uniform distribution over linear buckets has exact
    interpolated quantiles."""
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    h = metrics.histogram("u_seconds",
                          buckets=tuple((i + 1) / 10 for i in range(10)),
                          registry=reg)
    for i in range(1000):  # 100 observations per 0.1-wide bucket
        h.observe((i + 0.5) / 1000.0)
    for q in (0.1, 0.25, 0.5, 0.75, 0.9):
        assert h.quantile(q) == pytest.approx(q, abs=1e-9), q
    assert h.quantile(0.0) == 0.0
    assert h.quantile(1.0) == pytest.approx(1.0)


def test_quantile_golden_skewed_and_edges():
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    h = metrics.histogram("s_seconds", buckets=(1, 2, 4), registry=reg)
    for v in (0.5, 1.5, 3.0, 8.0):
        h.observe(v)
    # rank q*4 crosses: p50 (rank 2) consumes bucket (1,2] -> 2.0;
    # q=.625 (rank 2.5) -> halfway through (2,4] -> 3.0; p75 (rank 3)
    # tops that bucket -> 4.0; the +Inf overflow clamps to 4.0 too
    assert h.quantile(0.5) == pytest.approx(2.0)
    assert h.quantile(0.625) == pytest.approx(3.0)
    assert h.quantile(0.75) == pytest.approx(4.0)
    assert h.quantile(0.99) == pytest.approx(4.0)  # +Inf bucket clamp
    with pytest.raises(ValueError):
        h.quantile(1.5)
    empty = metrics.histogram("e_seconds", buckets=(1,), registry=reg)
    assert math.isnan(empty.quantile(0.9))


def test_registry_quantile_helper():
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    h = metrics.histogram("lat_seconds", "", ("verb",), buckets=(1, 2),
                          registry=reg)
    h.labels(verb="GET").observe(0.5)
    h.labels(verb="GET").observe(1.5)
    assert reg.quantile("lat_seconds", 0.5,
                        {"verb": "GET"}) == pytest.approx(1.0)
    with pytest.raises(KeyError):
        reg.quantile("nope_seconds", 0.5)
    metrics.counter("c_total", registry=reg)
    with pytest.raises(ValueError):
        reg.quantile("c_total", 0.5)
    # a typo'd label VALUE must raise, and must NOT mint an empty
    # child series the next dump would export forever (review pin)
    before = len(h.samples())
    with pytest.raises(KeyError):
        reg.quantile("lat_seconds", 0.5, {"verb": "GET-typo"})
    assert len(h.samples()) == before
    with pytest.raises(ValueError):  # wrong label NAME still explicit
        reg.quantile("lat_seconds", 0.5, {"nope": "x"})


# ---------------------------------------------------------------------------
# TimeSeriesStore windows
# ---------------------------------------------------------------------------


def _clocked_store(reg):
    clk = {"t": 0.0}
    store = timeseries.TimeSeriesStore(registry=reg,
                                       clock=lambda: clk["t"])
    return store, clk


def test_timeseries_counter_rate_and_latest():
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    c = metrics.counter("reqs_total", registry=reg)
    store, clk = _clocked_store(reg)
    store.sample_once()
    clk["t"] = 10.0
    c.inc(40)
    store.sample_once()
    assert store.rate("reqs_total", 100.0) == pytest.approx(4.0)
    assert store.latest("reqs_total") == 40
    assert store.rate("nope_total", 10.0) is None


def test_timeseries_windowed_quantile_isolates_window():
    """Old observations outside the window must not pollute the
    windowed quantile — the exact failure of reading a lifetime
    histogram."""
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    h = metrics.histogram("lat_seconds", buckets=(0.01, 0.1, 1.0, 10.0),
                          registry=reg)
    store, clk = _clocked_store(reg)
    store.sample_once()  # empty baseline BEFORE any observation
    clk["t"] = 1.0
    for _ in range(100):
        h.observe(5.0)  # ancient awfulness
    store.sample_once()
    clk["t"] = 100.0
    store.sample_once()  # baseline at the window edge
    clk["t"] = 110.0
    for _ in range(50):
        h.observe(0.005)  # recent goodness
    store.sample_once()
    # lifetime p50 is terrible, the 20s window is clean
    assert h.quantile(0.5) > 1.0
    assert store.quantile("lat_seconds", 0.5, 20.0) <= 0.01
    # and a window covering everything sees the old samples again
    assert store.quantile("lat_seconds", 0.5, 1000.0) > 1.0


def test_timeseries_label_subset_aggregation_and_drop():
    store = timeseries.TimeSeriesStore(clock=lambda: 1.0)
    for member in ("a", "b"):
        store.ingest_value("up", "gauge",
                           {"member": member, "kind": "pserver"}, 1.0)
        store.ingest_histogram(
            "lat_seconds", {"member": member, "kind": "pserver"},
            buckets=[1.0, 2.0], counts=[3, 1, 0], count=4, total=4.0)
    assert store.latest("up", {"kind": "pserver"}) == 2.0
    # aggregated quantile sums bucket deltas across members
    assert store.quantile("lat_seconds", 0.5, 60.0,
                          {"kind": "pserver"}) == pytest.approx(2 / 3)
    with pytest.raises(ValueError):  # ambiguous single-series query
        store.points("up", {"kind": "pserver"})
    assert store.drop({"member": "a"}) == 2
    assert store.latest("up", {"kind": "pserver"}) == 1.0


def test_timeseries_sampler_thread_and_capacity():
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    g = metrics.gauge("depth", registry=reg)
    store = timeseries.TimeSeriesStore(registry=reg, period_s=0.02,
                                       capacity=4)
    store.start()
    try:
        g.set(7)
        deadline = time.monotonic() + 5
        while store.latest("depth") != 7 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert store.latest("depth") == 7
        time.sleep(0.2)
        assert len(store.points("depth")) <= 4  # ring stays bounded
    finally:
        store.stop()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------


def test_flightrecorder_ring_only_span_capture():
    """Armed recorder captures spans with full tracing OFF; a span kept
    live by a listener is in the one span store too (PR 24); uninstall
    restores the no-op span."""
    assert not tracing.enabled()
    flightrecorder.install()
    with tracing.span("work.unit", k=1) as s:
        assert s is not None  # live span, ring-only
    flightrecorder.note("checkpoint", step=3)
    d = flightrecorder.dump_dict()
    assert [s["name"] for s in d["spans"]] == ["work.unit"]
    assert d["events"][0]["kind"] == "checkpoint"
    assert d["events"][0]["data"] == {"step": 3}
    assert [s["name"] for s in tracing.finished_spans()] == ["work.unit"]
    flightrecorder.uninstall()
    with tracing.span("gone") as s:
        assert s is None
    assert flightrecorder.dump_dict()["spans"] == []  # honest empty


def test_flightrecorder_periodic_flush_and_ring_bound(tmp_path):
    rec = flightrecorder.install(dir=str(tmp_path), flush_s=0.05,
                                 max_events=8)
    for i in range(50):
        flightrecorder.note("tick", i=i)
    path = rec.default_path()
    deadline = time.monotonic() + 5
    while not os.path.exists(path) and time.monotonic() < deadline:
        time.sleep(0.05)
    with open(path) as f:
        dump = json.load(f)
    events = [e for e in dump["events"] if e["kind"] == "tick"]
    assert len(events) <= 8  # ring bound
    assert events[-1]["data"]["i"] == 49  # ... keeping the NEWEST
    assert dump["metric_snapshots"]  # registry snapshots ride along


def test_flightrecorder_fault_injection_dump(tmp_path):
    from paddle_tpu.core.resilience import FaultError, fault_injector

    rec = flightrecorder.install(dir=str(tmp_path), flush_s=30.0)
    inj = fault_injector()
    inj.inject("flight.test.site", "error")
    try:
        with pytest.raises(FaultError):
            inj.fire("flight.test.site")
    finally:
        inj.clear()
    # the dump was written EAGERLY at fire time (flush period is 30s)
    with open(rec.default_path()) as f:
        dump = json.load(f)
    assert dump["reason"] == "fault:flight.test.site"
    assert any(e["kind"] == "fault" and
               e["data"]["site"] == "flight.test.site"
               for e in dump["events"])


def test_flightrecorder_sigterm_chains_previous_handler(tmp_path):
    got = []
    prev = signal.signal(signal.SIGTERM, lambda *a: got.append(a))
    try:
        rec = flightrecorder.install(dir=str(tmp_path), flush_s=30.0)
        os.kill(os.getpid(), signal.SIGTERM)
        deadline = time.monotonic() + 5
        while not got and time.monotonic() < deadline:
            time.sleep(0.01)
        assert got, "previous SIGTERM handler never ran"
        with open(rec.default_path()) as f:
            assert json.load(f)["reason"] == "sigterm"
        flightrecorder.uninstall()
    finally:
        signal.signal(signal.SIGTERM, prev)


def test_pserver_flight_verb_and_wire_span_ordering():
    """The FLIGHT verb returns the server process ring on demand, and
    the deflaked ordering invariant holds: the server-side span is in
    the buffer BEFORE the client sees the reply — pinned over many
    iterations (the old 1-in-4 flake window was between the reply
    sendall and the span record)."""
    from paddle_tpu.parallel.pserver import VariableClient, VariableServer

    flightrecorder.install()
    tracing.set_enabled(True)
    scope = fluid.Scope()
    scope.set_var("w", np.ones(4, np.float32))
    server = VariableServer(None, scope, None, fan_in=1)
    port = server.serve(0)
    client = VariableClient(f"127.0.0.1:{port}")
    try:
        for i in range(30):
            tracing.clear()
            with tracing.span("trainer.step") as step:
                client.get_var("w")
            spans = tracing.finished_spans()
            server_side = [s for s in spans
                           if s["name"] == "pserver.get"]
            assert len(server_side) == 1, \
                f"iteration {i}: server span not recorded before the " \
                f"client returned ({[s['name'] for s in spans]})"
            assert server_side[0]["trace_id"] == step.context.trace_id
        dump = client.get_flight_record()
        assert dump["pid"] == os.getpid()
        assert any(s["name"] == "pserver.get" for s in dump["spans"])
        assert any(s["name"] == "pserver.flight"
                   for s in dump["spans"]) is False  # its own span
        # records only after its reply left — by the same invariant
    finally:
        client.close()
        server.stop()


# ---------------------------------------------------------------------------
# collector: parse, announce/scrape/federate, churn, push, traces
# ---------------------------------------------------------------------------


def test_unescape_label_backslash_before_n_roundtrips():
    """Review regression: chained str.replace corrupted 'C:\\net'
    (the collapsed backslash re-matched '\\n'); the pairwise scanner
    must round-trip any value the exporter can escape."""
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    c = metrics.counter("paths_total", "", ("path",), registry=reg)
    for v in ("C:\\net", "a\\\\nb", "q\"x\\ny", "\\"):
        c.labels(path=v).inc()
    parsed = collector.parse_prometheus_text(
        exporters.prometheus_text(reg))
    got = {s["labels"]["path"] for s in parsed["paths_total"]["samples"]}
    assert got == {"C:\\net", "a\\\\nb", "q\"x\\ny", "\\"}


def test_interval_verdicts_histogram_rate_is_per_second():
    """Review regression: a histogram rate/qps SLO must compare the
    per-SECOND slope, not the raw per-interval count delta (which
    scales with the sample period)."""
    store = timeseries.TimeSeriesStore(clock=lambda: 0.0)
    cum = 0
    for i in range(5):  # 5 obs per 0.5s interval = 10/s
        store.ingest_histogram("h_seconds", {}, buckets=[1.0],
                               counts=[cum, 0], count=cum, total=0.0,
                               ts=i * 0.5)
        cum += 5
    spec = slo.parse_slo("h_seconds qps > 8 over 10s")
    verdicts = store.interval_verdicts(
        "h_seconds", 10.0, check=lambda v: not spec.meets(v),
        now=2.0)
    assert verdicts and not any(verdicts)  # 10/s meets '> 8'
    st, = slo.evaluate([spec], store, now=2.0)
    assert st.ok and not st.alerting


def test_slo_mean_burn_uses_interval_mean_not_rate():
    """Review regression: a 'mean' objective's burn verdicts must use
    the per-interval mean (sum delta / count delta), not the request
    rate — a healthy high-qps fleet must not page."""
    store = timeseries.TimeSeriesStore(clock=lambda: 0.0)
    cum_n, cum_sum = 0, 0.0
    for i in range(8):  # 10 obs of 10 ms latency per 1s interval
        store.ingest_histogram("m_seconds", {}, buckets=[1.0],
                               counts=[cum_n, 0], count=cum_n,
                               total=cum_sum, ts=float(i))
        cum_n += 10
        cum_sum += 10 * 0.01
    spec = slo.parse_slo("m_seconds mean < 0.5 over 10s")
    st, = slo.evaluate([spec], store, now=7.0)
    assert st.ok and not st.alerting, st.to_dict()
    assert st.value == pytest.approx(0.01)


def test_flightrecorder_sigterm_respects_sig_ign():
    """Review regression: arming the recorder must not turn a
    deliberately-ignored SIGTERM fatal."""
    prev = signal.signal(signal.SIGTERM, signal.SIG_IGN)
    try:
        flightrecorder.install()  # memory-only
        os.kill(os.getpid(), signal.SIGTERM)
        time.sleep(0.1)  # still alive = the signal stayed ignored
        assert flightrecorder.dump_dict()["events"][-1]["kind"] == \
            "sigterm"
    finally:
        flightrecorder.uninstall()
        signal.signal(signal.SIGTERM, prev)


def test_router_watch_after_close_raises():
    from paddle_tpu.cloud.router import ReplicaRouter

    router = ReplicaRouter(desired=1)
    router.close()
    with pytest.raises(RuntimeError, match="closed"):
        router.watch()


def test_parse_prometheus_text_roundtrip_with_escaping():
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    c = metrics.counter("weird_total", "strange chars", ("what",),
                        registry=reg)
    c.labels(what='a"b\\c\nd').inc(3)
    h = metrics.histogram("lat_seconds", "latency", buckets=(0.1, 1.0),
                          registry=reg)
    h.observe(0.05)
    h.observe(5.0)
    parsed = collector.parse_prometheus_text(
        exporters.prometheus_text(reg))
    assert parsed["weird_total"]["samples"][0]["labels"] == \
        {"what": 'a"b\\c\nd'}
    assert parsed["weird_total"]["samples"][0]["value"] == 3
    hv = parsed["lat_seconds"]["samples"][0]["value"]
    assert hv["count"] == 2 and hv["sum"] == pytest.approx(5.05)
    assert hv["buckets"] == [[0.1, 1], [1.0, 1], [float("inf"), 2]]


def _member(coll, kind, series_fn, member=""):
    """One in-process fleet member: a private registry exposed via
    announce(); series_fn(reg) populates it."""
    reg = metrics.MetricsRegistry()
    series_fn(reg)
    ann = collector.announce(coll.registry_addr, kind, member=member,
                             metrics_registry=reg)
    return reg, ann


def test_collector_scrape_federation_and_member_labels():
    metrics.set_enabled(True)
    coll = collector.TelemetryCollector(period_s=0.05,
                                        scrape_timeout_s=1.0)
    try:
        def pserver_series(reg):
            metrics.counter("paddle_tpu_pserver_requests_total", "",
                            ("verb",), registry=reg) \
                .labels(verb="SEND").inc(9)

        def replica_series(reg):
            h = metrics.histogram(
                "paddle_tpu_serving_generation_seconds", "",
                registry=reg)
            h.observe(0.2)

        _, ann_p = _member(coll, "pserver", pserver_series)
        _, ann_g = _member(coll, "generation", replica_series)
        res = coll.scrape_once()
        assert res == {ann_p.member: True, ann_g.member: True}
        text = coll.federation_text()
        assert (f'paddle_tpu_pserver_requests_total{{verb="SEND",'
                f'member="{ann_p.member}",kind="pserver"}} 9') in text
        assert f'member="{ann_g.member}"' in text
        assert ('paddle_tpu_member_up{member="%s",kind="generation"} 1'
                % ann_g.member) in text
        # fleet store answers windowed queries per member label
        assert coll.series.latest(
            "paddle_tpu_pserver_requests_total",
            {"member": ann_p.member}) == 9
        ann_p.close()
        ann_g.close()
    finally:
        coll.close()


def test_collector_member_death_mid_scrape_no_wedge_no_leak():
    """Satellite: a member that dies mid-scrape must neither wedge the
    loop nor leak its series — endpoint death (lease still live) is
    reclaimed after fail_limit scrapes, lease expiry immediately."""
    metrics.set_enabled(True)
    coll = collector.TelemetryCollector(period_s=0.05,
                                        scrape_timeout_s=0.3,
                                        fail_limit=2)
    try:
        reg, ann = _member(
            coll, "pserver",
            lambda reg: metrics.gauge("paddle_tpu_pserver_x", "",
                                      registry=reg).set(5))
        coll.scrape_once()
        assert coll.series.latest("paddle_tpu_pserver_x",
                                  {"member": ann.member}) == 5
        ann.http.close()  # endpoint dies; the lease keeps beating
        t0 = time.monotonic()
        coll.scrape_once()
        coll.scrape_once()
        assert time.monotonic() - t0 < 3.0  # bounded by the timeout
        # series reclaimed after fail_limit failures; member marked down
        assert coll.series.points("paddle_tpu_pserver_x",
                                  {"member": ann.member}) == []
        m = next(x for x in coll.members()
                 if x["member"] == ann.member)
        assert not m["up"] and m["fails"] >= 2
        assert coll.series.latest("paddle_tpu_member_up",
                                  {"member": ann.member}) in (0.0, None)
        # lease release -> delisted -> the member row itself goes
        ann.lease.release()
        coll.scrape_once()
        assert all(x["member"] != ann.member for x in coll.members())
    finally:
        coll.close()


def test_collector_stale_inflight_scrape_cannot_resurrect_series():
    """Review regression: scrape_once snapshots its target list, then
    scrapes outside the lock — a concurrent discovery pass that
    delists the member mid-flight drops its series, and the stale
    scrape's ingest (the endpoint may still answer) must not write
    them back: the member is gone from _members, so nothing would
    ever reclaim the resurrected series."""
    metrics.set_enabled(True)
    coll = collector.TelemetryCollector(period_s=0.05,
                                        scrape_timeout_s=1.0,
                                        fail_limit=1)
    try:
        reg, ann = _member(
            coll, "pserver",
            lambda reg: metrics.gauge("paddle_tpu_stale_x", "",
                                      registry=reg).set(7))
        coll.scrape_once()
        assert coll.series.latest("paddle_tpu_stale_x",
                                  {"member": ann.member}) == 7
        stale = coll._members[ann.member]
        with coll._lock:
            coll._drop_member_locked(ann.member)
        # success path: the endpoint still answers the stale scrape
        coll._scrape_member(stale)
        assert coll.series.points("paddle_tpu_stale_x",
                                  {"member": ann.member}) == []
        assert coll.series.points("paddle_tpu_member_up",
                                  {"member": ann.member}) == []
        # failure path: a stale FAILED scrape must not resurrect
        # member_up=0 either
        ann.http.close()
        coll._scrape_member(stale)
        assert coll.series.points("paddle_tpu_member_up",
                                  {"member": ann.member}) == []
        ann.close()
    finally:
        coll.close()


def test_collector_member_restart_same_id_drops_old_incarnation():
    """Review regression: a restarted process can reclaim the lowest
    free lease index (same member id, new /metrics port) — its reset
    counters must not append after the old incarnation's high values,
    which read as NEGATIVE rates fleet-wide."""
    metrics.set_enabled(True)
    coll = collector.TelemetryCollector(period_s=0.05,
                                        scrape_timeout_s=1.0)
    try:
        reg1 = metrics.MetricsRegistry()
        metrics.counter("paddle_tpu_restart_total",
                        registry=reg1).inc(1000)
        ann1 = collector.announce(coll.registry_addr, "pserver",
                                  metrics_registry=reg1)
        coll.scrape_once()
        member = ann1.member
        ann1.close()  # crash+restart: frees index 0 ...
        reg2 = metrics.MetricsRegistry()
        metrics.counter("paddle_tpu_restart_total",
                        registry=reg2).inc(5)  # reset counter
        ann2 = collector.announce(coll.registry_addr, "pserver",
                                  metrics_registry=reg2)
        assert ann2.member == member  # ... which the restart reclaims
        coll.scrape_once()
        time.sleep(0.05)
        coll.scrape_once()
        rate = coll.series.rate("paddle_tpu_restart_total", 60.0,
                                {"member": member})
        assert rate is None or rate >= 0, rate
        assert coll.series.latest("paddle_tpu_restart_total",
                                  {"member": member}) == 5
        ann2.close()
    finally:
        coll.close()


def test_collector_push_path_and_http_federation():
    metrics.set_enabled(True)
    coll = collector.TelemetryCollector(period_s=0.05)
    try:
        port = coll.serve(0)
        reg = metrics.MetricsRegistry()
        metrics.counter("paddle_tpu_oneshot_total",
                        registry=reg).inc(4)
        collector.push_metrics(f"http://127.0.0.1:{port}", "trainer",
                               "trainer-push", registry=reg)
        assert any(m["kind"] == "trainer" for m in coll.members())
        # pushed series survive registry-driven pruning (no lease)
        coll.scrape_once()
        text = coll.federation_text()
        assert ('paddle_tpu_oneshot_total{member="trainer-push",'
                'kind="trainer"} 4') in text
        # the collector's own HTTP endpoint serves the federation
        import urllib.request

        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=5).read()
        assert b"trainer-push" in body
    finally:
        coll.close()


def test_assemble_traces_joins_across_processes(tmp_path):
    """Spans of ONE trace id from a trace export (pid 100) and a
    flight-recorder ring (pid 200) land in one Chrome trace."""
    def ev(tid, sid, parent, pid, name):
        return {"ph": "X", "cat": "span", "name": name, "ts": 1.0,
                "dur": 2.0, "pid": pid, "tid": 1,
                "args": {"trace_id": tid, "span_id": sid,
                         "parent_id": parent}}

    with open(tmp_path / "trace_100.json", "w") as f:
        json.dump({"traceEvents": [
            ev("t1", "a", None, 100, "trainer.step"),
            ev("t2", "z", None, 100, "unrelated")]}, f)
    with open(tmp_path / "flight_200.json", "w") as f:
        json.dump({"spans": [
            {"name": "pserver.send", "trace_id": "t1", "span_id": "b",
             "parent_id": "a", "ts": 1.5, "dur": 0.5, "pid": 200,
             "tid": 2, "thread": "x", "attrs": {"var": "w"}}]}, f)
    out = collector.assemble_traces(str(tmp_path))
    assert set(out) == {"t1", "t2"}
    with open(out["t1"]) as f:
        events = json.load(f)["traceEvents"]
    assert {(e["name"], e["pid"]) for e in events} == \
        {("trainer.step", 100), ("pserver.send", 200)}
    assert all(e["args"]["trace_id"] == "t1" for e in events)


# ---------------------------------------------------------------------------
# SLO layer
# ---------------------------------------------------------------------------


def test_slo_grammar_and_aliases():
    s = slo.parse_slo("serving p99 < 500ms over 120s")
    assert s.metric == "paddle_tpu_serving_generation_seconds"
    assert s.stat == "p99" and s.op == "<"
    assert s.threshold == pytest.approx(0.5)
    assert s.window_s == 120.0
    s2 = slo.parse_slo("pserver.barrier_wait p99 < 1s")
    assert s2.metric == "paddle_tpu_pserver_barrier_wait_seconds"
    assert s2.window_s == 60.0
    s3 = slo.parse_slo("my_total qps > 2")
    assert s3.stat == "rate"
    for bad in ("nonsense", "m p99 ~ 3", "m z50 < 1"):
        with pytest.raises(ValueError):
            slo.parse_slo(bad)
    specs = slo.load_slos(os.path.join(REPO, "tools", "slo.json"))
    assert len(specs) >= 4
    assert any(s.metric == "paddle_tpu_serving_generation_seconds"
               for s in specs)


def test_slo_burn_rate_alerts_on_regression_not_on_noise():
    """A sustained p99 regression trips the multiwindow burn alert; a
    single bad interval inside a healthy run stays within budget."""
    store = timeseries.TimeSeriesStore(clock=lambda: 0.0)
    spec = slo.parse_slo("lat_seconds p99 < 0.1 over 10s",
                         budget=0.3)

    def ingest(ts, counts, count):
        store.ingest_histogram("lat_seconds", {}, buckets=[0.05, 1.0],
                               counts=counts, count=count,
                               total=0.0, ts=ts)

    # healthy: 10 samples of fast traffic, ONE bad interval
    cum_fast, cum_slow = 0, 0
    for i in range(11):
        if i == 5:
            cum_slow += 10  # one burst of slowness
        else:
            cum_fast += 10
        ingest(float(i), [cum_fast, cum_slow, 0],
               cum_fast + cum_slow)
    st, = slo.evaluate([spec], store, now=10.0)
    assert not st.alerting  # 1/10 bad < 0.3 budget
    # regression: every interval from t=11 on is slow
    for i in range(11, 22):
        cum_slow += 10
        ingest(float(i), [cum_fast, cum_slow, 0],
               cum_fast + cum_slow)
    st, = slo.evaluate([spec], store, now=21.0)
    assert st.alerting and not st.ok
    assert st.burn_fast >= 1.0 and st.burn_slow >= 1.0
    assert st.value > 0.1  # the windowed p99 itself is bad


def test_slo_no_data_is_not_a_violation():
    store = timeseries.TimeSeriesStore(clock=lambda: 0.0)
    st, = slo.evaluate([slo.parse_slo("ghost_seconds p99 < 1")], store)
    assert st.no_data and st.ok and not st.alerting
    assert not slo.failed([st])


def test_slo_snapshot_mode_gates_a_dump():
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    h = metrics.histogram("lat_seconds", buckets=(0.1, 1.0),
                          registry=reg)
    for _ in range(99):
        h.observe(0.05)
    h.observe(5.0)
    families = collector.parse_prometheus_text(
        exporters.prometheus_text(reg))
    ok_spec = slo.parse_slo("lat_seconds p50 < 0.1")
    bad_spec = slo.parse_slo("lat_seconds p99 < 0.001")
    statuses = slo.evaluate_snapshot([ok_spec, bad_spec], families)
    assert statuses[0].ok and not statuses[0].alerting
    assert not statuses[1].ok and statuses[1].alerting
    assert slo.failed(statuses)


# ---------------------------------------------------------------------------
# CLI surfaces
# ---------------------------------------------------------------------------


def test_cli_metrics_diff(tmp_path, capsys):
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    c = metrics.counter("steps_total", "", registry=reg)
    g = metrics.gauge("depth", registry=reg)
    h = metrics.histogram("lat_seconds", buckets=(1,), registry=reg)
    c.inc(5)
    g.set(2)
    a = exporters.write_json(str(tmp_path / "a.json"), reg)
    c.inc(7)
    g.set(9)
    h.observe(0.5)
    b = exporters.write_json(str(tmp_path / "b.json"), reg)
    assert cli.cmd_metrics(["--diff", a, b]) == 0
    out = capsys.readouterr().out
    assert "steps_total" in out and "+7" in out
    assert "2 -> 9" in out            # gauge before -> after
    assert "lat_seconds_count" in out  # histogram count delta rides
    assert "/s)" in out                # per-second rate printed


def test_cli_top_renders_fleet_table(capsys):
    metrics.set_enabled(True)
    coll = collector.TelemetryCollector(period_s=0.05)
    try:
        def series(reg):
            metrics.counter(
                "paddle_tpu_serving_generation_requests_total", "",
                registry=reg).inc(3)
            metrics.histogram(
                "paddle_tpu_serving_generation_seconds", "",
                registry=reg).observe(0.25)
            metrics.gauge(
                "paddle_tpu_serving_generation_queue_depth", "",
                registry=reg).set(2)
            metrics.gauge(
                "paddle_tpu_serving_kv_pool_utilization", "",
                registry=reg).set(0.5)

        _, ann = _member(coll, "generation", series, member="rep-a")
        rc = cli.cmd_top(["--registry", coll.registry_addr,
                          "--period", "0.05", "--samples", "2"])
        assert rc == 0
        out = capsys.readouterr().out
        assert "MEMBER" in out and "rep-a" in out
        assert "generation" in out and "up" in out
        assert "0.50" in out  # KV utilization column
        ann.close()
    finally:
        coll.close()


def test_cli_slo_live_mode_trips_on_injected_regression(capsys):
    """Acceptance bit: an injected p99 regression in a live fleet
    trips the burn-rate alert and `cli slo --check` exits nonzero."""
    metrics.set_enabled(True)
    coll = collector.TelemetryCollector(period_s=0.05)
    spec_path = None
    try:
        reg = metrics.MetricsRegistry()
        h = metrics.histogram(
            "paddle_tpu_serving_generation_seconds", "", registry=reg)
        ann = collector.announce(coll.registry_addr, "generation",
                                 metrics_registry=reg)
        import tempfile

        fd, spec_path = tempfile.mkstemp(suffix=".json")
        with os.fdopen(fd, "w") as f:
            json.dump({"slos": [
                "serving p99 < 0.1s over 30s"]}, f)

        def traffic(stop, value):
            while not stop.is_set():
                h.observe(value)
                time.sleep(0.005)

        stop = threading.Event()
        t = threading.Thread(target=traffic, args=(stop, 0.01),
                             daemon=True)
        t.start()
        try:
            rc_ok = cli.cmd_slo(["--check", "--spec", spec_path,
                                 "--registry", coll.registry_addr,
                                 "--period", "0.05", "--samples", "6"])
        finally:
            stop.set()
            t.join()
        assert rc_ok == 0, capsys.readouterr().out
        # now the regression: every request takes 0.5s
        stop = threading.Event()
        t = threading.Thread(target=traffic, args=(stop, 0.5),
                             daemon=True)
        t.start()
        try:
            rc_bad = cli.cmd_slo(["--check", "--spec", spec_path,
                                  "--registry", coll.registry_addr,
                                  "--period", "0.05", "--samples",
                                  "6"])
        finally:
            stop.set()
            t.join()
        assert rc_bad == 1
        out = capsys.readouterr().out
        assert "ALERT" in out and "FAILED" in out
        ann.close()
    finally:
        if spec_path:
            os.unlink(spec_path)
        coll.close()


# ---------------------------------------------------------------------------
# router signals (the ROADMAP-4 autoscaler substrate)
# ---------------------------------------------------------------------------


def test_router_signals_windowed_p99_and_qps():
    from paddle_tpu.cloud.router import ReplicaRouter

    router = ReplicaRouter(desired=2, refresh_s=0.05)
    try:
        store = router.watch(period_s=0.05)
        assert router.watch() is store  # idempotent
        store.sample_once()  # baseline before traffic
        # synthesize completed requests (the real path observes these
        # in _run_request; always=True so no metrics switch needed)
        for v in (0.1, 0.2, 0.2, 0.4):
            router._m_latency.observe(v)
            router._m_ok.inc()
        router._m_outstanding.set(17)
        store.sample_once()
        sig = router.signals(window_s=60.0)
        assert sig["replicas_live"] == 0
        assert sig["outstanding_tokens"] == 17
        assert 0.1 <= sig["p50"] <= 0.4
        assert sig["p99"] >= sig["p50"]
        assert sig["qps"] is not None and sig["qps"] > 0
    finally:
        router.close()
    # close() reclaimed the instance series
    fam = metrics.registry().get(
        "paddle_tpu_serving_router_request_seconds")
    assert not any(lbl.get("router") == router._rid
                   for lbl, _ in fam.samples())


# ---------------------------------------------------------------------------
# concurrency-analyzer satellite: the new modules stay clean
# ---------------------------------------------------------------------------


def test_new_modules_concurrency_clean():
    from paddle_tpu.analysis import concurrency as conc

    paths = [os.path.join(REPO, "paddle_tpu", "observability", f)
             for f in ("timeseries.py", "collector.py",
                       "flightrecorder.py", "slo.py")]
    findings = conc.analyze_paths(paths)
    errors = [f for f in findings if f.severity == "error"]
    assert not errors, "\n".join(str(f) for f in errors)


# ---------------------------------------------------------------------------
# acceptance: 2-member fleet, SIGKILLed pserver, joined trace
# ---------------------------------------------------------------------------

_PSERVER_CHILD = r"""
import os, sys, time
sys.path.insert(0, {repo!r})
import numpy as np
import paddle_tpu as fluid
from paddle_tpu.parallel.pserver import VariableServer

prog = fluid.Program()
with fluid.program_guard(prog, fluid.Program()):
    blk = prog.global_block()
    p = blk.create_var(name="w", shape=[4], dtype="float32",
                       persistable=True)
    g = blk.create_var(name="w@GRAD", shape=[4], dtype="float32",
                       persistable=True)
    lr = blk.create_var(name="pserver_lr", shape=[1], dtype="float32",
                        persistable=True)
    blk.append_op("sgd", {{"Param": [p.name], "Grad": [g.name],
                           "LearningRate": [lr.name]}},
                  {{"ParamOut": [p.name]}}, {{}})
scope = fluid.Scope()
scope.set_var("w", np.ones(4, np.float32))
scope.set_var("pserver_lr", np.array([0.1], np.float32))
exe = fluid.Executor(fluid.CPUPlace())
server = VariableServer(prog, scope, exe, fan_in=1)
port = server.serve(0)
print("READY", port, flush=True)
time.sleep(600)
"""


@pytest.mark.slow
@pytest.mark.chaos
def test_fleet_acceptance_sigkill_pserver_and_joined_trace(tmp_path):
    """ISSUE acceptance: a 2-member fleet (pserver subprocess +
    in-process serving member) scraped by a TelemetryCollector yields
    (a) one federated dump with member-labeled series from both,
    (b) a merged Chrome trace joining trainer-side and pserver-side
    spans of one trace id — the pserver side recovered from its
    flight ring after SIGKILL, and (c) the SIGKILLed pserver's flight
    dump itself, holding its final spans."""
    from paddle_tpu.parallel.pserver import VariableClient

    flight_dir = tmp_path / "flight"
    trace_dir = tmp_path / "traces"
    coll = collector.TelemetryCollector(period_s=0.1,
                                        scrape_timeout_s=2.0)
    metrics.set_enabled(True)
    tracing.set_enabled(True)
    script = tmp_path / "pserver_child.py"
    script.write_text(_PSERVER_CHILD.format(repo=REPO))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_METRICS="on",
               PADDLE_TPU_TELEMETRY_REGISTRY=coll.registry_addr,
               PADDLE_TPU_FLIGHT_DIR=str(flight_dir))
    proc = subprocess.Popen([sys.executable, str(script)],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.PIPE, text=True, env=env)
    try:
        line = ""
        deadline = time.monotonic() + 180
        while time.monotonic() < deadline:
            line = proc.stdout.readline()
            if line.startswith("READY"):
                break
            assert proc.poll() is None, proc.stderr.read()
        assert line.startswith("READY"), "pserver never came up"
        port = int(line.split()[1])

        # the serving member: this process, announced under kind
        # "generation" with the real serving series (the family is
        # per-{server}-labeled, as GenerationServer registers it)
        metrics.histogram("paddle_tpu_serving_generation_seconds",
                          "request latency: submit -> last token",
                          ("server",)).labels(server="acc") \
            .observe(0.03)
        ann = collector.announce(coll.registry_addr, "generation")

        # trainer-side rounds against the pserver subprocess, traced
        client = VariableClient(f"127.0.0.1:{port}",
                                client_id="acceptance")
        step_ctx = None
        for i in range(5):
            with tracing.span("trainer.step", batch_id=i) as s:
                client.send_var("w@GRAD",
                                np.full(4, 0.5, np.float32))
                client.send_batch_barrier()
                client.get_var("w")
                step_ctx = s.context
            coll.scrape_once()
            time.sleep(0.1)
        client.close()

        # (a) federated dump, member-labeled series from both kinds
        members = coll.members()
        kinds = {m["kind"] for m in members}
        assert {"pserver", "generation"} <= kinds, members
        text = coll.federation_text()
        pmember = next(m["member"] for m in members
                       if m["kind"] == "pserver")
        assert f'member="{pmember}"' in text
        assert 'paddle_tpu_pserver_requests_total' in text
        assert f'member="{ann.member}"' in text
        assert 'paddle_tpu_serving_generation_seconds' in text

        # (c) SIGKILL the pserver after its flush period elapses
        time.sleep(1.2)
        flight_path = flight_dir / f"flight_{proc.pid}.json"
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=30)
        assert flight_path.exists(), "no flight dump after SIGKILL"
        with open(flight_path) as f:
            dump = json.load(f)
        names = {s["name"] for s in dump["spans"]}
        assert any(n.startswith("pserver.") for n in names), names
        assert any(e["kind"] == "pserver.optimize"
                   for e in dump["events"])

        # (b) join: my trace export + the dead pserver's flight ring
        os.makedirs(trace_dir, exist_ok=True)
        tracing.write_chrome_trace(
            str(trace_dir / f"trace_{os.getpid()}.json"))
        import shutil

        shutil.copy(flight_path, trace_dir / flight_path.name)
        joined = collector.assemble_traces(str(trace_dir))
        assert step_ctx.trace_id in joined
        with open(joined[step_ctx.trace_id]) as f:
            events = json.load(f)["traceEvents"]
        pids = {e["pid"] for e in events}
        assert os.getpid() in pids and proc.pid in pids, \
            "trace not joined across processes"
        names = {e["name"] for e in events}
        assert "trainer.step" in names
        assert any(n.startswith("pserver.")
                   and not n.startswith("pserver.client")
                   for n in names)
        ann.close()
    finally:
        if proc.poll() is None:
            proc.kill()
        coll.close()
