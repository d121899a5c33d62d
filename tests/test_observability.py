"""Unified observability layer: registry semantics, span nesting +
thread/wire propagation, exporter formats, end-to-end 2-trainer x
1-pserver trace, and the metrics-off overhead guard
(docs/observability.md)."""
import json
import logging
import os
import threading
import time
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu.observability import exporters, metrics, tracing


@pytest.fixture(autouse=True)
def _fresh_observability():
    """Default off + empty span buffer per test; global metric series
    persist (process registry), so tests assert deltas or use private
    registries."""
    metrics.set_enabled(False)
    tracing.set_enabled(False)
    tracing.clear()
    yield
    metrics.set_enabled(False)
    tracing.set_enabled(False)
    tracing.clear()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------


def test_counter_labels_independent_series():
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    c = metrics.counter("req_total", "requests", ("verb",), registry=reg)
    c.labels(verb="GET").inc()
    c.labels(verb="GET").inc(2)
    c.labels(verb="SEND").inc()
    assert c.labels(verb="GET").value == 3
    assert c.labels(verb="SEND").value == 1
    # same child object on every .labels() call — hot paths can cache it
    assert c.labels(verb="GET") is c.labels(verb="GET")
    with pytest.raises(ValueError):
        c.labels(nope="x")
    with pytest.raises(ValueError):
        c.labels(verb="GET").inc(-1)  # counters only go up


def test_get_or_create_and_conflicts():
    reg = metrics.MetricsRegistry()
    a = metrics.counter("x_total", "x", registry=reg)
    b = metrics.counter("x_total", "x", registry=reg)
    assert a is b
    with pytest.raises(ValueError):  # kind conflict
        metrics.gauge("x_total", registry=reg)
    with pytest.raises(ValueError):  # label conflict
        metrics.counter("x_total", labelnames=("a",), registry=reg)


def test_histogram_buckets_sum_count():
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    h = metrics.histogram("lat_seconds", "latency",
                          buckets=(0.001, 0.01, 0.1), registry=reg)
    for v in (0.0005, 0.005, 0.05, 0.5):
        h.observe(v)
    assert h.count == 4
    assert abs(h.sum - 0.5555) < 1e-9
    # cumulative counts per le, +Inf last
    cum = h._default_child().cumulative_buckets()
    assert cum == [(0.001, 1), (0.01, 2), (0.1, 3), (float("inf"), 4)]
    # boundary lands in its bucket (le semantics)
    h.observe(0.01)
    assert h._default_child().cumulative_buckets()[1] == (0.01, 3)


def test_default_buckets_are_exponential():
    b = metrics.DEFAULT_LATENCY_BUCKETS
    assert len(b) >= 10
    ratios = {round(b[i + 1] / b[i], 6) for i in range(len(b) - 1)}
    assert ratios == {2.0}


def test_off_switch_is_noop_but_always_counts():
    reg = metrics.MetricsRegistry()
    gated = metrics.counter("gated_total", registry=reg)
    always = metrics.counter("always_total", registry=reg, always=True)
    h = metrics.histogram("gated_seconds", registry=reg)
    g = metrics.gauge("gated_depth", registry=reg)
    assert not metrics.enabled()
    gated.inc()
    always.inc()
    h.observe(1.0)
    g.set(5)
    assert gated.value == 0
    assert always.value == 1
    assert h.count == 0
    assert g.value == 0
    metrics.set_enabled(True)
    gated.inc()
    assert gated.value == 1


def test_remove_reclaims_series_but_held_child_keeps_counting():
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    c = metrics.counter("churn_total", "", ("inst",), registry=reg)
    child = c.labels(inst="0")
    child.inc()
    assert any(s["labels"] == {"inst": "0"}
               for s in c.snapshot()["samples"])
    c.remove(inst="0")
    assert c.snapshot()["samples"] == []  # gone from exports
    child.inc()  # the held child still works (stats()-style views)
    assert child.value == 2
    c.remove(inst="0")  # absent: no-op
    with pytest.raises(ValueError):
        c.remove(wrong="0")


def test_executor_close_reclaims_registry_series():
    exe = fluid.Executor(fluid.CPUPlace())
    fam = metrics.registry().get("paddle_tpu_executor_cache_lookups_total")
    eid = exe._exe_id
    assert any(lbl == {"exe": eid, "result": "hit"}
               for lbl, _ in fam.samples())
    stats = exe.cache_stats()
    exe.close()
    assert not any(lbl.get("exe") == eid for lbl, _ in fam.samples())
    assert exe.cache_stats() == stats  # the view survives close


def test_gauge_set_inc_dec():
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    g = metrics.gauge("depth", registry=reg)
    g.set(4)
    g.inc()
    g.dec(2)
    assert g.value == 3


# ---------------------------------------------------------------------------
# spans: nesting, thread handoff, wire inject/extract
# ---------------------------------------------------------------------------


def test_span_disabled_is_noop():
    assert not tracing.enabled()
    with tracing.span("x") as s:
        assert s is None
    assert tracing.finished_spans() == []
    assert tracing.current_context() is None


def test_span_nesting_and_ids():
    tracing.set_enabled(True)
    with tracing.span("outer") as outer:
        with tracing.span("inner", op="mul") as inner:
            pass
        with tracing.span("inner2") as inner2:
            pass
    spans = {s["name"]: s for s in tracing.finished_spans()}
    assert set(spans) == {"outer", "inner", "inner2"}
    o, i1, i2 = spans["outer"], spans["inner"], spans["inner2"]
    # one trace; children point at the outer span; ids are well-formed
    assert i1["trace_id"] == i2["trace_id"] == o["trace_id"]
    assert len(o["trace_id"]) == 32 and len(o["span_id"]) == 16
    assert i1["parent_id"] == o["span_id"]
    assert i2["parent_id"] == o["span_id"]
    assert o["parent_id"] is None
    assert i1["attrs"] == {"op": "mul"}
    assert i1["span_id"] != i2["span_id"]
    # siblings opened after exit start fresh traces
    with tracing.span("later") as later:
        assert later.context.trace_id != o["trace_id"]


def test_span_thread_handoff():
    tracing.set_enabled(True)
    recorded = {}

    def worker(ctx):
        with tracing.activate(ctx):
            with tracing.span("worker.item") as s:
                recorded["ctx"] = s.context

    with tracing.span("producer") as prod:
        ctx = tracing.current_context()
        t = threading.Thread(target=worker, args=(ctx,))
        t.start()
        t.join()
    spans = {s["name"]: s for s in tracing.finished_spans()}
    assert spans["worker.item"]["trace_id"] == \
        spans["producer"]["trace_id"]
    assert spans["worker.item"]["parent_id"] == prod.context.span_id
    # the worker's own thread recorded it
    assert spans["worker.item"]["tid"] != spans["producer"]["tid"]


def test_record_span_detached_from_stack():
    tracing.set_enabled(True)
    with tracing.span("holder") as h:
        parent = tracing.current_context()
        ctx = tracing.record_span("window", time.time(), 0.25,
                                  parent=parent, task_id=7)
        # the stack is untouched: recording did not push/pop anything
        assert tracing.current_context() == h.context
    spans = {s["name"]: s for s in tracing.finished_spans()}
    w = spans["window"]
    assert w["span_id"] == ctx.span_id
    assert w["trace_id"] == h.context.trace_id
    assert w["parent_id"] == h.context.span_id
    assert w["dur"] == 0.25 and w["attrs"]["task_id"] == 7
    assert tracing.record_span("x", 0.0, 0.0) is not None  # own trace
    tracing.set_enabled(False)
    assert tracing.record_span("x", 0.0, 0.0) is None


def test_record_event_sync_raise_keeps_span_stack_balanced():
    """A raising device fence inside record_event must still pop the
    span — a leaked context would mis-parent every later span on the
    thread."""
    from paddle_tpu import profiler

    tracing.set_enabled(True)

    def bad_sync():
        raise RuntimeError("fence failed")

    with pytest.raises(RuntimeError, match="fence failed"):
        with profiler.record_event("op", sync=bad_sync):
            pass
    assert tracing.current_context() is None  # stack balanced
    with tracing.span("after") as s:
        assert s.parent_id is None  # not adopted by the dead span


def test_inject_extract_roundtrip():
    tracing.set_enabled(True)
    assert tracing.inject() is None  # no active span -> omit the field
    with tracing.span("client") as c:
        header = tracing.inject()
        assert header == {"tid": c.context.trace_id,
                          "sid": c.context.span_id}
    # tolerant extract: old peers / malformed headers
    assert tracing.extract(None) is None
    assert tracing.extract({}) is None
    assert tracing.extract({"tid": 7, "sid": "x"}) is None
    ctx = tracing.extract(header)
    assert ctx == tracing.SpanContext(c.context.trace_id,
                                      c.context.span_id)


def test_prefetch_pipeline_handoff_and_metrics():
    """The prefetch worker records under the span that opened the
    reader, and the queue-depth/wait series move."""
    from paddle_tpu.reader.pipeline import prefetch_feeder

    tracing.set_enabled(True)
    metrics.set_enabled(True)

    def reader():
        for i in range(3):
            yield {"x": np.full((2, 2), i, np.float32)}

    wait_h = metrics.registry().get("paddle_tpu_pipeline_wait_seconds")
    depth_fam = metrics.registry().get("paddle_tpu_pipeline_queue_depth")
    before = wait_h._default_child().count
    depth_series_before = len(depth_fam.samples())
    with tracing.span("epoch") as ep:
        feeds = prefetch_feeder(reader, feeder=None, device_put=False)()
        batches = list(feeds)
    assert len(batches) == 3
    spans = [s for s in tracing.finished_spans()
             if s["name"] == "pipeline.prepare"]
    assert len(spans) == 3
    assert all(s["trace_id"] == ep.context.trace_id for s in spans)
    # 3 batches + the end sentinel = 4 queue waits
    assert wait_h._default_child().count == before + 4
    # closing the stream reclaims its per-instance depth series
    feeds.close()
    assert len(depth_fam.samples()) <= depth_series_before


# ---------------------------------------------------------------------------
# exporters: Prometheus text, JSON snapshot/table, HTTP, Chrome trace
# ---------------------------------------------------------------------------


def test_prometheus_text_golden():
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    c = metrics.counter("steps_total", "steps done", ("job",),
                        registry=reg)
    c.labels(job="trainer").inc(3)
    g = metrics.gauge("queue_depth", "", registry=reg)
    g.set(2)
    h = metrics.histogram("step_seconds", "step latency",
                          buckets=(0.1, 1.0), registry=reg)
    h.observe(0.05)
    h.observe(5.0)
    assert exporters.prometheus_text(reg) == (
        "# TYPE queue_depth gauge\n"
        "queue_depth 2\n"
        "# HELP step_seconds step latency\n"
        "# TYPE step_seconds histogram\n"
        'step_seconds_bucket{le="0.1"} 1\n'
        'step_seconds_bucket{le="1"} 1\n'
        'step_seconds_bucket{le="+Inf"} 2\n'
        "step_seconds_sum 5.05\n"
        "step_seconds_count 2\n"
        "# HELP steps_total steps done\n"
        "# TYPE steps_total counter\n"
        'steps_total{job="trainer"} 3\n')


def test_label_value_escaping():
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    c = metrics.counter("weird_total", "", ("what",), registry=reg)
    c.labels(what='a"b\\c\nd').inc()
    text = exporters.prometheus_text(reg)
    assert r'weird_total{what="a\"b\\c\nd"} 1' in text


def test_json_snapshot_and_table(tmp_path):
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    metrics.counter("a_total", "", registry=reg).inc(2)
    metrics.histogram("b_seconds", "", buckets=(1,),
                      registry=reg).observe(0.5)
    path = exporters.write_json(str(tmp_path / "m.json"), reg)
    with open(path) as f:
        snap = json.load(f)
    assert snap["metrics"]["a_total"]["samples"][0]["value"] == 2
    table = exporters.format_metrics_table(snap)
    assert "a_total" in table and "count=1" in table


def test_cli_metrics_renders_snapshot(tmp_path, capsys):
    from paddle_tpu import cli

    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    metrics.counter("cli_total", "", registry=reg).inc(7)
    path = exporters.write_json(str(tmp_path / "snap.json"), reg)
    assert cli.cmd_metrics([path]) == 0
    out = capsys.readouterr().out
    assert "cli_total" in out and "7" in out


def test_cli_trace_runs_config_and_writes_chrome_trace(tmp_path, capsys):
    from paddle_tpu import cli

    cfg = tmp_path / "config.py"
    cfg.write_text(
        "import numpy as np\n"
        "import paddle_tpu as fluid\n\n"
        "def build():\n"
        "    x = fluid.layers.data(name='x', shape=[4],"
        " dtype='float32')\n"
        "    y = fluid.layers.data(name='y', shape=[1],"
        " dtype='float32')\n"
        "    pred = fluid.layers.fc(input=x, size=1)\n"
        "    loss = fluid.layers.mean(\n"
        "        fluid.layers.square_error_cost(pred, y))\n"
        "    def reader():\n"
        "        r = np.random.RandomState(0)\n"
        "        for _ in range(4):\n"
        "            yield {'x': r.rand(2, 4).astype('float32'),\n"
        "                   'y': r.rand(2, 1).astype('float32')}\n"
        "    return {'loss': loss, 'reader': reader}\n")
    out = tmp_path / "trace.json"
    mout = tmp_path / "metrics.json"
    assert cli.cmd_trace([str(cfg), str(out), "--steps", "2",
                          "--use_tpu", "0",
                          "--metrics_out", str(mout)]) == 0
    with open(out) as f:
        payload = json.load(f)
    names = {e["name"] for e in payload["traceEvents"]
             if e["ph"] == "X"}
    assert "trainer.step" in names and "executor.run" in names
    with open(mout) as f:
        snap = json.load(f)
    assert "paddle_tpu_executor_cache_lookups_total" in snap["metrics"]
    assert "2 step(s)" in capsys.readouterr().out


def test_http_endpoint_serves_prometheus_text():
    reg = metrics.MetricsRegistry()
    metrics.set_enabled(True)
    metrics.counter("http_total", "", registry=reg).inc()
    srv = exporters.start_http_server(registry=reg)
    try:
        body = urllib.request.urlopen(srv.url(), timeout=5).read()
        assert b"http_total 1" in body
    finally:
        srv.close()


def test_chrome_trace_output(tmp_path):
    tracing.set_enabled(True)
    with tracing.span("parent"):
        with tracing.span("child", k="v"):
            time.sleep(0.001)
    path = tracing.write_chrome_trace(str(tmp_path / "t.json"))
    with open(path) as f:
        payload = json.load(f)
    events = [e for e in payload["traceEvents"] if e["ph"] == "X"]
    by_name = {e["name"]: e for e in events}
    assert set(by_name) >= {"parent", "child"}
    child = by_name["child"]
    assert child["dur"] >= 1000  # microseconds
    assert child["args"]["trace_id"] == \
        by_name["parent"]["args"]["trace_id"]
    assert child["args"]["parent_id"] == \
        by_name["parent"]["args"]["span_id"]
    assert child["args"]["k"] == "v"


def test_chrome_trace_includes_profiler_events(tmp_path):
    from paddle_tpu import profiler

    tracing.set_enabled(True)
    with profiler.profiler("CPU", print_table=False):
        with profiler.record_event("my_op"):
            pass
        path = tracing.write_chrome_trace(str(tmp_path / "t.json"))
    with open(path) as f:
        payload = json.load(f)
    names = {e["name"] for e in payload["traceEvents"]
             if e["ph"] == "X" and e.get("cat") == "profiler"}
    assert "my_op" in names
    # and record_event doubled as a span (real wall placement)
    assert any(e["ph"] == "X" and e.get("cat") == "span"
               and e["name"] == "my_op"
               for e in payload["traceEvents"])


def test_trace_dir_env_exit_dump(tmp_path):
    d = str(tmp_path / "traces")
    old = tracing.trace_dir()
    tracing.set_trace_dir(d)
    try:
        with tracing.span("x"):
            pass
        path = tracing.write_chrome_trace()  # default path from dir
        assert path == os.path.join(d, f"trace_{os.getpid()}.json")
        assert os.path.exists(path)
    finally:
        tracing._TRACE_DIR = old


# ---------------------------------------------------------------------------
# satellites: profiler sort, resilience logging, serving stats
# ---------------------------------------------------------------------------


def test_profiler_summary_default_sorts_by_total():
    from paddle_tpu import profiler

    profiler.enable_profiler("CPU")
    profiler.reset_profiler()
    try:
        with profiler.record_event("small"):
            pass
        t0 = time.perf_counter()
        with profiler.record_event("big"):
            while time.perf_counter() - t0 < 0.005:
                pass
    finally:
        profiler.disable_profiler(print_table=False)
    rows = profiler.profiler_summary()  # no sorted_key: total desc
    assert rows[0]["name"] == "big"
    totals = [r["total"] for r in rows]
    assert totals == sorted(totals, reverse=True)
    # "insertion" keeps recording order
    rows_ins = profiler.profiler_summary("insertion")
    assert [r["name"] for r in rows_ins] == ["small", "big"]


def test_retry_and_fault_injection_log_warnings(caplog):
    from paddle_tpu.core.resilience import (
        FaultError,
        RetryError,
        RetryPolicy,
        fault_injector,
    )

    policy = RetryPolicy(max_attempts=2, base_delay=0.0, jitter=0.0,
                         deadline=None, sleep=lambda s: None)
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.resilience"):
        with pytest.raises(RetryError):
            policy.call(lambda: (_ for _ in ()).throw(OSError("boom")),
                        what="test op failed")
    msgs = [r.message for r in caplog.records]
    assert any("retrying" in m and "test op failed" in m for m in msgs)
    assert any("retry exhausted" in m for m in msgs)

    caplog.clear()
    inj = fault_injector()
    inj.inject("obs.test.site", "error")
    with caplog.at_level(logging.WARNING, logger="paddle_tpu.resilience"):
        with pytest.raises(FaultError):
            inj.fire("obs.test.site")
    assert any("fault injected at obs.test.site" in r.message
               for r in caplog.records)


def test_retry_and_fault_metrics_counted():
    from paddle_tpu.core.resilience import RetryPolicy, fault_injector

    metrics.set_enabled(True)
    reg = metrics.registry()
    attempts = reg.get("paddle_tpu_resilience_retry_attempts_total")
    faults = reg.get("paddle_tpu_resilience_faults_fired_total")
    a0 = attempts._default_child().value
    policy = RetryPolicy(max_attempts=3, base_delay=0.0, jitter=0.0,
                         deadline=None, sleep=lambda s: None)
    calls = {"n": 0}

    def flaky():
        calls["n"] += 1
        if calls["n"] < 3:
            raise OSError("flaky")
        return "ok"

    assert policy.call(flaky, what="flaky op") == "ok"
    assert attempts._default_child().value == a0 + 2

    inj = fault_injector()
    inj.inject("obs.metric.site", "delay", delay_s=0.0)
    inj.fire("obs.metric.site")
    assert faults.labels(site="obs.metric.site", kind="delay").value >= 1


def test_serving_stats_shed_deadline_queue_depth():
    """InferenceServer.stats() reports what submit can reject (shed /
    deadline-expired) plus the live queue depth — with metrics OFF,
    since the stats() contract predates the switch."""
    from paddle_tpu.serving import (
        InferenceServer,
        RequestDeadlineExceeded,
        ServerSaturated,
    )
    from paddle_tpu.core.resilience import fault_injector

    main = fluid.Program()
    startup = fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[4], dtype="float32")
        out = fluid.layers.fc(input=img, size=2, act="softmax")
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    infer_prog = main.clone(for_test=True)

    assert not metrics.enabled()
    # stall the worker so submits pile up, then overflow the queue
    inj = fault_injector()
    inj.inject("serving.dispatch", "delay", nth=1, count=100,
               delay_s=0.2)
    server = InferenceServer(infer_prog, "img", out, scope,
                             place=fluid.CPUPlace(), buckets=(1, 2),
                             window_ms=0.0, max_queue=2)
    try:
        x = np.ones(4, np.float32)
        futs, sheds = [], 0
        deadline_fut = None
        for i in range(8):
            try:
                if deadline_fut is None and i >= 1:
                    deadline_fut = server.submit(x, deadline_ms=0.001)
                    futs.append(deadline_fut)
                else:
                    futs.append(server.submit(x))
            except ServerSaturated:
                sheds += 1
        assert sheds > 0
        stats = server.stats()
        assert stats["shed"] == sheds
        assert stats["queue_depth"] >= 0
        assert set(stats) == {"requests", "dispatches", "shed",
                              "deadline_expired", "queue_depth"}
        # drain: the deadline future must have expired in the queue
        for f in futs:
            try:
                f.result(timeout=10)
            except RequestDeadlineExceeded:
                pass
        assert server.stats()["deadline_expired"] >= 1
        assert server.stats()["requests"] >= 1
    finally:
        inj.clear()
        server.close()


# ---------------------------------------------------------------------------
# wire propagation + the 2-trainer x 1-pserver acceptance run
# ---------------------------------------------------------------------------


def _sgd_program(param_name, grad_name):
    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        blk = prog.global_block()
        p = blk.create_var(name=param_name, shape=[4], dtype="float32",
                           persistable=True)
        g = blk.create_var(name=grad_name, shape=[4], dtype="float32",
                           persistable=True)
        lr = blk.create_var(name="pserver_lr", shape=[1],
                            dtype="float32", persistable=True)
        blk.append_op("sgd",
                      {"Param": [p.name], "Grad": [g.name],
                       "LearningRate": [lr.name]},
                      {"ParamOut": [p.name]}, {})
    return prog


def test_wire_propagation_one_trace_id_both_sides():
    # Deflaked (was 1-in-4 under host load): the server used to SEND
    # the reply inside its span, so the client could return — and this
    # test read finished_spans() — while the server thread was still
    # parked between sendall and the span record.  _serve_conn now
    # buffers the reply and sends it only after the span context
    # manager exits, making "client saw the reply => server span
    # recorded" an invariant (pinned over 30 iterations in
    # tests/test_fleet_telemetry.py).
    from paddle_tpu.parallel.pserver import VariableClient, VariableServer

    tracing.set_enabled(True)
    scope = fluid.Scope()
    scope.set_var("w", np.ones(4, np.float32))
    server = VariableServer(None, scope, None, fan_in=1)
    port = server.serve(0)
    try:
        client = VariableClient(f"127.0.0.1:{port}")
        with tracing.span("trainer.step") as step:
            client.get_var("w")
        client.close()
    finally:
        server.stop()
    spans = tracing.finished_spans()
    client_get = [s for s in spans if s["name"] == "pserver.client.get"]
    server_get = [s for s in spans if s["name"] == "pserver.get"]
    assert len(client_get) == 1 and len(server_get) == 1
    # one trace across the wire: trainer step -> client span -> server
    # handler span, parented exactly
    assert client_get[0]["trace_id"] == step.context.trace_id
    assert server_get[0]["trace_id"] == step.context.trace_id
    assert server_get[0]["parent_id"] == client_get[0]["span_id"]
    # the handler ran on the server's thread, not the caller's
    assert server_get[0]["tid"] != client_get[0]["tid"]


def test_frames_without_trace_header_still_work():
    """Backward compat: hand-rolled frames lacking the trace field (the
    pre-PR wire format) parse and serve unchanged."""
    import socket as socket_mod
    import struct

    from paddle_tpu.parallel.pserver import (
        VariableServer,
        _recv_frame,
        deserialize_var,
    )

    scope = fluid.Scope()
    scope.set_var("w", np.arange(4, dtype=np.float32))
    server = VariableServer(None, scope, None, fan_in=1)
    port = server.serve(0)
    try:
        s = socket_mod.create_connection(("127.0.0.1", port), timeout=5)
        hdr = struct.Struct("<I")

        def send_legacy(verb, name=""):
            head = json.dumps({"verb": verb, "name": name}).encode()
            s.sendall(hdr.pack(len(head)) + hdr.pack(0) + head)

        send_legacy("HELLO", "legacy-client")
        verb, _, _, trace = _recv_frame(s)
        assert verb == "OK" and trace is None
        send_legacy("GET", "w")
        verb, name, payload, _ = _recv_frame(s)
        assert verb == "VAR"
        np.testing.assert_array_equal(deserialize_var(payload),
                                      np.arange(4, dtype=np.float32))
        s.close()
    finally:
        server.stop()


def test_two_trainer_one_pserver_metrics_and_trace(tmp_path):
    """Acceptance: a 2-trainer x 1-pserver round under metrics + tracing
    produces (a) a Prometheus dump with executor, serving, pserver and
    resilience series and (b) a valid Chrome trace where a trainer-side
    span and its pserver-side child share a trace id."""
    from paddle_tpu.core.resilience import fault_injector
    from paddle_tpu.parallel.pserver import VariableClient, VariableServer
    from paddle_tpu.serving import InferenceServer

    metrics.set_enabled(True)
    tracing.set_enabled(True)
    barrier_child = metrics.registry().get(
        "paddle_tpu_pserver_requests_total").labels(verb="BARRIER")
    barriers_before = barrier_child.value

    # -- pserver with a real optimize program (exercises the executor
    #    series too: the server runs Executor.run per round)
    scope = fluid.Scope()
    scope.set_var("w", np.ones(4, np.float32))
    scope.set_var("pserver_lr", np.array([0.1], np.float32))
    exe = fluid.Executor(fluid.CPUPlace())
    server = VariableServer(_sgd_program("w", "w@GRAD"), scope, exe,
                            fan_in=2)
    port = server.serve(0)

    # one injected transport fault -> a client retry -> resilience series
    inj = fault_injector()
    inj.inject("pserver.request", "error", nth=3)

    def trainer(tid, grad):
        client = VariableClient(f"127.0.0.1:{port}",
                                client_id=f"trainer-{tid}")
        with tracing.span("trainer.step", trainer=tid):
            client.send_var("w@GRAD", grad)
            client.send_batch_barrier()
            w = client.get_var("w")
        client.close()
        return w

    results = {}
    threads = [threading.Thread(
        target=lambda i=i: results.update(
            {i: trainer(i, np.full(4, i + 1.0, np.float32))}))
        for i in range(2)]
    try:
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert set(results) == {0, 1}
        # fan-in really happened: w -= lr * (g0 + g1)
        np.testing.assert_allclose(results[0],
                                   np.full(4, 1.0 - 0.1 * 3.0), rtol=1e-6)
    finally:
        inj.clear()
        server.stop()

    # -- one serving request so the serving series are live
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        img = fluid.layers.data(name="img", shape=[4], dtype="float32")
        out = fluid.layers.fc(input=img, size=2, act="softmax")
    sscope = fluid.Scope()
    exe.run(startup, scope=sscope)
    infer_server = InferenceServer(main.clone(for_test=True), "img", out,
                                   sscope, place=fluid.CPUPlace(),
                                   buckets=(1, 2))
    try:
        infer_server.infer(np.ones(4, np.float32), timeout=30)
        # (a) dump while the server is live — close() reclaims its
        # per-instance series from the registry
        prom_path = exporters.write_prometheus(
            str(tmp_path / "metrics.prom"))
    finally:
        infer_server.close()
    text = open(prom_path).read()
    for series in ("paddle_tpu_executor_cache_lookups_total",
                   "paddle_tpu_serving_requests_total",
                   "paddle_tpu_pserver_bytes_sent_total",
                   "paddle_tpu_pserver_requests_total",
                   "paddle_tpu_resilience_retry_attempts_total"):
        assert series in text, f"missing {series} in dump"
    assert barrier_child.value == barriers_before + 2
    assert 'paddle_tpu_pserver_requests_total{verb="BARRIER"}' in text

    # (b) Chrome trace: a trainer-side span and its pserver-side child
    # share one trace id
    trace_path = tracing.write_chrome_trace(str(tmp_path / "trace.json"))
    with open(trace_path) as f:
        payload = json.load(f)
    events = [e for e in payload["traceEvents"] if e["ph"] == "X"]
    assert events, "empty chrome trace"
    steps = [e for e in events if e["name"] == "trainer.step"]
    server_side = [e for e in events
                   if e["name"].startswith("pserver.")
                   and not e["name"].startswith("pserver.client")]
    assert len(steps) == 2
    matched = 0
    for st in steps:
        tid = st["args"]["trace_id"]
        children = [e for e in server_side
                    if e["args"]["trace_id"] == tid]
        assert children, f"no pserver-side span in trace {tid}"
        matched += len(children)
    assert matched >= 6  # send+barrier+get per trainer, server side


# ---------------------------------------------------------------------------
# overhead guards: instruments off / flight recorder armed must be
# near-free on a hot loop.  The first counts what the off path does
# (D14: its timing was the suite's one flaky test); the second still
# times.  Each probe runs in a FRESH interpreter: the timing
# guard compares paired loop timings at 5% granularity, and in-process
# that marginal is polluted by whatever heap/allocator state the test
# modules that happen to run earlier in the suite leave behind — the
# instrumented side ALLOCATES (span records, ring entries) while the
# bare side doesn't, so fragmentation inflates exactly the quantity
# under test (observed: the same probe green 8x in isolation, ~1-in-3
# red after a serving-heavy module ran first).  A subprocess pins the
# baseline; noise can still only INFLATE a round, so one retry keeps a
# loaded host from flagging a false regression.
# ---------------------------------------------------------------------------


def _run_probe(script):
    """Run `script` in a fresh interpreter with every observability
    switch of the environment off; its last line of output, as JSON."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_TPU_METRICS",
                                "PADDLE_TPU_TRACE",
                                "PADDLE_TPU_FLIGHT",
                                "PADDLE_TPU_FAULTS"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = repo + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([sys.executable, "-c", script], text=True,
                         capture_output=True, env=env, timeout=180)
    assert out.returncode == 0, out.stderr
    return json.loads(out.stdout.strip().splitlines()[-1])


def _overhead_probe(script, attempts=2):
    best = None
    for _ in range(attempts):
        verdict = _run_probe(script)
        if best is None or verdict["overhead"] < best["overhead"]:
            best = verdict
        if best["overhead"] < 0.05:
            break
    return best


@pytest.mark.perf
def test_metrics_off_overhead_under_5_percent():
    """What the instrumented shape of a hot loop (a span with an
    attribute, an attributed phase, a gated counter inc, gauge set and
    histogram observe, a resilience fire()) DOES when everything is
    off, counted and not timed: a wall-clock ratio of two loops under
    six test workers failed in whole runs and passed alone (ledger,
    PR 31 and PR 34).  Counted under `sys.setprofile` in a fresh
    interpreter, an iteration of the six sites:

      * makes no object: no `Span`, no span or phase context, no
        record, and the interpreter holds as many blocks after the
        loop as before it;
      * sets no attribute: the `with` target is None at every site;
      * reads no clock and takes no lock;
      * makes 12 calls in all (`span` and `phase`, the shared no-op's
        `__enter__` and `__exit__` at both, `phase`'s two `enabled()`
        tests, and one each for `inc`, `set`, `observe`, `fire`), none
        of them into C.

    At some 60 ns a call that is under a microsecond an iteration: 1%
    of the 100 us of host work a MINIMAL real step has, which is what
    the 5% of the name was about.  A site that grows a call, a clock
    read or an object when off fails here, whatever the host's load."""
    verdict = _run_probe(r"""
import json, sys
from collections import Counter
from paddle_tpu.core.resilience import fault_injector
from paddle_tpu.observability import attribution, metrics, tracing

assert not (metrics.enabled() or tracing.enabled() or tracing._listeners)
reg = metrics.MetricsRegistry()
c = metrics.counter("bench_total", registry=reg)
g = metrics.gauge("bench_depth", registry=reg)
h = metrics.histogram("bench_seconds", registry=reg)
inj = fault_injector()
n = 200
targets = set()


def instrumented():
    for i in range(n):
        with tracing.span("bench.step", i=i) as sp:
            if sp is not None:
                sp.set_attr("i", i)
        with attribution.phase("generation", "bench") as ph:
            pass
        targets.add(sp)
        targets.add(ph)
        c.inc()
        g.set(i)
        h.observe(0.001)
        inj.fire("bench.site")


calls = Counter()


def prof(frame, event, arg):
    if event == "call":
        calls["py:" + frame.f_code.co_qualname] += 1
    elif event == "c_call":
        calls["c:" + getattr(arg, "__qualname__", repr(arg))] += 1


instrumented()  # warm: caches, the first-use imports
blocks = sys.getallocatedblocks()
instrumented()
blocks = sys.getallocatedblocks() - blocks
sys.setprofile(prof)
instrumented()
sys.setprofile(None)
del calls["py:instrumented"], calls["c:setprofile"]
del calls["c:set.add"]      # the probe's own
print(json.dumps({"calls": calls, "n": n, "blocks": blocks,
                  "targets": sorted(map(repr, targets))}))
""")
    calls, n = verdict["calls"], verdict["n"]
    assert verdict["targets"] == ["None"], verdict["targets"]
    assert abs(verdict["blocks"]) <= 2, verdict["blocks"]
    assert not [k for k in calls if k.startswith("c:")], calls
    made = [k for k in calls if k.endswith((
        "Span.__init__", "_SpanCtx.__init__", "_PhaseCtx.__init__",
        "_record", "_store", "record_span", "set_attr",
        "observe_phase"))]
    assert made == [], calls
    assert set(calls.values()) <= {n, 2 * n}, calls
    assert sum(calls.values()) == 12 * n, calls


@pytest.mark.perf
def test_flight_recorder_armed_overhead_under_5_percent():
    """ARMING the always-on flight recorder must add < 5% to the same
    instrumented hot loop the metrics-off guard above vouches for —
    i.e. the recorder's MARGINAL cost over disabled instruments, which
    is exactly what a fleet pays when it sets PADDLE_TPU_FLIGHT_DIR.
    Armed, the only live machinery is ring-only span capture (~5 µs:
    ids, the record dict, a deque append) plus a note() append — and
    every span site in this codebase wraps a >=ms-scale unit
    (trainer.step, executor.run, pserver verb handling, a serving
    tick), so the loop uses a representative multi-ms step over a
    DRAM-resident working set (real training arrays exceed L3 too; an
    L3-resident array instead measures the span allocations EVICTING
    it — a cache artifact of the microbench, not a cost any real
    >=ms step pays twice).  Both sides run the IDENTICAL instrumented
    loop, alternating armed/disarmed per round; the verdict is the
    ratio of each side's minimum round, since scheduler noise only
    ever inflates a round and the two minima converge on the true
    costs independently."""
    verdict = _overhead_probe(r"""
import json, time
import numpy as np
from paddle_tpu.core.resilience import fault_injector
from paddle_tpu.observability import flightrecorder, metrics, tracing

assert not metrics.enabled() and not tracing.enabled()
reg = metrics.MetricsRegistry()
c = metrics.counter("bench_flight_total", registry=reg)
inj = fault_injector()
x = np.random.RandomState(0).rand(4096, 2048)  # 64 MB
n = 8


def instrumented():
    acc = 0.0
    for i in range(n):
        with tracing.span("bench.step", i=i):
            acc += float(x.sum())
        c.inc()
        inj.fire("bench.site")
        flightrecorder.note("step", i=i)
    return acc


instrumented()  # warm (disarmed)
flightrecorder.install()
instrumented()  # warm (armed)
flightrecorder.uninstall()
t_off, t_on = [], []
for _ in range(9):
    t0 = time.perf_counter()
    instrumented()
    t_off.append(time.perf_counter() - t0)
    flightrecorder.install()
    t0 = time.perf_counter()
    instrumented()
    t_on.append(time.perf_counter() - t0)
    captured = flightrecorder.dump_dict()
    flightrecorder.uninstall()
print(json.dumps({
    "overhead": min(t_on) / min(t_off) - 1.0,
    "off_min": round(min(t_off), 4), "on_min": round(min(t_on), 4),
    "captured_span": any(s["name"] == "bench.step"
                         for s in captured["spans"]),
    "captured_event": any(e["kind"] == "step"
                          for e in captured["events"]),
}))
""")
    assert verdict["overhead"] < 0.05, (
        f"flight-recorder-armed overhead {verdict['overhead']:.1%} "
        f"(disarmed min {verdict['off_min']}s, armed min "
        f"{verdict['on_min']}s over 9 rounds)")
    # and the armed rounds really captured the loop they watched
    assert verdict["captured_span"] and verdict["captured_event"]
