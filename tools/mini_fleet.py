#!/usr/bin/env python
"""Mini-fleet telemetry smoke: 1 trainer x 1 pserver + 1 serving
replica under a TelemetryCollector (tools/ci_check.sh step 11).

The driver hosts the TTL-lease registry and the collector, then spawns
three REAL processes with PADDLE_TPU_METRICS=on and
PADDLE_TPU_TELEMETRY_REGISTRY pointed at the registry:

  * a pserver (`--role pserver`): VariableServer + SGD optimize
    program; its serve() auto-announces the /metrics endpoint;
  * a trainer (`--role trainer`): VariableClient rounds
    (send grad -> barrier -> get) under trainer.step spans, moving the
    real trainer series;
  * a generation replica: `python -m paddle_tpu.cli serve` over a tiny
    saved model dir; the driver streams a few generate requests at it.

While traffic flows the collector scrapes on a period; the driver then
asserts the FEDERATED Prometheus dump carries member-labeled series
from all three kinds, renders the `cli top` fleet table, SIGKILLs the
pserver and asserts its flight-recorder dump (PADDLE_TPU_FLIGHT_DIR)
survived on disk with the pserver's final spans.  The federation dump
is written to --out for the `cli slo --check --prom` gate that follows
in ci_check.

Every child process is pinned to the CPU (JAX_PLATFORMS=cpu in its
environment, `--use_tpu 0` for replicas): several share this host and a
chip belongs to one process, so the fleet drills exercise the control
plane, never the accelerator.

Usage:  python tools/mini_fleet.py [--out /tmp/fleet.prom]
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:  # `python tools/mini_fleet.py` from anywhere
    sys.path.insert(0, REPO)


# ---------------------------------------------------------------------------
# member roles (run in child processes)
# ---------------------------------------------------------------------------


def role_pserver(args):
    import numpy as np

    import paddle_tpu as fluid
    from paddle_tpu.parallel.pserver import VariableServer

    prog = fluid.Program()
    with fluid.program_guard(prog, fluid.Program()):
        blk = prog.global_block()
        p = blk.create_var(name="w", shape=[8], dtype="float32",
                           persistable=True)
        g = blk.create_var(name="w@GRAD", shape=[8], dtype="float32",
                           persistable=True)
        lr = blk.create_var(name="pserver_lr", shape=[1],
                            dtype="float32", persistable=True)
        blk.append_op("sgd",
                      {"Param": [p.name], "Grad": [g.name],
                       "LearningRate": [lr.name]},
                      {"ParamOut": [p.name]}, {})
    scope = fluid.Scope()
    scope.set_var("w", np.ones(8, np.float32))
    scope.set_var("pserver_lr", np.array([0.1], np.float32))
    exe = fluid.Executor(fluid.CPUPlace())
    server = VariableServer(prog, scope, exe, fan_in=1)
    port = server.serve(0)  # announces via PADDLE_TPU_TELEMETRY_REGISTRY
    print(f"PSERVER_PORT {port}", flush=True)
    time.sleep(args.run_s)  # serve until the driver kills us
    server.stop()
    return 0


def role_comm_trainer(args):
    """Trainer driving FUSED rounds through a CommPool against SEVERAL
    pservers (--endpoint ep1,ep2) — the per-endpoint round histogram
    the straggler detector z-scores only exists on this path."""
    import numpy as np

    import paddle_tpu as fluid  # noqa: F401 (registers the series)
    from paddle_tpu.observability import tracing
    from paddle_tpu.observability.collector import maybe_announce
    from paddle_tpu.parallel.comm import CommPool

    maybe_announce("trainer")
    eps = [e for e in args.endpoint.split(",") if e]
    pool = CommPool()
    for i in range(args.rounds):
        with tracing.span("trainer.step", batch_id=i):
            pool.send_round(
                [(ep, "w@GRAD", np.full(8, 0.1, np.float32))
                 for ep in eps],
                [(ep, "w") for ep in eps])
        print(f"TRAINER_ROUND {i}", flush=True)
        time.sleep(0.1)
    print("TRAINER_DONE", flush=True)
    time.sleep(args.linger_s)  # stay scrape-able until the driver kills
    pool.close()
    return 0


def role_trainer(args):
    import numpy as np

    import paddle_tpu as fluid  # noqa: F401 (registers the series)
    from paddle_tpu.observability import metrics, tracing
    from paddle_tpu.observability.collector import maybe_announce
    from paddle_tpu.parallel.pserver import VariableClient

    maybe_announce("trainer")
    # get-or-create the REAL trainer series (paddle_tpu.trainer may
    # not be imported yet; same names, so a real Trainer would share)
    steps = metrics.counter("paddle_tpu_trainer_steps_total",
                            "training steps completed")
    step_s = metrics.histogram(
        "paddle_tpu_trainer_step_seconds",
        "train-loop iteration wall latency (feed ready -> dispatch "
        "done)")
    client = VariableClient(args.endpoint, client_id="mini-fleet")
    for i in range(args.rounds):
        t0 = time.perf_counter()
        with tracing.span("trainer.step", batch_id=i):
            client.send_var("w@GRAD",
                            np.full(8, 0.1, np.float32))
            client.send_batch_barrier()
            client.get_var("w")
        steps.inc()
        step_s.observe(time.perf_counter() - t0)
        print(f"TRAINER_ROUND {i}", flush=True)
        time.sleep(0.15)
    print("TRAINER_DONE", flush=True)
    # stay alive (and scrape-able, lease held) until the driver kills
    # us — exiting releases the lease and delists the member, which
    # would race the driver's final assertions
    time.sleep(args.linger_s)
    client.close()
    return 0


# ---------------------------------------------------------------------------
# driver
# ---------------------------------------------------------------------------


def _spawn(cmd, env, logf):
    import queue
    import threading

    proc = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                            stderr=logf, text=True, cwd=REPO)
    # a reader thread drains stdout into a queue so _wait_line can
    # time out on a child that wedges WITHOUT printing (select() on
    # the raw fd misses lines already pulled into the TextIOWrapper
    # buffer, and a bare readline() blocks past any deadline)
    proc._lines = queue.Queue()

    def _drain():
        for line in proc.stdout:
            proc._lines.put(line)
        proc._lines.put(None)  # EOF marker

    threading.Thread(target=_drain, daemon=True).start()
    return proc


def _wait_line(proc, prefix, timeout_s, what):
    import queue

    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            line = proc._lines.get(timeout=1.0)
        except queue.Empty:
            continue
        if line is None:
            raise SystemExit(f"{what}: exited before '{prefix}' "
                             f"(rc {proc.poll()})")
        print(f"  [{what}] {line.rstrip()}")
        if line.startswith(prefix):
            return line.split()
    raise SystemExit(f"{what}: no '{prefix}' within {timeout_s}s")


def _build_model_dir(workdir, vocab=23, d_model=16, max_blocks=4,
                     kv_blocks=16):
    """A tiny random-init decoder saved as a generation model dir (2
    heads, 1 layer, blocks of 4, 2 slots)."""
    import numpy as np

    import paddle_tpu as fluid
    import paddle_tpu.core.framework as fw
    from paddle_tpu.models.transformer import build_lm_paged_decoder
    from paddle_tpu.serving import save_generation_model

    fw.reset_unique_names()
    startup, dec = build_lm_paged_decoder(vocab, 4, max_blocks,
                                          d_model=d_model, n_heads=2,
                                          n_layers=1)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    states = {n: np.asarray(scope.find_var(n))
              for n in dec.state_names}
    model_dir = os.path.join(workdir, "model")
    save_generation_model(model_dir, states, {
        "vocab_size": vocab, "d_model": d_model, "n_heads": 2,
        "n_layers": 1, "block_size": 4,
        "max_blocks_per_seq": max_blocks, "slots": 2,
        "kv_blocks": kv_blocks})
    return model_dir


def driver(args):
    from paddle_tpu.cli import format_fleet_table
    from paddle_tpu.cloud.registry import Registry
    from paddle_tpu.observability.collector import TelemetryCollector
    from paddle_tpu.serving.replica import replica_call, replica_stream

    workdir = tempfile.mkdtemp(prefix="paddle_mini_fleet_")
    flight_dir = os.path.join(workdir, "flight")
    trace_dir = os.path.join(workdir, "traces")
    print(f"mini-fleet workdir: {workdir}")

    registry = Registry()
    reg_addr = f"127.0.0.1:{registry.serve(0)}"
    coll = TelemetryCollector(registry_addr=reg_addr, period_s=0.3,
                              scrape_timeout_s=1.0)

    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PADDLE_TPU_METRICS="on",
               PADDLE_TPU_TELEMETRY_REGISTRY=reg_addr,
               PADDLE_TPU_FLIGHT_DIR=flight_dir,
               PADDLE_TPU_TRACE_DIR=trace_dir)
    logf = open(os.path.join(workdir, "children.log"), "w")
    me = [sys.executable, os.path.abspath(__file__)]
    procs = []
    try:
        pserver = _spawn(me + ["--role", "pserver",
                               "--run_s", "600"], env, logf)
        procs.append(pserver)
        port = int(_wait_line(pserver, "PSERVER_PORT", 180,
                              "pserver")[1])
        # scrape THROUGH the traffic window: windowed rates/quantiles
        # need samples on both sides of the counters moving
        coll.start()

        trainer = _spawn(me + ["--role", "trainer", "--endpoint",
                               f"127.0.0.1:{port}",
                               "--rounds", str(args.rounds)],
                         env, logf)
        procs.append(trainer)

        model_dir = _build_model_dir(workdir)
        replica = _spawn([sys.executable, "-m", "paddle_tpu.cli",
                          "serve", model_dir, "--use_tpu", "0"],
                         env, logf)
        procs.append(replica)
        line = _wait_line(replica, "serving ", 300, "replica")
        replica_addr = line[3]

        _wait_line(trainer, "TRAINER_DONE", 180, "trainer")

        # a few generate streams so the serving series move, spaced so
        # scrapes land between them
        for i in range(4):
            toks = list(replica_stream(
                replica_addr,
                {"op": "generate", "prompt": [1, 2, 3], "max_new": 5},
                timeout_s=300))
            assert toks, "replica generated nothing"
            time.sleep(0.4)
        print(f"  [driver] replica streamed 4 requests "
              f"({len(toks)} tokens last)")
        assert replica_call(replica_addr,
                            {"op": "flight"})["ok"], "flight op"

        time.sleep(0.5)
        coll.scrape_once()  # one deterministic final sweep

        members = coll.members()
        kinds = {m["kind"] for m in members}
        assert {"trainer", "pserver", "generation"} <= kinds, members
        text = coll.federation_text()
        for kind, series in (
                ("pserver", "paddle_tpu_pserver_requests_total"),
                ("trainer", "paddle_tpu_trainer_steps_total"),
                ("generation",
                 "paddle_tpu_serving_generation_requests_total")):
            member = next(m["member"] for m in members
                          if m["kind"] == kind)
            assert f'kind="{kind}"' in text, f"no {kind} series"
            assert f'member="{member}"' in text, f"no {member} label"
            assert series in text, f"missing {series}"
        print()
        print(format_fleet_table(coll, window_s=60))
        print()

        out = coll.write_federation(args.out)
        print(f"federated Prometheus dump -> {out} "
              f"({len(text.splitlines())} lines, "
              f"{len(members)} members)")

        # flight-recorder recovery from a SIGKILLed pserver: the
        # periodic flush (0.5 s) must have left its final seconds on
        # disk — no handler runs for SIGKILL
        time.sleep(1.5)
        flight_path = os.path.join(flight_dir,
                                   f"flight_{pserver.pid}.json")
        os.kill(pserver.pid, signal.SIGKILL)
        pserver.wait(timeout=30)
        assert os.path.exists(flight_path), \
            f"no flight dump at {flight_path}"
        import json
        with open(flight_path) as f:
            dump = json.load(f)
        span_names = {s["name"] for s in dump["spans"]}
        assert any(n.startswith("pserver.") for n in span_names), \
            span_names
        print(f"flight dump recovered from SIGKILLed pserver: "
              f"{len(dump['spans'])} spans, "
              f"{len(dump['events'])} events")
        print("mini-fleet: all green")
        return 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        coll.close()
        registry.close()
        logf.close()


# ---------------------------------------------------------------------------
# autoscale drill (tools/ci_check.sh step 12)
# ---------------------------------------------------------------------------


# ---------------------------------------------------------------------------
# open-loop ramp against a fleet of real `cli serve` replicas (the
# autoscale drill's driver; tests/test_autoscaler.py rides the ramp)
# ---------------------------------------------------------------------------

VOCAB = 211


def make_requests(n, max_len, rng, long_every=4):
    """Mixed-length open-loop mix: 1 long pole per `long_every`
    requests, the rest short: the shape that separates continuous
    batching from drain-then-refill (a static batch always waits for
    its pole)."""
    reqs = []
    for i in range(n):
        prompt = list(rng.randint(0, VOCAB, rng.randint(2, 9)))
        if i % long_every == long_every - 1:
            max_new = max_len - len(prompt) - 8   # long pole
        else:
            max_new = int(rng.randint(4, 9))      # short answer
        reqs.append((prompt, max_new))
    return reqs


def ramp_rates(peak_rps, floor_frac=0.25):
    """The up-then-down open-loop schedule: floor -> half -> peak ->
    half -> floor."""
    return [peak_rps * floor_frac, peak_rps * 0.5, peak_rps,
            peak_rps * 0.5, peak_rps * floor_frac]


def run_ramp(submit, reqs, rates, phase_s, *, result_timeout_s=180.0,
             deadline_ms=None, on_phase=None):
    """Drive an open-loop up-then-down ramp through `submit(prompt,
    max_new, deadline_ms=...) -> stream` (a GenerationServer or a
    ReplicaRouter — the fleet path).  Arrivals follow the rate
    schedule alone; each request is attributed to the phase it ARRIVED
    in.  Returns per-phase tokens/s, p50/p99 completion latency and
    shed rate, plus the totals the zero-failed acceptance pins:
    `failed` counts non-shed errors (sheds are policy answers)."""
    import threading

    import numpy as np

    from paddle_tpu.serving import (RequestDeadlineExceeded,
                                    ServerSaturated)

    reqs = list(reqs)
    results = []  # (phase, latency_or_None, ntokens, shed, failed)
    rlock = threading.Lock()
    waiters = []
    it = iter(reqs)

    def wait_for(phase, t0, stream):
        lat = ntok = 0
        shed = failed = False
        try:
            out = stream.result(timeout=result_timeout_s)
            lat, ntok = time.perf_counter() - t0, len(out)
        except (RequestDeadlineExceeded, ServerSaturated):
            shed = True
        except Exception:
            failed = True
        with rlock:
            results.append((phase, lat if ntok else None, ntok, shed,
                            failed))

    t_start = time.perf_counter()
    for phase, rate in enumerate(rates):
        phase_t0 = time.perf_counter()
        n_phase = max(1, int(rate * phase_s))
        for i in range(n_phase):
            target = phase_t0 + i / rate if rate > 0 else phase_t0
            delay = target - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
            req = next(it, None)
            if req is None:
                it = iter(reqs)   # recycle the mix
                req = next(it)
            prompt, max_new = req
            t0 = time.perf_counter()
            try:
                stream = submit(prompt, max_new,
                                deadline_ms=deadline_ms)
            except (ServerSaturated, RequestDeadlineExceeded):
                with rlock:
                    results.append((phase, None, 0, True, False))
                continue
            except Exception:
                with rlock:
                    results.append((phase, None, 0, False, True))
                continue
            w = threading.Thread(target=wait_for,
                                 args=(phase, t0, stream), daemon=True)
            w.start()
            waiters.append(w)
        left = phase_s - (time.perf_counter() - phase_t0)
        if left > 0:
            time.sleep(left)
        if on_phase is not None:
            on_phase(phase, rate)
    for w in waiters:
        w.join(timeout=result_timeout_s)
    wall = time.perf_counter() - t_start

    phases = []
    for phase, rate in enumerate(rates):
        rows = [r for r in results if r[0] == phase]
        lats = [r[1] for r in rows if r[1] is not None]
        toks = sum(r[2] for r in rows)
        phases.append({
            "phase": phase, "rate_rps": round(rate, 2),
            "requests": len(rows),
            "tokens_per_sec": round(toks / phase_s, 1),
            "latency_p50_s": round(float(np.percentile(lats, 50)), 4)
            if lats else None,
            "latency_p99_s": round(float(np.percentile(lats, 99)), 4)
            if lats else None,
            "shed_rate": round(sum(r[3] for r in rows)
                               / max(len(rows), 1), 4),
        })
    return {
        "rates_rps": [round(r, 2) for r in rates],
        "phase_s": phase_s,
        "wall_s": round(wall, 2),
        "requests": len(results),
        "tokens": sum(r[2] for r in results),
        "shed": sum(1 for r in results if r[3]),
        "failed": sum(1 for r in results if r[4]),
        "phases": phases,
    }


def run_fleet_ramp(*, requests=64, peak_rps=20.0, phase_s=6.0,
                   max_replicas=3, backlog_low=8.0, sustain_s=1.0,
                   idle_sustain_s=4.0, cooldown_s=4.0, d_model=32,
                   decode_delay_s=0.02, phase_hook=None, post_hook=None,
                   env_extra=None):
    """Save a model dir, front it with
    ReplicaRouter + Autoscaler spawning REAL `cli serve` replicas,
    drive the open-loop ramp, and report per-phase serving stats
    alongside the scaling timeline and each surviving replica's
    warmup accounting (compiles vs compile-cache hits).

    This is a CPU fleet: several replicas share one host and a
    chip belongs to one process, so every replica is pinned to the CPU
    (`--use_tpu 0`, JAX_PLATFORMS=cpu in its environment) — the calling
    process may hold the chip without starving them.

    `decode_delay_s` arms a PADDLE_TPU_FAULTS delay rule on the
    replicas' ``serving.decode`` chaos site: the bench model is tiny
    (a laptop CPU decodes it at thousands of tokens/s), so the
    injected per-tick latency stands in for a real accelerator's — it
    makes the overload, and therefore the scale-out/scale-in
    trajectory, deterministic across hosts.  Pass 0 to measure the
    raw fleet instead.

    Chaos-drill hooks (`drill_autoscale` rides this function):
    `phase_hook(phase, rate, router, scaler)` fires after each ramp
    phase (e.g. SIGKILL an owned replica at the peak);
    `post_hook(record, router, scaler)` fires on the finished record
    BEFORE teardown (the autoscaler/router metric series are reclaimed
    on close, so a telemetry scrape must happen here); `env_extra`
    merges into the replica environment."""
    import shutil

    import numpy as np

    from paddle_tpu.cloud.autoscaler import (Autoscaler,
                                             AutoscalerPolicy,
                                             SubprocessReplicaLauncher)
    from paddle_tpu.cloud.router import ReplicaRouter
    from paddle_tpu.serving.replica import replica_call

    workdir = tempfile.mkdtemp(prefix="paddle_ramp_")
    min_replicas, max_blocks, spawn_timeout_s = 1, 8, 300.0
    model_dir = _build_model_dir(workdir, vocab=VOCAB, d_model=d_model,
                                 max_blocks=max_blocks, kv_blocks=24)

    router = ReplicaRouter(desired=max_replicas * 2, refresh_s=0.1)
    policy = AutoscalerPolicy(
        min_replicas, max_replicas, p99_high_s=30.0,
        backlog_high=64.0, backlog_low=backlog_low,
        sustain_s=sustain_s, idle_sustain_s=idle_sustain_s,
        cooldown_s=cooldown_s)
    extra = dict(env_extra or {}, JAX_PLATFORMS="cpu")
    if decode_delay_s > 0:
        extra["PADDLE_TPU_FAULTS"] = ",".join(filter(None, [
            extra.get("PADDLE_TPU_FAULTS",
                      os.environ.get("PADDLE_TPU_FAULTS", "")),
            f"serving.decode:delay:1:1000000000:{decode_delay_s}"]))
    launcher = SubprocessReplicaLauncher(
        model_dir, router.registry_addr, use_tpu=0, ttl_s=1.5,
        drain_grace_s=30.0, env=dict(os.environ, **extra))
    scaler = Autoscaler(router, launcher, policy, poll_s=0.2,
                        window_s=8.0,
                        spawn_timeout_s=spawn_timeout_s,
                        drain_grace_s=30.0)
    reqs = make_requests(requests, 4 * max_blocks,
                         np.random.RandomState(0))
    fleet_sizes = []

    def _on_phase(p, r):
        fleet_sizes.append(
            len(router.live_replicas(include_draining=False)))
        if phase_hook is not None:
            phase_hook(p, r, router, scaler)

    try:
        scaler.ensure_min(timeout_s=spawn_timeout_s)
        scaler.start()
        ramp = run_ramp(
            router.submit, reqs, ramp_rates(peak_rps), phase_s,
            on_phase=_on_phase)
        # ramp-down tail: give the idle-sustain window room to retire
        deadline = time.monotonic() + 4 * (idle_sustain_s
                                           + cooldown_s) + 30
        while (len(router.live_replicas(include_draining=False))
               > min_replicas and time.monotonic() < deadline):
            time.sleep(0.2)
        replicas = {}
        for addr in router.live_replicas():
            try:
                st = replica_call(addr, {"op": "stats"},
                                  timeout_s=10)["stats"]
                replicas[addr] = {
                    "warm_start": st.get("warm_start"),
                    "warmup_s": st.get("warmup_s"),
                    "compile_seconds": st.get("compile_seconds"),
                    "cache_hits": st.get("cache_hits"),
                    "cache_misses": st.get("cache_misses"),
                    "recompiles_after_warmup":
                        st.get("recompiles_after_warmup"),
                }
            except OSError:
                pass
        out = {
            "peak_rps": peak_rps, "phase_s": phase_s,
            "decode_delay_s": decode_delay_s,
            "band": [min_replicas, max_replicas],
            "ramp": ramp,
            "fleet_size_per_phase": fleet_sizes,
            "fleet_size_final": len(
                router.live_replicas(include_draining=False)),
            "scale_events": list(scaler.events),
            "status": scaler.status(),
            "replicas": replicas,
            "router": router.stats(),
        }
        if post_hook is not None:
            post_hook(out, router, scaler)
        return out
    finally:
        scaler.close(retire_owned=True)
        router.close()
        shutil.rmtree(workdir, ignore_errors=True)


def drill_autoscale(args):
    """Chaos acceptance for the autoscaling fleet (docs/serving.md
    "Autoscaling"): ride `run_fleet_ramp`, which owns the
    model/router/autoscaler/teardown, with its
    chaos hooks: ramp open-loop load until a second `cli serve` replica
    spawns, SIGKILL one AT THE PEAK (phase_hook), keep ramping down
    until the fleet scales back in — asserting ZERO failed requests end
    to end (the router's resume contract holds through spawn, drain,
    and the SIGKILL), that the fleet actually grew and shrank, and that
    the warm-started scale-out replicas deserialized their executables.
    The federated Prometheus dump (driver announces the
    router/autoscaler series; post_hook scrapes before teardown
    reclaims them) is written to --out for the `cli slo --check --prom`
    fleet-size / crash-loop / zero-failed gate that follows in
    ci_check."""
    import signal as _signal

    from paddle_tpu.cloud.registry import Registry
    from paddle_tpu.observability.collector import (TelemetryCollector,
                                                    maybe_announce)

    telem_registry = Registry()
    telem_addr = f"127.0.0.1:{telem_registry.serve(0)}"
    # federate the driver's own series (router + autoscaler gauges/
    # counters) so the SLO gate sees fleet.replicas / crashloops /
    # router outcome counters
    os.environ["PADDLE_TPU_TELEMETRY_REGISTRY"] = telem_addr
    ann = maybe_announce("router")
    coll = TelemetryCollector(registry_addr=telem_addr, period_s=0.3)
    coll.start()

    killed = {"pid": None}

    def phase_hook(phase, rate, router, scaler):
        live = router.live_replicas(include_draining=False)
        print(f"  [drill] phase {phase} (rate {rate:.0f}/s) done: "
              f"fleet size {len(live)}", flush=True)
        if phase == 2 and killed["pid"] is None:
            owned = scaler.owned_pids()
            if len(owned) >= 2:
                addr, pid = sorted(owned.items())[-1]
                killed["pid"] = pid
                print(f"  [drill] SIGKILL replica {addr} (pid {pid}) "
                      "at the peak", flush=True)
                os.kill(pid, _signal.SIGKILL)

    def post_hook(record, router, scaler):
        # scrape while the driver's router/autoscaler series still
        # exist — teardown reclaims them on close()
        time.sleep(1.0)
        coll.scrape_once()

    try:
        record = run_fleet_ramp(
            requests=64, peak_rps=args.peak_rps, phase_s=args.phase_s,
            max_replicas=args.max_replicas, backlog_low=6.0,
            sustain_s=0.8, idle_sustain_s=3.0, cooldown_s=3.0,
            d_model=16, decode_delay_s=args.decode_delay,
            phase_hook=phase_hook, post_hook=post_hook,
            env_extra={
                "JAX_PLATFORMS": "cpu",
                "PADDLE_TPU_METRICS": "on",
                "PADDLE_TPU_TELEMETRY_REGISTRY": telem_addr})
        ramp = record["ramp"]
        sizes = record["fleet_size_per_phase"]
        print(f"  [drill] ramp: {ramp['requests']} requests, "
              f"{ramp['shed']} shed, {ramp['failed']} failed")
        for e in record["scale_events"]:
            print(f"  [drill] {e}")
        assert ramp["failed"] == 0, \
            f"{ramp['failed']} requests FAILED (zero-failed contract)"
        assert max(sizes) >= 2, \
            f"fleet never scaled out (sizes {sizes})"
        assert killed["pid"] is not None, \
            "drill never found a second owned replica to SIGKILL"
        assert record["fleet_size_final"] == 1, record
        assert record["status"]["crashloops"] == 0, record["status"]
        # no surviving replica paid a compile inside request latency
        assert record["replicas"], record
        for addr, rs in record["replicas"].items():
            assert rs["recompiles_after_warmup"] == 0, (addr, rs)
        text = coll.federation_text()
        for series in ("paddle_tpu_autoscaler_replicas_live",
                       "paddle_tpu_autoscaler_scale_events_total",
                       "paddle_tpu_serving_router_requests_total"):
            assert series in text, f"missing {series} in federation"
        out = coll.write_federation(args.out)
        print(f"federated Prometheus dump -> {out}")
        print("autoscale drill: all green "
              f"(sizes {sizes} -> {record['fleet_size_final']}, "
              f"{ramp['requests']} requests, 0 failed)")
        return 0
    finally:
        if ann is not None:
            ann.close()
        coll.close()
        telem_registry.close()


# ---------------------------------------------------------------------------
# time-attribution drill (tools/ci_check.sh step 13)
# ---------------------------------------------------------------------------

_PHASE_OVERHEAD_PROBE = r"""
import json, time
import numpy as np
from paddle_tpu.observability import attribution, exemplars, metrics, tracing

assert not metrics.enabled() and not tracing.enabled()
x = np.random.RandomState(0).rand(512, 512)
n = 100


def step_light():
    return float(x.sum())          # ~100 us: worst case for noop sites


def step_tick():
    return float((x @ x)[0, 0])    # ~ms: a realistic serving-tick body


def plain(step):
    acc = 0.0
    for _ in range(n):
        acc += step()
    return acc


def attributed(step, traced):
    acc = 0.0
    for _ in range(n):
        if traced:
            with tracing.span("probe.tick"):
                with attribution.phase("generation", "decode"):
                    acc += step()
                for ph in ("sample", "deliver", "kv_alloc", "admit"):
                    with attribution.phase("generation", ph):
                        pass
        else:
            with attribution.phase("generation", "decode"):
                acc += step()
            for ph in ("sample", "deliver", "kv_alloc", "admit"):
                with attribution.phase("generation", ph):
                    pass
    return acc


def measure(step, traced):
    plain(step)  # warm both paths
    attributed(step, traced)
    ratios = []
    for _ in range(7):
        t0 = time.perf_counter()
        plain(step)
        t_plain = time.perf_counter() - t0
        t0 = time.perf_counter()
        attributed(step, traced)
        t_attr = time.perf_counter() - t0
        ratios.append(t_attr / t_plain)
        tracing.clear()
    return min(ratios) - 1.0, [round(r, 3) for r in ratios]

# (1) whole stack off: five noop phase() sites on the ~100 us step
off, off_ratios = measure(step_light, traced=False)
# (2) everything armed — metrics + tracing + exemplars + tail sampler —
# on a tick-sized step, each iteration under a root span so every
# histogram observation records an exemplar and the sampler sees the
# full span tree (threshold high enough that nothing is ever kept:
# steady-state cost, not flush cost)
metrics.set_enabled(True)
tracing.set_enabled(True)
exemplars.set_armed(True)
tracing.arm_tail_sampler(threshold_s=3600.0)
on, on_ratios = measure(step_tick, traced=True)
print(json.dumps({"overhead_off": off, "off_ratios": off_ratios,
                  "overhead_on": on, "on_ratios": on_ratios}))
"""


def _phase_overhead_guard(attempts=2):
    """Both ends of the attribution cost spectrum must stay < 5%:
    five disarmed phase() sites on a ~100 us step (noop path), and the
    fully armed plane — metrics + tracing + exemplars + tail sampler —
    on a tick-sized step.  Same fresh-subprocess + one-retry ladder as
    the tests/test_observability.py guards (noise only ever INFLATES a
    round, so min-of-rounds + best-of-attempts is the honest floor)."""
    env = {k: v for k, v in os.environ.items()
           if not k.startswith(("PADDLE_TPU_METRICS",
                                "PADDLE_TPU_TRACE",
                                "PADDLE_TPU_FLIGHT",
                                "PADDLE_TPU_EXEMPLARS",
                                "PADDLE_TPU_TAIL_SAMPLE"))}
    env["JAX_PLATFORMS"] = "cpu"
    env["PYTHONPATH"] = REPO + os.pathsep + env.get("PYTHONPATH", "")
    import json
    best = None
    for _ in range(attempts):
        out = subprocess.run(
            [sys.executable, "-c", _PHASE_OVERHEAD_PROBE], text=True,
            capture_output=True, env=env, timeout=180)
        assert out.returncode == 0, out.stderr
        verdict = json.loads(out.stdout.strip().splitlines()[-1])
        verdict["worst"] = max(verdict["overhead_off"],
                               verdict["overhead_on"])
        if best is None or verdict["worst"] < best["worst"]:
            best = verdict
        if best["worst"] < 0.05:
            break
    assert best["worst"] < 0.05, \
        (f"attribution overhead: off {best['overhead_off']:.1%} "
         f"({best['off_ratios']}), armed {best['overhead_on']:.1%} "
         f"({best['on_ratios']})")
    print(f"  [drill] attribution overhead: disarmed "
          f"{best['overhead_off']:.1%}, fully armed "
          f"{best['overhead_on']:.1%} (< 5% guard)")


def drill_attribution(args):
    """Time-attribution acceptance (docs/observability.md "Time
    attribution"): a mini-fleet with the attribution plane armed —
    2 pservers (one delay-faulted into a straggler), a CommPool
    trainer, a decode-delay-faulted serving replica with exemplars +
    tail sampling on.  Asserts per-phase series federate from all
    three member kinds, the `cli why` table shows the decode-delay
    fault as the dominant generation phase, a latency exemplar
    resolves through `cli trace-of` to a JOINED Chrome trace, the
    straggler endpoint is flagged within one collector window, and
    the plane stays under the 5% overhead guard both disarmed and
    fully armed (exemplars + tail sampling on).  The
    federated dump goes to --out for the `cli slo --check --prom`
    gate that follows in ci_check."""
    import json

    from paddle_tpu import cli as cli_mod
    from paddle_tpu.cloud.registry import Registry
    from paddle_tpu.observability import attribution
    from paddle_tpu.observability.collector import (TelemetryCollector,
                                                    assemble_traces,
                                                    parse_prometheus_text)
    from paddle_tpu.serving.replica import replica_stream

    _phase_overhead_guard()

    workdir = tempfile.mkdtemp(prefix="paddle_attr_drill_")
    trace_dir = os.path.join(workdir, "traces")
    print(f"attribution drill workdir: {workdir}")

    registry = Registry()
    reg_addr = f"127.0.0.1:{registry.serve(0)}"
    coll = TelemetryCollector(registry_addr=reg_addr, period_s=0.3,
                              scrape_timeout_s=1.0)

    base_env = dict(os.environ,
                    JAX_PLATFORMS="cpu",
                    PADDLE_TPU_METRICS="on",
                    PADDLE_TPU_TELEMETRY_REGISTRY=reg_addr,
                    PADDLE_TPU_TRACE_DIR=trace_dir,
                    PADDLE_TPU_EXEMPLARS="on",
                    PADDLE_TPU_TAIL_SAMPLE="0.05")
    logf = open(os.path.join(workdir, "children.log"), "w")
    me = [sys.executable, os.path.abspath(__file__)]
    procs = []
    try:
        # pserver A healthy; pserver B serves every frame 50 ms late —
        # the client-side per-endpoint round histogram pins the drift
        # on B alone
        ports = []
        for fault in ("", "pserver.serve:delay:1:1000000000:0.05"):
            env = dict(base_env)
            if fault:
                env["PADDLE_TPU_FAULTS"] = fault
            p = _spawn(me + ["--role", "pserver", "--run_s", "600"],
                       env, logf)
            procs.append(p)
            ports.append(int(_wait_line(
                p, "PSERVER_PORT", 180,
                f"pserver{'B' if fault else 'A'}")[1]))
        straggler_ep = f"127.0.0.1:{ports[1]}"
        coll.start()

        trainer = _spawn(
            me + ["--role", "comm_trainer", "--endpoint",
                  ",".join(f"127.0.0.1:{p}" for p in ports),
                  "--rounds", str(args.rounds)], base_env, logf)
        procs.append(trainer)

        # the replica's decode phase eats a 30 ms injected delay per
        # tick: `cli why` must show decode dominating, and every
        # request is slow enough for the tail sampler to keep
        model_dir = _build_model_dir(workdir)
        env = dict(base_env,
                   PADDLE_TPU_FAULTS="serving.decode:delay:1:"
                   "1000000000:0.03")
        replica = _spawn([sys.executable, "-m", "paddle_tpu.cli",
                          "serve", model_dir, "--use_tpu", "0"],
                         env, logf)
        procs.append(replica)
        replica_addr = _wait_line(replica, "serving ", 300,
                                  "replica")[3]

        for i in range(4):
            toks = list(replica_stream(
                replica_addr,
                {"op": "generate", "prompt": [1, 2, 3], "max_new": 5},
                timeout_s=300))
            assert toks, "replica generated nothing"
            time.sleep(0.4)
        _wait_line(trainer, "TRAINER_DONE", 180, "trainer")

        time.sleep(1.2)  # tail-sampler flush cadence + a scrape period
        coll.scrape_once()  # deterministic final sweep + detector pass

        text = coll.federation_text()
        # (a) per-phase series federated from all three member kinds
        for kind in ("generation", "trainer", "pserver"):
            series = f"paddle_tpu_{kind}_phase_seconds"
            assert series in text, f"missing {series}"
            assert f'kind="{kind}"' in text, f"no {kind} member"
        parsed = parse_prometheus_text(text)
        rows = attribution.why_rows_from_parsed(parsed)
        print()
        print(attribution.format_why_table(rows))
        print()
        gen = {r["phase"]: r for r in rows
               if r["kind"] == "generation"}
        assert gen["decode"]["share"] > 0.35, \
            f"decode-delay fault invisible in why-table: {gen}"
        assert rows[0] is not None and len(
            {r["kind"] for r in rows}) == 3

        # (b) straggler flagged within one collector window
        strag = parsed.get(attribution.STRAGGLER_METRIC)
        assert strag, "no straggler scores in federation"
        scores = {s["labels"]["endpoint"]: s["value"]
                  for s in strag["samples"]}
        assert scores.get(straggler_ep, 0.0) >= 3.0, \
            f"straggler {straggler_ep} not flagged: {scores}"
        footer = cli_mod.format_straggler_lines(coll)
        assert "STRAGGLER" in footer, footer
        print(footer)

        # (c) exemplar -> joined end-to-end Chrome trace
        ex = attribution.pick_exemplar(
            parsed, "paddle_tpu_serving_generation_seconds")
        assert ex, "no exemplar on the generation latency histogram"
        joined = assemble_traces(trace_dir)
        assert ex["trace_id"] in joined, \
            (ex["trace_id"], sorted(joined))
        with open(joined[ex["trace_id"]]) as f:
            names = {e["name"]
                     for e in json.load(f)["traceEvents"]}
        assert "serving.request" in names, names
        print(f"  [drill] p99 exemplar {ex['value']:.3f}s -> trace "
              f"{ex['trace_id']} -> {joined[ex['trace_id']]} "
              f"({len(names)} span names)")

        out = coll.write_federation(args.out)
        print(f"federated Prometheus dump -> {out}")

        # the `cli trace-of` surface end to end, off the written dump
        rc = cli_mod.cmd_trace_of(
            ["--metric", "paddle_tpu_serving_generation_seconds",
             "--prom", out, "--p99", "--trace-dir", trace_dir])
        assert rc == 0, "cli trace-of failed"
        print("attribution drill: all green")
        return 0
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
        coll.close()
        registry.close()
        logf.close()


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--role", default="driver",
                    choices=["driver", "pserver", "trainer",
                             "comm_trainer"])
    ap.add_argument("--drill", default="telemetry",
                    choices=["telemetry", "autoscale", "attribution"],
                    help="telemetry: the step-11 federation smoke; "
                    "autoscale: the step-12 scale-out/SIGKILL/"
                    "scale-in chaos drill; attribution: the step-13 "
                    "time-attribution drill (phases, exemplars, "
                    "stragglers)")
    ap.add_argument("--out", default="/tmp/paddle_tpu_fleet.prom")
    ap.add_argument("--endpoint", default="")
    ap.add_argument("--rounds", type=int, default=8)
    ap.add_argument("--scrapes", type=int, default=8)
    ap.add_argument("--run_s", type=float, default=600.0)
    ap.add_argument("--linger_s", type=float, default=600.0)
    ap.add_argument("--peak_rps", type=float, default=20.0)
    ap.add_argument("--phase_s", type=float, default=6.0)
    ap.add_argument("--max_replicas", type=int, default=3)
    ap.add_argument("--decode_delay", type=float, default=0.02)
    args = ap.parse_args(argv)
    if args.role == "pserver":
        return role_pserver(args)
    if args.role == "trainer":
        return role_trainer(args)
    if args.role == "comm_trainer":
        return role_comm_trainer(args)
    if args.drill == "autoscale":
        return drill_autoscale(args)
    if args.drill == "attribution":
        return drill_attribution(args)
    return driver(args)


if __name__ == "__main__":
    sys.exit(main())
