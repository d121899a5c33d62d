"""Autoscaling serving fleet (cloud/autoscaler.py + the drain/warm-
start machinery it rides on).

Fast tier: pure policy semantics (hysteresis, sustain, cooldown, band,
non-flapping under a noisy signal burst — all on synthetic signals
with injected clocks), the crash-loop detector and its backoff, the
chaos sites, replica drain/resume over the wire, the at-least-one-
replica invariant under a raced death, an in-process fake fleet
scaling out and back in with zero failed requests, and the warm-start
artifact contract (cache_misses == 0, recompiles_after_warmup == 0,
compile-dominated cold baseline documented).

Chaos+slow tier: the ROADMAP-4 acceptance — an open-loop ramp against
REAL `cli serve` subprocess replicas triggers scale-out then scale-in
with a SIGKILL at the peak and ZERO failed requests (mirrors
tools/mini_fleet.py --drill autoscale, ci_check step 12).
"""
import os
import signal
import socket
import sys
import threading
import time

import numpy as np
import pytest

import paddle_tpu as fluid
import paddle_tpu.core.framework as fw
from paddle_tpu.cloud.autoscaler import Autoscaler, AutoscalerPolicy
from paddle_tpu.cloud.router import ReplicaRouter
from paddle_tpu.core.resilience import fault_injector
from paddle_tpu.models.transformer import build_lm_paged_decoder
from paddle_tpu.serving import (GenerationServer, ReplicaServer,
                                save_generation_model,
                                server_from_model_dir)
from paddle_tpu.serving.replica import (ReplicaError, replica_call,
                                        replica_stream)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

V = 23
_DECODERS = {}


def _decoder(max_blocks=5):
    """Shared tiny decoder (one compile for the whole module — the
    tier-1 budget note in CHANGES.md applies here too)."""
    if max_blocks not in _DECODERS:
        fw.reset_unique_names()
        startup, dec = build_lm_paged_decoder(V, 4, max_blocks,
                                              d_model=16, n_heads=2,
                                              n_layers=1)
        scope = fluid.Scope()
        fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
        states = {n: np.asarray(scope.find_var(n))
                  for n in dec.state_names}
        _DECODERS[max_blocks] = (dec, states)
    return _DECODERS[max_blocks]


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    fault_injector().clear()


# ---------------------------------------------------------------------------
# policy: pure decision logic on synthetic signals
# ---------------------------------------------------------------------------


def _sig(backlog=0.0, p99=float("nan"), qps=0.0):
    return {"outstanding_tokens": backlog, "p99": p99, "qps": qps,
            "p50": p99, "replicas_live": 1}


def _policy(**kw):
    kw.setdefault("p99_high_s", 1.0)
    kw.setdefault("backlog_high", 100)
    kw.setdefault("backlog_low", 10)
    kw.setdefault("sustain_s", 2.0)
    kw.setdefault("idle_sustain_s", 5.0)
    kw.setdefault("cooldown_s", 4.0)
    return AutoscalerPolicy(1, 4, **kw)


def test_policy_scale_out_requires_sustained_hot():
    p = _policy()
    assert p.observe(_sig(backlog=500), live=1, now=0.0) == 0
    assert p.observe(_sig(backlog=500), live=1, now=1.9) == 0
    assert p.observe(_sig(backlog=500), live=1, now=2.0) == +1
    # p99 alone is also a hot trigger
    p2 = _policy()
    assert p2.observe(_sig(backlog=0, p99=3.0), live=1, now=0.0) == 0
    assert p2.observe(_sig(backlog=0, p99=3.0), live=1, now=2.5) == +1


def test_policy_scale_in_uses_longer_idle_sustain():
    p = _policy()
    assert p.observe(_sig(backlog=0), live=2, now=0.0) == 0
    assert p.observe(_sig(backlog=0), live=2, now=4.9) == 0
    assert p.observe(_sig(backlog=0), live=2, now=5.0) == -1


def test_policy_band_is_hard():
    p = _policy()
    for t in (0.0, 3.0):
        assert p.observe(_sig(backlog=500), live=4, now=t) == 0
    assert "max_replicas" in p.last_reason
    p2 = _policy()
    for t in (0.0, 6.0):
        assert p2.observe(_sig(backlog=0), live=1, now=t) == 0
    assert "min_replicas" in p2.last_reason
    with pytest.raises(ValueError):
        AutoscalerPolicy(0, 4)           # fleet can never go to zero
    with pytest.raises(ValueError):
        AutoscalerPolicy(1, 4, backlog_low=100, backlog_high=50)


def test_policy_noisy_burst_never_flaps():
    """THE non-flapping pin: a signal oscillating across the hot
    threshold faster than the sustain window accumulates nothing —
    zero scale decisions over a long burst.  Same for the idle side:
    oscillation across the low threshold never retires a replica."""
    p = _policy()
    decisions = []
    for i in range(100):
        now = i * 0.5                     # period < sustain_s = 2.0
        hot = i % 2 == 0
        decisions.append(p.observe(
            _sig(backlog=500 if hot else 50), live=2, now=now))
    assert decisions == [0] * 100
    # idle-side flapping: backlog bounces between cold and mid-band
    p2 = _policy()
    decisions = [p2.observe(_sig(backlog=5 if i % 2 else 50), live=2,
                            now=i * 2.0)
                 for i in range(40)]      # period < idle_sustain_s
    assert decisions == [0] * 40


def test_policy_hysteresis_band_resets_both_clocks():
    p = _policy()
    p.observe(_sig(backlog=500), live=1, now=0.0)      # hot starts
    p.observe(_sig(backlog=50), live=1, now=1.0)       # mid-band reset
    assert p.observe(_sig(backlog=500), live=1, now=2.5) == 0
    assert p.observe(_sig(backlog=500), live=1, now=4.5) == +1


def test_policy_cooldown_blocks_after_action():
    p = _policy()
    assert p.observe(_sig(backlog=500), live=1, now=0.0) == 0
    assert p.observe(_sig(backlog=500), live=1, now=2.0) == +1
    p.record_action(2.5)
    # still hot, but inside the cooldown window (until 6.5): no
    # action.  The sustain clock DOES accumulate through the cooldown
    # — only the action is refractory, not the evidence — so the next
    # decision can fire as soon as the window closes.
    assert p.observe(_sig(backlog=500), live=2, now=3.0) == 0
    assert "cooldown" in p.last_reason
    assert p.observe(_sig(backlog=500), live=2, now=6.0) == 0
    assert "cooldown" in p.last_reason
    assert p.observe(_sig(backlog=500), live=2, now=7.0) == +1


def test_policy_no_data_is_not_hot():
    p = _policy()
    # NaN p99 + zero backlog before any traffic: cold, never hot
    assert not p.is_hot(_sig())
    assert p.is_cold(_sig())
    assert not p.is_cold(_sig(p99=0.9))   # real latency above low bar


# ---------------------------------------------------------------------------
# fake in-process fleet (no subprocesses: fast tier)
# ---------------------------------------------------------------------------


class FakeHandle:
    _pids = iter(range(10_000, 20_000))

    def __init__(self, registry_addr):
        self.pid = next(self._pids)
        dec, states = _decoder()
        self.server = GenerationServer(dec, states, slots=2,
                                       kv_blocks=16,
                                       place=fluid.CPUPlace())
        self.rep = ReplicaServer(self.server,
                                 registry_addr=registry_addr,
                                 ttl_s=1.0)
        self.addr = self.rep.addr

    def alive(self):
        return not self.rep._stop.is_set()

    def terminate(self):
        # what a graceful SIGTERM does in-process
        self.rep.shutdown_gracefully(10)
        self.server.close()

    def kill(self):
        # SIGKILL semantics: sockets die, lease heartbeats stop, no
        # release — the registry TTL must evict it.  shutdown() before
        # close(): a real SIGKILL takes the accept thread with it, so
        # the listening socket fully closes and later connects are
        # REFUSED — a bare close() here would leave the accept thread
        # holding the open file description and the "corpse" would
        # answer one more ping
        self.rep._lease._stop.set()
        self.rep._lease.released = True   # never deregister
        self.rep._stop.set()
        try:
            self.rep._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        self.rep._sock.close()
        self.server.close()

    def wait(self, timeout=None):
        return 0


class FakeLauncher:
    def __init__(self, registry_addr):
        self.registry_addr = registry_addr
        self.spawned = []

    def spawn(self):
        h = FakeHandle(self.registry_addr)
        self.spawned.append(h)
        return h


class DyingLauncher:
    """Every spawn is already dead: the crash-loop shape."""

    def __init__(self, registry_addr):
        self.registry_addr = registry_addr

    class DeadHandle:
        pid = 0
        addr = None

        def alive(self):
            return False

        def kill(self):
            pass

        def terminate(self):
            pass

        def wait(self, timeout=None):
            return 1

    def spawn(self):
        return self.DeadHandle()


def _fleet(policy=None, launcher_cls=FakeLauncher, **scaler_kw):
    router = ReplicaRouter(desired=8, refresh_s=0.05)
    launcher = launcher_cls(router.registry_addr)
    policy = policy or AutoscalerPolicy(
        1, 3, p99_high_s=60.0, backlog_high=60, backlog_low=5,
        sustain_s=0.2, idle_sustain_s=0.5, cooldown_s=0.2)
    scaler_kw.setdefault("poll_s", 0.05)
    scaler_kw.setdefault("window_s", 5.0)
    scaler_kw.setdefault("drain_grace_s", 15.0)
    scaler = Autoscaler(router, launcher, policy, **scaler_kw)
    return router, launcher, scaler


def _teardown(router, launcher, scaler):
    scaler.close()
    for h in getattr(launcher, "spawned", []):
        if h.alive():
            h.kill()
    router.close()


def test_autoscaler_scales_out_and_in_zero_failed():
    """The fast acceptance loop: sustained backlog grows the fake
    fleet, idleness shrinks it via graceful drain, every request
    completes (zero failed), and the policy's reasons land in the
    event log."""
    router, launcher, scaler = _fleet()
    streams, slock = [], threading.Lock()
    stop = threading.Event()
    try:
        scaler.ensure_min(timeout_s=60)
        assert len(router.live_replicas()) == 1

        def feeder():    # keep ~10 long generations outstanding
            while not stop.is_set():
                with slock:
                    if sum(not s.done for s in streams) < 10:
                        streams.append(router.submit([1, 2, 3], 16))
                time.sleep(0.002)

        t = threading.Thread(target=feeder, daemon=True)
        t.start()
        deadline = time.monotonic() + 60
        while (len(router.live_replicas(include_draining=False)) < 2
               and time.monotonic() < deadline):
            scaler.poll()
            time.sleep(0.02)
        assert len(router.live_replicas(include_draining=False)) >= 2, \
            scaler.events
        stop.set()
        t.join(timeout=5)
        with slock:
            snap = list(streams)
        for s in snap:
            assert len(s.result(timeout=120)) == 16
        assert router.stats()["requests_failed"] == 0

        # idle: drains back to the floor via the graceful path
        deadline = time.monotonic() + 60
        while (len(router.live_replicas()) > 1
               and time.monotonic() < deadline):
            scaler.poll()
            time.sleep(0.02)
        assert len(router.live_replicas()) == 1, scaler.events
        assert any("scale-in complete" in e for e in scaler.events)
        assert router.stats()["draining"] == []   # no marks left
    finally:
        stop.set()
        _teardown(router, launcher, scaler)


def test_scale_in_invariant_survives_raced_sigkill(monkeypatch):
    """The at-least-one-replica pin: scale-in has drained its victim
    when a SIGKILL takes the LAST survivor — the re-count notices,
    the victim is resumed instead of retired, and the fleet never
    drops below the floor."""
    import paddle_tpu.cloud.autoscaler as asc

    router, launcher, scaler = _fleet()
    try:
        scaler.ensure_min(timeout_s=60)
        h2 = launcher.spawn()             # second replica, adopted
        deadline = time.monotonic() + 30
        while (len(router.live_replicas()) < 2
               and time.monotonic() < deadline):
            scaler.poll()
            time.sleep(0.02)
        assert len(router.live_replicas()) == 2

        real_call = asc.replica_call
        state = {"killed": False}

        def racing_call(addr, obj, **kw):
            out = real_call(addr, obj, **kw)
            if obj.get("op") == "drain" and not state["killed"]:
                state["killed"] = True
                # the OTHER replica dies between drain and retire
                other = next(h for h in launcher.spawned
                             if h.addr != addr and h.alive())
                other.kill()
            return out

        monkeypatch.setattr(asc, "replica_call", racing_call)
        victim = scaler._pick_victim(
            router.live_replicas(include_draining=False))
        # registry delisting of the killed replica takes one TTL
        retired = scaler._scale_in(time.monotonic(),
                                   router.live_replicas())
        assert state["killed"]
        assert not retired, scaler.events
        assert any("aborted" in e for e in scaler.events)
        # the resumed victim still serves: the fleet floor held
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            live = router.live_replicas(include_draining=False)
            if live == [victim]:
                break
            time.sleep(0.05)
        assert router.live_replicas(include_draining=False) == [victim]
        assert not replica_call(victim, {"op": "ping"})["draining"]
        assert router.generate([1, 2, 3], 4, timeout=60)
    finally:
        _teardown(router, launcher, scaler)


def test_poll_restores_min_replicas_after_out_of_band_death():
    """The floor is repair, not policy: the last replica dying OUTSIDE
    a scale-in (OOM kill, hardware) leaves a fleet whose signals look
    cold — no traffic moves, so no backlog and no p99 — and the policy
    alone would idle at zero forever.  poll() must spawn back to
    min_replicas regardless of signals."""
    router, launcher, scaler = _fleet()
    try:
        scaler.ensure_min(timeout_s=60)
        victim = launcher.spawned[0]
        victim.kill()                     # SIGKILL semantics: no lease
        # the registry TTL (1s) evicts the corpse; poll then repairs
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            scaler.poll()
            live = router.live_replicas(include_draining=False)
            if live and victim.addr not in live:
                break
            time.sleep(0.05)
        live = router.live_replicas(include_draining=False)
        assert live and victim.addr not in live, scaler.events
        assert any("below min_replicas" in e for e in scaler.events)
        assert router.generate([1, 2, 3], 4, timeout=60)
    finally:
        _teardown(router, launcher, scaler)


def test_scale_in_aborts_when_drain_times_out(monkeypatch):
    """A drain reply of {'drained': false} (grace expired with accepted
    streams still running) must ABORT the scale-in — retiring a
    not-drained replica would cut its streams mid-flight — and resume
    the victim."""
    import paddle_tpu.cloud.autoscaler as asc

    router, launcher, scaler = _fleet()
    try:
        scaler.ensure_min(timeout_s=60)
        launcher.spawn()                  # a second replica to retire
        deadline = time.monotonic() + 30
        while (len(router.live_replicas()) < 2
               and time.monotonic() < deadline):
            scaler.poll()
            time.sleep(0.02)
        assert len(router.live_replicas()) == 2

        real_call = asc.replica_call

        def timing_out_call(addr, obj, **kw):
            if obj.get("op") == "drain":
                real_call(addr, obj, **kw)     # really stop admission
                return {"ok": True, "drained": False}
            return real_call(addr, obj, **kw)

        monkeypatch.setattr(asc, "replica_call", timing_out_call)
        retired = scaler._scale_in(time.monotonic(),
                                   router.live_replicas())
        assert not retired, scaler.events
        assert any("not drained" in e for e in scaler.events)
        assert len(router.live_replicas()) == 2    # nothing retired
        assert router.stats()["draining"] == []    # mark removed
        for addr in router.live_replicas():
            assert not replica_call(addr, {"op": "ping"})["draining"]
    finally:
        _teardown(router, launcher, scaler)


def test_banner_parse_survives_spaces_in_model_dir():
    """The spawn banner is 'serving MODEL_DIR on HOST:PORT[, ...]' —
    a model dir containing spaces (or ' on ') must still parse to the
    ADDRESS, never a path fragment (which would make _check_pending
    kill a healthy replica at spawn_timeout as never-joined)."""
    from paddle_tpu.cloud.autoscaler import ReplicaProcess

    class FakeProc:
        pid = 1

        def __init__(self, lines):
            self.stdout = iter(lines)

        def poll(self):
            return None

    for line, want in [
        ("serving /tmp/my models/llm on 127.0.0.1:4242, registered "
         "in 127.0.0.1:9 (warm start: 1 executables deserialized)\n",
         "127.0.0.1:4242"),
        ("serving /data/on call/m on 10.0.0.7:80 (cold start: 3 "
         "compiles, warmup 0.5s)\n", "10.0.0.7:80"),
        ("serving plain on 127.0.0.1:1\n", "127.0.0.1:1"),
    ]:
        h = ReplicaProcess.__new__(ReplicaProcess)
        h.proc, h.pid, h.addr = FakeProc([line]), 1, None
        h._read_banner()
        assert h.addr == want, (line, h.addr)


def test_pending_join_not_absorbed_by_sibling(monkeypatch):
    """The pre-banner fuzzy join (addr still unknown) must not let a
    SIBLING's registry join absorb a different pending spawn: a dead
    pending is a spawn FAILURE even when a new member appeared (else a
    replica crash-looping next to a healthy neighbour never trips the
    detector), and one new member can satisfy at most ONE pending."""
    router, launcher, scaler = _fleet()

    class H:
        addr, pid = None, 0

        def __init__(self, alive):
            self._alive = alive

        def alive(self):
            return self._alive

        def kill(self):
            pass

    try:
        now = time.monotonic()
        # a corpse and a live boot, one sibling join: the corpse fails
        scaler._pending = [(H(False), now, set()), (H(True), now,
                                                    set())]
        scaler._check_pending(now, live={"127.0.0.1:9"})
        assert scaler.status()["pending_spawns"] == 0
        assert any("exited before first serving" in e
                   for e in scaler.events), scaler.events
        assert any("scale-out complete" in e for e in scaler.events)
        # two live boots, ONE new member: only one may claim it
        scaler.events.clear()
        scaler._pending = [(H(True), now, set()), (H(True), now,
                                                   set())]
        scaler._check_pending(now, live={"127.0.0.1:10"})
        assert scaler.status()["pending_spawns"] == 1, scaler.events
        assert sum("scale-out complete" in e
                   for e in scaler.events) == 1
        # a member claimed by a sibling's BANNER address is never up
        # for a fuzzy grab, regardless of processing order (the
        # pre-banner pending here is processed FIRST)
        scaler.events.clear()
        a = H(True)
        a.addr = "127.0.0.1:11"
        scaler._pending = [(H(True), now, set()), (a, now, set())]
        scaler._check_pending(now, live={"127.0.0.1:11"})
        assert scaler.status()["pending_spawns"] == 1, scaler.events
        assert any("127.0.0.1:11 live" in e for e in scaler.events)
    finally:
        scaler._pending = []
        with scaler._lock:
            scaler._unplaced = []
        _teardown(router, launcher, scaler)


def test_crash_loop_detector_backs_off_and_alerts():
    router, launcher, scaler = _fleet(launcher_cls=DyingLauncher,
                                      crash_loop_limit=3,
                                      crash_backoff_s=30.0)
    try:
        now = 100.0
        for i in range(3):
            assert scaler._spawn(now + i, reason="test")
            scaler._check_pending(now + i + 0.01)
        st = scaler.status()
        assert st["crash_streak"] == 3
        assert st["crashloops"] == 1      # the alert counter fired
        assert scaler._backoff_until > now + 2
        assert any("CRASH LOOP" in e for e in scaler.events)
        # poll during backoff does NOT spawn (DyingLauncher would
        # happily hand out more corpses)
        spawned_before = len(scaler.events)
        assert scaler.poll(now=scaler._backoff_until - 1.0) == 0
        assert len(scaler.events) == spawned_before
        # a further failure past the limit doubles the backoff
        scaler._spawn_failed(now + 10, "again")
        assert st["crashloops"] + 1 == scaler.status()["crashloops"]
    finally:
        _teardown(router, launcher, scaler)


def test_chaos_sites_abort_cleanly():
    """autoscaler.spawn / autoscaler.drain through the FaultInjector:
    an injected error is a counted, clean abort — never a half-spawned
    or half-drained fleet, never a dead control loop."""
    router, launcher, scaler = _fleet()
    try:
        scaler.ensure_min(timeout_s=60)
        fault_injector().inject("autoscaler.spawn", "error", nth=1)
        assert not scaler._spawn(time.monotonic(), reason="chaos")
        assert scaler.status()["crash_streak"] == 1
        assert len(router.live_replicas()) == 1

        h2 = launcher.spawn()             # a second replica to retire
        deadline = time.monotonic() + 30
        while (len(router.live_replicas()) < 2
               and time.monotonic() < deadline):
            time.sleep(0.05)
        fault_injector().inject("autoscaler.drain", "error", nth=1)
        assert not scaler._scale_in(time.monotonic(),
                                    router.live_replicas())
        assert len(router.live_replicas()) == 2   # nothing retired
        assert router.stats()["draining"] == []
        for addr in router.live_replicas():
            assert not replica_call(addr, {"op": "ping"})["draining"]
    finally:
        _teardown(router, launcher, scaler)


# ---------------------------------------------------------------------------
# replica drain verb + retryable admission during drain
# ---------------------------------------------------------------------------


def test_replica_drain_verb_resume_and_retryable_reject():
    dec, states = _decoder()
    server = GenerationServer(dec, states, slots=2, kv_blocks=16,
                              place=fluid.CPUPlace())
    rep = ReplicaServer(server)
    try:
        want = server.generate([1, 2, 3], 6, timeout=60)
        ans = replica_call(rep.addr, {"op": "drain", "timeout": 30})
        assert ans["ok"] and ans["drained"]
        assert replica_call(rep.addr, {"op": "ping"})["draining"]
        # a generate against a draining replica is a RETRYABLE error
        # (the router's cue to resubmit on a survivor), never fatal
        with pytest.raises(ReplicaError) as ei:
            list(replica_stream(rep.addr,
                                {"op": "generate",
                                 "prompt": [1, 2, 3], "max_new": 4}))
        assert not ei.value.fatal
        assert replica_call(rep.addr, {"op": "resume"})["ok"]
        assert not replica_call(rep.addr, {"op": "ping"})["draining"]
        got = list(replica_stream(rep.addr,
                                  {"op": "generate",
                                   "prompt": [1, 2, 3], "max_new": 6}))
        assert got == want
    finally:
        rep.close()
        server.close()


def test_drain_completes_accepted_requests_first():
    """drain() is not a kill: requests already accepted (active AND
    queued) run to completion; only new admission is refused."""
    dec, states = _decoder()
    server = GenerationServer(dec, states, slots=1, kv_blocks=16,
                              place=fluid.CPUPlace())
    try:
        want = server.generate([1, 2, 3], 8, timeout=60)
        # one active + one queued (slots=1), then drain
        s1 = server.submit([1, 2, 3], 8)
        s2 = server.submit([1, 2, 3], 8)
        assert server.drain(wait=True, timeout=60)
        assert s1.result(timeout=5) == want
        assert s2.result(timeout=5) == want
        with pytest.raises(RuntimeError):
            server.submit([1, 2, 3], 4)
        server.resume()
        assert server.generate([1, 2, 3], 8, timeout=60) == want
    finally:
        server.close()


# ---------------------------------------------------------------------------
# warm start: replicas on a host share the one compile cache
# ---------------------------------------------------------------------------


def test_second_server_on_a_warm_cache_compiles_nothing(tmp_path,
                                                        compile_cache):
    """The first server on a host compiles and fills the one compile
    cache; the next one started from the same model dir DESERIALIZES
    every executable (cache_misses == 0) and never compiles after
    warmup (recompiles_after_warmup == 0): its time-to-first-token is
    bounded by model load.  `warm_start` is what warmup OBSERVED, not
    a setting."""
    # a DISTINCT geometry from the shared module decoder, so the
    # executables cannot come from jax's in-memory jit cache — every
    # hit below is a real persistent-cache deserialization
    fw.reset_unique_names()
    startup, dec = build_lm_paged_decoder(V, 4, 6, d_model=24,
                                          n_heads=2, n_layers=1)
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    states = {n: np.asarray(scope.find_var(n))
              for n in dec.state_names}
    d = str(tmp_path / "model")
    save_generation_model(
        d, states,
        {"vocab_size": V, "d_model": 24, "n_heads": 2, "n_layers": 1,
         "block_size": 4, "max_blocks_per_seq": 6, "slots": 2,
         "kv_blocks": 12})
    assert os.listdir(d) and "xla_cache" not in os.listdir(d)

    cold = server_from_model_dir(d, place=fluid.CPUPlace())
    try:
        cs = cold.warmup_stats
        assert cs["compiles"] >= 1 and cs["cache_misses"] >= 1, cs
        assert cold.stats()["warm_start"] is False
    finally:
        cold.close()
    assert os.listdir(compile_cache), "the first server wrote no entries"

    warm = server_from_model_dir(d, place=fluid.CPUPlace())
    try:
        ws = warm.warmup_stats
        assert ws["cache_misses"] == 0, ws     # nothing compiled...
        assert ws["cache_hits"] >= 1, ws       # ...all deserialized
        out = warm.generate([1, 2, 3], 6, timeout=60)
        assert len(out) == 6
        st = warm.stats()
        assert st["recompiles_after_warmup"] == 0, st
        assert st["warm_start"] is True
    finally:
        warm.close()


# ---------------------------------------------------------------------------
# chaos acceptance: REAL `cli serve` fleet, ramp + SIGKILL (slow tier)
# ---------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.slow
def test_autoscale_ramp_acceptance_sigkill_zero_failed():
    """ROADMAP-4 acceptance: open-loop ramp against a live fleet of
    `cli serve` subprocess replicas triggers scale-out then scale-in;
    one replica is SIGKILLed at the peak; ZERO requests fail (the
    router resume contract holds through spawn, drain and the kill);
    the scale-out replica is warm-started (no XLA compile)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        from mini_fleet import make_requests, ramp_rates, run_ramp
    finally:
        sys.path.pop(0)
    import shutil
    import tempfile

    from paddle_tpu.cloud.autoscaler import SubprocessReplicaLauncher

    workdir = tempfile.mkdtemp(prefix="paddle_as_accept_")
    dec, states = _decoder(max_blocks=8)
    model_dir = os.path.join(workdir, "model")
    save_generation_model(
        model_dir, states,
        {"vocab_size": V, "d_model": 16, "n_heads": 2, "n_layers": 1,
         "block_size": 4, "max_blocks_per_seq": 8, "slots": 2,
         "kv_blocks": 24})

    router = ReplicaRouter(desired=8, refresh_s=0.1)
    policy = AutoscalerPolicy(1, 3, p99_high_s=30.0, backlog_high=64,
                              backlog_low=6, sustain_s=0.8,
                              idle_sustain_s=3.0, cooldown_s=3.0)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PADDLE_TPU_DATASET="synthetic",
               # the host's one compile cache, placed from outside, with
               # JAX's write thresholds at zero so this tiny model's
               # executables are persisted: the floor replica fills it,
               # every scale-out replica must deserialize from it
               JAX_COMPILATION_CACHE_DIR=os.path.join(workdir,
                                                      "jax_cache"),
               JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="0",
               JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES="-1",
               # per-tick delay = a slow accelerator: the tiny CPU
               # model overloads deterministically (docs/serving.md)
               PADDLE_TPU_FAULTS="serving.decode:delay:1:1000000000:"
               "0.02")
    launcher = SubprocessReplicaLauncher(
        model_dir, router.registry_addr, use_tpu=0, ttl_s=1.5,
        drain_grace_s=30.0, env=env)
    scaler = Autoscaler(router, launcher, policy, poll_s=0.2,
                        window_s=8.0, spawn_timeout_s=300.0,
                        drain_grace_s=30.0)
    sizes = []
    killed = {"pid": None}
    peak_stats = {}

    def on_phase(phase, rate):
        sizes.append(len(router.live_replicas(include_draining=False)))
        if phase == 2 and killed["pid"] is None:
            owned = scaler.owned_pids()
            if len(owned) >= 2:
                for a in owned:
                    peak_stats[a] = replica_call(
                        a, {"op": "stats"}, timeout_s=10)["stats"]
                addr, pid = sorted(owned.items())[-1]
                killed["pid"] = pid
                os.kill(pid, signal.SIGKILL)

    try:
        scaler.ensure_min(timeout_s=300)
        scaler.start()
        reqs = make_requests(64, 32, np.random.RandomState(0))
        ramp = run_ramp(router.submit, reqs, ramp_rates(20.0), 6.0,
                        on_phase=on_phase)
        deadline = time.monotonic() + 60
        while (len(router.live_replicas(include_draining=False)) > 1
               and time.monotonic() < deadline):
            time.sleep(0.2)
        final = router.live_replicas(include_draining=False)

        assert ramp["failed"] == 0, (ramp, scaler.events)
        assert max(sizes) >= 2, (sizes, scaler.events)
        assert killed["pid"] is not None, scaler.events
        assert len(final) == 1, (final, scaler.events)
        assert scaler.status()["crashloops"] == 0
        st = replica_call(final[0], {"op": "stats"},
                          timeout_s=10)["stats"]
        assert st["recompiles_after_warmup"] == 0, st
        # the floor replica compiled; whoever scaled out beside it
        # found the cache warm and compiled nothing
        warm = [s for s in peak_stats.values() if s["warm_start"]]
        assert warm and all(s["cache_misses"] == 0 for s in warm), \
            peak_stats
    finally:
        scaler.close(retire_owned=True)
        router.close()
        shutil.rmtree(workdir, ignore_errors=True)
