"""Time attribution: phase breakdowns, why-tables, stragglers, drift.

PR 13's telemetry plane can say THAT a latency objective regressed;
this layer says WHERE the time went and WHO is slow:

  * **Phase instrumentation** — :func:`phase(kind, name)` wraps one
    phase of a serving tick (admit / build / prefill / decode /
    draft_verify / sample / deliver / kv_alloc / kv_release), a
    training iteration (feed_pack / h2d / compute / send_round /
    barrier_wait / get) or a pserver round (optimize / recv /
    barrier) in a labeled child span PLUS an observation into the
    per-kind
    ``paddle_tpu_<kind>_phase_seconds{phase=...}`` histogram family.
    Cost: one no-op context manager when both metrics and tracing are
    off; two perf_counter reads + a cached-child observe when on.
  * **Why-table** — :func:`why_rows` (live TimeSeriesStore) /
    :func:`why_rows_from_parsed` (a federated Prometheus dump) compute
    the fleet "where does the time go" table behind ``cli why``: per
    (kind, member, phase) seconds-of-phase-per-second and its share of
    the member's attributed time.
  * **Straggler detection** — :func:`straggler_scores` z-scores each
    endpoint's windowed mean of
    ``paddle_tpu_comm_endpoint_round_seconds`` against its PEERS
    (leave-one-out, sigma floored at 10% of the peer mean so two
    healthy endpoints never read as mutual stragglers), published by
    the collector as the SLO-able ``paddle_tpu_comm_straggler_score``
    gauge and surfaced in ``cli top``.
  * **Calibration drift** — member processes publish the PR 11 static
    roofline floor per phase (``*_phase_static_seconds`` gauges via
    :func:`publish_static_floor`); :func:`calibration_ratios` bands
    measured phase time against it and the collector republishes
    ``paddle_tpu_calibration_ratio{kind,member,phase}`` for burn-rate
    alerting (tools/slo.json pins the static_vs_measured band).

  * **Iteration clock** — :class:`IterationClock` times every
    iteration of a loop (the serving scheduler's tick, an executor's
    step) with tracing on or off, and keeps the slow ones as records
    that say whether the thread worked, waited or was taken off the
    CPU.

The collector calls :func:`run_detectors` after every scrape pass.
See docs/observability.md "Time attribution".
"""
from __future__ import annotations

import gc
import math
import os
import resource
import threading
import time
from typing import Dict, List, Optional, Sequence, Tuple

from . import metrics as metrics_mod
from . import tracing

__all__ = [
    "KINDS",
    "PHASES",
    "PHASE_BUCKETS",
    "phase",
    "phased_iter",
    "IterationClock",
    "observe_phase",
    "phase_family",
    "publish_static_floor",
    "why_rows",
    "why_rows_from_parsed",
    "format_why_table",
    "straggler_scores",
    "calibration_ratios",
    "run_detectors",
    "pick_exemplar",
]

# the attributed member kinds and their canonical phase vocabularies —
# docs/observability.md "Time attribution" mirrors these tables; adding
# a phase needs only a new phase() call site, the label carries it
KINDS = ("generation", "trainer", "pserver")

PHASES: Dict[str, Tuple[str, ...]] = {
    "generation": ("admit", "build", "prefill", "decode",
                   "draft_verify", "sample", "deliver", "kv_alloc",
                   "kv_release"),
    "trainer": ("reader", "feed_pack", "h2d", "compute", "send_round",
                "barrier_wait", "get"),
    "pserver": ("optimize", "recv", "barrier"),
}

# phases run from tens of µs (KV alloc) to seconds (a cold compile in
# the compute phase): a wider, finer ladder than the request-latency
# default (50 µs .. ~26 s doubling)
PHASE_BUCKETS: Tuple[float, ...] = tuple(
    0.00005 * 2 ** i for i in range(20))


def phase_family(kind: str) -> metrics_mod.Histogram:
    return metrics_mod.histogram(
        f"paddle_tpu_{kind}_phase_seconds",
        f"seconds spent per {kind} phase",
        labelnames=("phase",), buckets=PHASE_BUCKETS)


def _static_family(kind: str) -> metrics_mod.Gauge:
    return metrics_mod.gauge(
        f"paddle_tpu_{kind}_phase_static_seconds",
        "static roofline floor (seconds) for the phase",
        labelnames=("phase",))


# child cache keyed on family identity: registry().clear() in tests
# mints a new family, and observing into an orphaned child would make
# phase data silently vanish for the rest of the process
_children: Dict[Tuple[str, str], Tuple[object, object]] = {}


def observe_phase(kind: str, name: str, seconds: float) -> None:
    """Record one phase duration into the kind's histogram family (a
    no-op when metrics are disabled)."""
    if not metrics_mod.enabled():
        return
    key = (kind, name)
    fam = phase_family(kind)
    hit = _children.get(key)
    if hit is None or hit[0] is not fam:
        hit = (fam, fam.labels(phase=name))
        _children[key] = hit
    hit[1].observe(seconds)


class _NoopCtx:
    __slots__ = ()

    def __enter__(self):
        return None

    def __exit__(self, *exc):
        return False


_NOOP = _NoopCtx()


class _PhaseCtx:
    __slots__ = ("_kind", "_name", "_span_cm", "_span", "_t0")

    def __init__(self, kind: str, name: str):
        self._kind = kind
        self._name = name

    def __enter__(self):
        self._span_cm = tracing.span(f"{self._kind}.phase.{self._name}")
        self._span = self._span_cm.__enter__()
        self._t0 = time.perf_counter()
        return self._span

    def __exit__(self, exc_type, exc, tb):
        dt = time.perf_counter() - self._t0
        if exc_type is not None and self._span is not None:
            # an error attr makes the tail sampler keep the trace
            self._span.set_attr("error", exc_type.__name__)
        self._span_cm.__exit__(exc_type, exc, tb)
        observe_phase(self._kind, self._name, dt)
        return False


def phase(kind: str, name: str):
    """Context manager attributing the block to (kind, phase): a child
    span named ``<kind>.phase.<name>`` under the active trace plus an
    observation into ``paddle_tpu_<kind>_phase_seconds``.  One boolean
    test and a shared no-op when the whole observability stack is off —
    safe on per-tick hot paths."""
    if not (metrics_mod.enabled() or tracing.enabled()
            or tracing._listeners):
        return _NOOP
    return _PhaseCtx(kind, name)


_END = object()


def phased_iter(kind: str, name: str, iterable):
    """Yield `iterable`'s items, attributing the time inside each pull
    (`next`) to (kind, phase) like :func:`phase`: one
    ``<kind>.phase.<name>`` span a delivered item, a child of the
    caller's active span, plus the histogram observation.  The range is
    held by this generator, so it is recorded after the fact
    (`tracing.record_span`) and never sits on the thread's span stack;
    the last, empty pull is not recorded.  With the stack off a pull
    costs the same boolean tests as `phase`."""
    it = iter(iterable)
    while True:
        if not (metrics_mod.enabled() or tracing.enabled()
                or tracing._listeners):
            item = next(it, _END)
        else:
            ts, t0 = time.time(), time.perf_counter()
            c0 = time.thread_time()
            item = next(it, _END)
            if item is not _END:
                cpu = time.thread_time() - c0
                dt = time.perf_counter() - t0
                tracing.record_span(f"{kind}.phase.{name}", ts, dt,
                                    parent=tracing.current_context(),
                                    cpu=cpu)
                observe_phase(kind, name, dt)
        if item is _END:
            return
        yield item


# ---------------------------------------------------------------------------
# the iteration clock: where a loop's iteration went, tracing on or off
# ---------------------------------------------------------------------------


def _cgroup_cpu_stat() -> Optional[str]:
    """`cpu.stat` of the CPU group this process is in, as
    `/proc/self/cgroup` names it: under a `cpu` controller of the first
    version where one is mounted, else in the unified hierarchy.  None
    where no such file is."""
    try:
        with open("/proc/self/cgroup") as f:
            lines = [ln.strip().split(":", 2) for ln in f]
    except OSError:
        return None
    found = []
    for _, controllers, path in (ln for ln in lines if len(ln) == 3):
        if "cpu" in controllers.split(","):
            found.insert(0, os.path.join(
                "/sys/fs/cgroup", controllers, path.lstrip("/")))
        elif not controllers:
            found += [os.path.join(root, path.lstrip("/"))
                      for root in ("/sys/fs/cgroup",
                                   "/sys/fs/cgroup/unified")]
    for d in found:
        if os.path.isfile(os.path.join(d, "cpu.stat")):
            return os.path.join(d, "cpu.stat")
    return None


def _read_words(path: Optional[str]) -> List[List[str]]:
    if not path:
        return []
    try:
        with open(path) as f:
            return [ln.split() for ln in f]
    except OSError:
        return []


class IterationClock:
    """Where an iteration of a loop went, kept whether tracing is on
    or off.  An iteration runs from one `end()` to the next.  Its host
    `parts` are named by the caller, in the order they run; the loop
    calls `mark(part)` as each ends, and what lies between the last
    part's mark and `end()` is the `wait` (the blocking read of the
    device).  A part not marked in an iteration took no time.  `start`
    is None while the loop is not iterating; `begin()` then opens an
    iteration that no `end()` precedes.

    The reference period is the median of the last 64 periods,
    refreshed every 64 iterations (one sort of 64 floats); an
    iteration longer than `slow_factor` times it comes back from `end`
    as a record, and `slow` keeps the newest `KEEP` of them.  Until 64
    iterations have run there is no reference and nothing is slow.

    Every `end()` also reads, for the calling thread, its CPU seconds,
    context switches and page faults (one `getrusage(RUSAGE_THREAD)`),
    and the last part's `mark` reads the thread's CPU seconds once
    more.  The process's CPU seconds and the count of full collections
    are read at an `end()` `PROCESS_EVERY_S` or more after their last
    reading and at every slow one (where a CPU clock is a system call
    of 6 us, as on the v5e's host, three an iteration are too many for
    a tick of 4 ms).  A slow record carries the differences over the
    iteration, the process's over `counted_ms` (what each says:
    docs/observability.md "How a slow record reads"), and the
    cumulative throttled time of the process's CPU group and the
    machine's CPU pressure, read only then.  The thread's counters
    belong to it: an iteration that ends on another thread than the
    one it began on restarts the clock and gives no record."""

    __slots__ = ("parts", "wait", "slow_factor", "start", "reference",
                 "slow", "_marks", "_cpu_mark", "_prev", "_process",
                 "_periods", "_n", "_cpu_stat", "_pressure")

    KEEP = 8
    PROCESS_EVERY_S = 0.032
    _WINDOW = 64

    def __init__(self, parts: Sequence[str], wait: str = "wait",
                 slow_factor: float = 4.0,
                 cpu_stat_path: Optional[str] = None,
                 pressure_path: str = "/proc/pressure/cpu"):
        self.parts = tuple(parts)
        self.wait = wait
        self.slow_factor = float(slow_factor)
        self.start: Optional[float] = None
        self.reference: Optional[float] = None
        self.slow: List[dict] = []
        self._marks: Dict[str, float] = {}
        self._cpu_mark = 0.0
        self._prev: Optional[tuple] = None
        self._process: Optional[tuple] = None
        self._periods = [0.0] * self._WINDOW
        self._n = 0
        self._cpu_stat = cpu_stat_path or _cgroup_cpu_stat()
        self._pressure = pressure_path

    @staticmethod
    def _read_thread() -> tuple:
        ru = resource.getrusage(resource.RUSAGE_THREAD)
        return (threading.get_ident(), ru.ru_utime + ru.ru_stime,
                ru.ru_nvcsw, ru.ru_nivcsw, ru.ru_minflt, ru.ru_majflt)

    @staticmethod
    def _read_process(now: float) -> tuple:
        return (now, time.process_time(),
                gc.get_stats()[2]["collections"])

    def begin(self) -> None:
        """Open an iteration with no `end()` before it."""
        self.start = time.perf_counter()
        self._prev = self._read_thread()
        self._process = self._read_process(self.start)

    def mark(self, part: str) -> None:
        """`part` has ended."""
        self._marks[part] = time.perf_counter()
        if part == self.parts[-1]:
            self._cpu_mark = time.thread_time()

    def end(self, **extra) -> Optional[dict]:
        """Close the iteration; its record, with the caller's `extra`
        keys, if it was slow."""
        now = time.perf_counter()
        cur = self._read_thread()
        start, self.start = self.start, now
        prev, self._prev = self._prev, cur
        process = self._process
        slow = False
        if start is not None and prev is not None and prev[0] == cur[0]:
            period = now - start
            self._periods[self._n % self._WINDOW] = period
            self._n += 1
            if self._n % self._WINDOW == 0:
                self.reference = sorted(self._periods)[self._WINDOW // 2]
            ref = self.reference
            slow = ref is not None and period > self.slow_factor * ref
        if (slow or process is None
                or now - process[0] >= self.PROCESS_EVERY_S):
            self._process = self._read_process(now)
        if not slow:
            return None
        # (an iteration that began had the process read, by `begin()`
        # or by the `end()` before it)
        rec = self._record(now, start, period, ref, prev, cur, process,
                           self._process)
        rec.update(extra)
        self.slow = (self.slow + [rec])[-self.KEEP:]
        return rec

    def _record(self, now, start, period, ref, prev, cur, process0,
                process1) -> dict:
        spent, at = [], start
        for part in self.parts:
            # a mark from before this iteration: the part did not run
            mark = max(self._marks.get(part, at), at)
            spent.append((part, mark - at))
            at = mark
        wait = now - at
        phase, longest = max(spent + [(self.wait, wait)],
                             key=lambda p: p[1])
        _, cpu0, vol0, invol0, minor0, major0 = prev
        _, cpu1, vol1, invol1, minor1, major1 = cur
        # the CPU clock at the last part's mark, between the two
        # readings of `getrusage` (which drops each's last microsecond);
        # a mark this iteration did not make counts as no host work
        cpu_mark = (min(max(self._cpu_mark, cpu0), cpu1)
                    if self.parts
                    and self._marks.get(self.parts[-1], 0.0) >= start
                    else cpu0)
        rec = {"at": time.time(), "ms": 1e3 * period,
               "wait_ms": 1e3 * wait, "phase": phase,
               "phase_ms": 1e3 * longest, "reference_ms": 1e3 * ref,
               "cpu_ms": 1e3 * (cpu_mark - cpu0),
               "wait_cpu_ms": 1e3 * (cpu1 - cpu_mark),
               "offcpu_ms": 1e3 * max(
                   period - wait - (cpu_mark - cpu0), 0.0),
               "counted_ms": 1e3 * (now - process0[0]),
               "process_cpu_ms": 1e3 * (process1[1] - process0[1]),
               "gen2_collections": process1[2] - process0[2],
               "vol_switches": vol1 - vol0,
               "invol_switches": invol1 - invol0,
               "minor_faults": minor1 - minor0,
               "major_faults": major1 - major0}
        rec.update(self._machine())
        return rec

    def _machine(self) -> dict:
        """What the machine has done to the process so far: the
        cumulative throttled time and count of its CPU group and the
        time some task of the machine has waited for a CPU.  Two slow
        records in a row give what came between them.  A key is left
        out where its file is not there."""
        out = {}
        stat = {w[0]: int(w[1]) for w in _read_words(self._cpu_stat)
                if len(w) == 2}
        if "throttled_usec" in stat:
            out["throttled_ms"] = stat["throttled_usec"] / 1e3
        elif "throttled_time" in stat:        # nanoseconds
            out["throttled_ms"] = stat["throttled_time"] / 1e6
        if "nr_throttled" in stat:
            out["throttled_count"] = stat["nr_throttled"]
        for words in _read_words(self._pressure):
            if words[:1] == ["some"]:
                total = [w[6:] for w in words if w.startswith("total=")]
                if total:
                    out["cpu_pressure_ms"] = int(total[0]) / 1e3
        return out


def publish_static_floor(kind: str,
                         floors: Dict[str, float]) -> None:
    """Export the static roofline floor (seconds) per phase as
    ``paddle_tpu_<kind>_phase_static_seconds{phase=...}`` gauges —
    the calibration detector's denominator.  No-op when metrics are
    off or a floor is non-positive (no model, no band)."""
    if not metrics_mod.enabled():
        return
    fam = _static_family(kind)
    for p, v in floors.items():
        if v and v > 0:
            fam.labels(phase=p).set(float(v))


# ---------------------------------------------------------------------------
# the why-table ("where does the time go")
# ---------------------------------------------------------------------------


def _with_shares(rows: List[dict], seconds_key: str) -> List[dict]:
    totals: Dict[Tuple[str, str], float] = {}
    for r in rows:
        k = (r["kind"], r["member"])
        totals[k] = totals.get(k, 0.0) + max(r[seconds_key], 0.0)
    for r in rows:
        t = totals[(r["kind"], r["member"])]
        r["share"] = (max(r[seconds_key], 0.0) / t) if t > 0 else 0.0
    rows.sort(key=lambda r: (r["kind"], r["member"], -r["share"]))
    return rows


def why_rows(series, kind: Optional[str] = None,
             window_s: float = 60.0,
             now: Optional[float] = None) -> List[dict]:
    """Per (kind, member, phase) attribution over a live fleet
    TimeSeriesStore: ``seconds_per_s`` (windowed rate of the phase
    histogram's _sum — seconds of phase time per wall second),
    ``mean_s``, ``calls_per_s`` and the phase's ``share`` of the
    member's total attributed time."""
    rows: List[dict] = []
    for k in (KINDS if kind is None else (kind,)):
        name = f"paddle_tpu_{k}_phase_seconds"
        members = series.label_values(name, "member") or [""]
        for m in members:
            base = {"member": m} if m else {}
            for p in series.label_values(name, "phase",
                                         base or None):
                lbl = {**base, "phase": p}
                sr = series.sum_rate(name, window_s, lbl, now)
                if sr is None:
                    continue
                mean = series.mean(name, window_s, lbl, now)
                rate = series.rate(name, window_s, lbl, now)
                rows.append({
                    "kind": k, "member": m or "-", "phase": p,
                    "seconds_per_s": sr,
                    "mean_s": mean if mean == mean else 0.0,
                    "calls_per_s": rate or 0.0,
                })
    return _with_shares(rows, "seconds_per_s")


def why_rows_from_parsed(parsed: Dict[str, dict],
                         kind: Optional[str] = None) -> List[dict]:
    """The why-table from a PARSED Prometheus dump (a federated file or
    one process's exit dump) — lifetime totals instead of windowed
    rates, so it works on a single snapshot with no history."""
    rows: List[dict] = []
    for k in (KINDS if kind is None else (kind,)):
        fam = parsed.get(f"paddle_tpu_{k}_phase_seconds")
        if not fam or fam.get("type") != "histogram":
            continue
        for s in fam["samples"]:
            v = s["value"]
            rows.append({
                "kind": k,
                "member": s["labels"].get("member", "-"),
                "phase": s["labels"].get("phase", "?"),
                "seconds": v["sum"],
                "count": v["count"],
                "mean_s": (v["sum"] / v["count"]) if v["count"] else 0.0,
            })
    return _with_shares(rows, "seconds")


def format_why_table(rows: List[dict]) -> str:
    """Render why-rows as the ``cli why`` table."""
    if not rows:
        return ("no phase data — run with PADDLE_TPU_METRICS=on and "
                "phase instrumentation armed")
    live = "seconds_per_s" in rows[0]
    head = ["kind", "member", "phase", "share",
            "sec/s" if live else "seconds",
            "mean", "calls/s" if live else "count"]
    table: List[List[str]] = [head]
    for r in rows:
        table.append([
            r["kind"], r["member"], r["phase"],
            f"{r['share'] * 100:5.1f}%",
            (f"{r['seconds_per_s']:.4f}" if live
             else f"{r['seconds']:.4f}"),
            f"{r['mean_s'] * 1000:.3f}ms",
            (f"{r['calls_per_s']:.1f}" if live
             else str(r["count"])),
        ])
    widths = [max(len(row[i]) for row in table)
              for i in range(len(head))]
    out = []
    for i, row in enumerate(table):
        out.append("  ".join(c.ljust(w)
                             for c, w in zip(row, widths)).rstrip())
        if i == 0:
            out.append("  ".join("-" * w for w in widths))
    return "\n".join(out)


# ---------------------------------------------------------------------------
# straggler detection (comm endpoints)
# ---------------------------------------------------------------------------

ENDPOINT_ROUND_METRIC = "paddle_tpu_comm_endpoint_round_seconds"
STRAGGLER_METRIC = "paddle_tpu_comm_straggler_score"
CALIBRATION_METRIC = "paddle_tpu_calibration_ratio"


def straggler_scores(series, name: str = ENDPOINT_ROUND_METRIC,
                     window_s: float = 60.0,
                     now: Optional[float] = None) -> Dict[str, float]:
    """Per-endpoint straggler z-score: how many (floored) standard
    deviations an endpoint's windowed mean round time sits ABOVE its
    peers' (leave-one-out).  Sigma is floored at 10% of the peer mean —
    near-identical healthy peers must not amplify µs jitter into a
    flag.  Negative drift (faster than peers) clamps to 0: only slow
    is a straggler."""
    means: Dict[str, float] = {}
    for ep in series.label_values(name, "endpoint"):
        m = series.mean(name, window_s, {"endpoint": ep}, now)
        if m == m:  # not NaN
            means[ep] = m
    if len(means) < 2:
        return {}
    out: Dict[str, float] = {}
    for ep, v in means.items():
        peers = [x for e, x in means.items() if e != ep]
        mu = sum(peers) / len(peers)
        var = sum((x - mu) ** 2 for x in peers) / len(peers)
        sigma = max(math.sqrt(var), 0.1 * abs(mu), 1e-9)
        out[ep] = max(0.0, (v - mu) / sigma)
    return out


# ---------------------------------------------------------------------------
# calibration drift (static roofline vs measured)
# ---------------------------------------------------------------------------


def calibration_ratios(series, window_s: float = 120.0,
                       now: Optional[float] = None) -> List[dict]:
    """measured/static per (kind, member, phase): the windowed mean of
    the phase histogram over the member's published static roofline
    floor.  >1 means production is slower than the model predicts
    (expected — the floor ignores overheads); a drifting ratio is the
    alert signal, banded by tools/slo.json."""
    out: List[dict] = []
    for k in KINDS:
        sname = f"paddle_tpu_{k}_phase_static_seconds"
        mname = f"paddle_tpu_{k}_phase_seconds"
        members = series.label_values(sname, "member") or [""]
        for m in members:
            base = {"member": m} if m else {}
            for p in series.label_values(sname, "phase",
                                         base or None):
                static = series.latest(sname, {**base, "phase": p})
                if not static or static <= 0:
                    continue
                measured = series.mean(mname, window_s,
                                       {**base, "phase": p}, now)
                if measured != measured:  # NaN: no observations yet
                    continue
                out.append({"kind": k, "member": m or "-",
                            "phase": p, "static_s": static,
                            "measured_s": measured,
                            "ratio": measured / static})
    return out


def run_detectors(series, window_s: float = 60.0,
                  now: Optional[float] = None) -> Dict[str, dict]:
    """One detector pass over a fleet TimeSeriesStore -> synthetic
    gauge families in the parsed-snapshot shape the collector merges
    into its federation output."""
    synth: Dict[str, dict] = {}
    scores = straggler_scores(series, window_s=window_s, now=now)
    if scores:
        synth[STRAGGLER_METRIC] = {
            "type": "gauge",
            "help": ("z-score of an endpoint's mean round time vs its "
                     "peers (leave-one-out, sigma floored)"),
            "samples": [{"labels": {"endpoint": ep}, "value": v}
                        for ep, v in sorted(scores.items())]}
    ratios = calibration_ratios(series,
                                window_s=max(window_s, 120.0), now=now)
    if ratios:
        synth[CALIBRATION_METRIC] = {
            "type": "gauge",
            "help": ("measured phase seconds / static roofline floor "
                     "(static_vs_measured band)"),
            "samples": [{"labels": {"kind": r["kind"],
                                    "member": r["member"],
                                    "phase": r["phase"]},
                         "value": r["ratio"]} for r in ratios]}
    return synth


# ---------------------------------------------------------------------------
# exemplar -> trace resolution (the `cli trace-of` core)
# ---------------------------------------------------------------------------


def pick_exemplar(parsed: Dict[str, dict], metric: str,
                  q: float = 0.99) -> Optional[dict]:
    """From a parsed (federated) dump, pick the exemplar that best
    represents the metric's q-quantile: pool the family's buckets,
    compute the lifetime quantile, and return the freshest exemplar at
    or above it (falling back to the largest-valued one).  Returns
    ``{"trace_id", "value", "ts", "labels", "quantile_s"}`` or None
    when the family has no exemplars."""
    from .metrics import quantile_from_buckets
    from .timeseries import cum_to_per_bucket

    fam = parsed.get(metric)
    if not fam or fam.get("type") != "histogram":
        return None
    les: Optional[List[float]] = None
    agg: Optional[List[float]] = None
    total = 0
    exs: List[Tuple[dict, dict]] = []  # (sample labels, exemplar)
    for s in fam["samples"]:
        v = s["value"]
        for ex in (v.get("exemplars") or {}).values():
            if ex.get("labels", {}).get("trace_id"):
                exs.append((s["labels"], ex))
        ls, counts = cum_to_per_bucket(v["buckets"])
        if not ls:
            continue
        if les is None:
            les, agg = ls, [0.0] * len(counts)
        elif ls != les or len(counts) != len(agg):
            continue  # mismatched member layout: skip from the pool
        for i, c in enumerate(counts):
            agg[i] += c
        total += v["count"]
    if not exs:
        return None
    thr = (quantile_from_buckets(les, agg, total, q)
           if les and total else 0.0)
    qualifying = [(lbl, ex) for lbl, ex in exs
                  if ex.get("value", 0.0) >= thr]
    if qualifying:
        lbl, ex = max(qualifying,
                      key=lambda t: t[1].get("ts") or 0.0)
    else:  # quantile fell between exemplared buckets: take the worst
        lbl, ex = max(exs, key=lambda t: t[1].get("value", 0.0))
    return {"trace_id": ex["labels"]["trace_id"],
            "value": ex.get("value"), "ts": ex.get("ts"),
            "labels": dict(lbl),
            "quantile_s": thr if thr == thr else None}
