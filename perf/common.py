"""What every job needs: the cell's files, the device gate, the clock,
the profiler window and the span tap.  Jobs (`perf/jobs/<kind>.py`)
get one `Cell` and return one `Run`; per-layer metrics
(`perf/metrics/<name>.py`) read the `Run`.
"""
from __future__ import annotations

import glob
import importlib.util
import json
import os
import shutil
import sys
import threading
import time
from typing import Any, Dict, List, Optional

import reduce_trace

PERF_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(PERF_DIR)
WINDOW_MARKER = "perf.trace_window"
CLOCK_SYNC = "perf.clock_sync"


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: str):
    """Import one file of the benchmark by path: configurations, jobs,
    metrics and references are found by the names in BENCHMARK.json and
    in the traffic and configuration files, never by an import list."""
    name = "perf_" + os.path.relpath(path, PERF_DIR).replace(
        os.sep, "_").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def seed31(seed: int) -> int:
    """`--seed` may exceed 32 signed bits; PRNG keys and numpy's legacy
    seeding want fewer.  A bijection is not needed, only that the same
    seed gives the same inputs."""
    return int(seed) % 2147483629


def percentile(values, q: float) -> float:
    import numpy as np

    return float(np.percentile(np.asarray(values, np.float64), q))


class Cell:
    """One entry of `workloads` with everything it names resolved."""

    def __init__(self, bench: dict, name: str, *, seed: int, seconds: float,
                 trace: bool, rehearse: bool, t_process_start: float):
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json "
                             f"(has {sorted(cells)})")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        cfg_entry = {c["name"]: c for c in bench["configs"]}[
            self.entry["config"]]
        self.config = load_json(os.path.join(ROOT, cfg_entry["file"]))
        self.traffic = load_json(os.path.join(
            PERF_DIR, "traffic", self.entry["traffic"] + ".json"))
        if rehearse:
            # the CPU rehearsal: same control flow, the files' own tiny
            # twins of their sizes (a shallow overlay of top-level keys)
            self.config.update(self.config.get("rehearse", {}))
            self.traffic.update(self.traffic.get("rehearse", {}))
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        self.rehearse = bool(rehearse)
        self.t_process_start = t_process_start
        self.setup_marks: List[tuple] = []

        def of_cell(metric):
            return "workloads" not in metric or name in metric["workloads"]

        self.end_to_end = [m for m in bench["end_to_end"] if of_cell(m)]
        self.per_layer = [m for m in bench["per_layer"] if of_cell(m)]

    def job(self):
        return load_module(os.path.join(
            PERF_DIR, "jobs", self.traffic["job"] + ".py"))

    def reference(self):
        return load_module(os.path.join(
            PERF_DIR, "reference", self.config["reference"] + ".py"))

    def mark(self, what: str):
        """Note how far set-up has come, in seconds since the process
        started: the earlier output line shows where `setup_s` goes."""
        self.setup_marks.append(
            (what, round(time.perf_counter() - self.t_process_start, 3)))

    def scratch_dir(self, name: str) -> str:
        """A directory for what a run writes (profiler captures, IR
        dumps): inside the checkout, git-ignored, emptied per use."""
        path = os.path.join(PERF_DIR, ".scratch",
                            f"{self.name}.{name}")
        shutil.rmtree(path, ignore_errors=True)
        os.makedirs(path, exist_ok=True)
        return path


class Run:
    """What a job hands back.  `end_to_end` holds the job's own
    end-to-end readings (the harness adds `setup_s`); everything else
    is raw material for the per-layer readers."""

    def __init__(self):
        self.correct = False
        self.notes: Dict[str, Any] = {}      # why correct is what it is
        self.attempted = 0
        self.failed = 0
        self.end_to_end: Dict[str, float] = {}
        self.t_window_open = 0.0             # perf_counter
        self.t_window_close = 0.0
        self.samples: Dict[str, list] = {}   # named host-clock samples
        self.counters: Dict[str, float] = {}
        self.spans: List[dict] = []          # program spans (traced run)
        self.trace: Optional[dict] = None    # reduce_trace.reduce(...)
        self.cell: Optional[Cell] = None
        self.device: Dict[str, Any] = {}
        self.peaks: Dict[str, float] = {}


def device_info() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """What the fullest chip had to hold at its peak: the allocator's
    `peak_bytes_in_use` (arrays: weights, state, caches, inputs) plus
    `peak_bytes_reserved`, where the TPU runtime keeps the scratch of
    the loaded programs (their temporaries: activations, gradients).
    A probe on the v5e (PR 23) showed a program's 268 MB of temporaries
    under `reserved` alone, so `in_use` by itself under-reads a training
    step by its whole activation memory."""
    import jax

    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak


def hbm_peak_gb(run: "Run") -> Optional[float]:
    """The `*_hbm_peak_gb` readers: `memory_peak_bytes` in 1e9 bytes."""
    peak = memory_peak_bytes()
    return peak / 1e9 if peak else None


def device_idle_share(run: "Run") -> Optional[float]:
    """The `*_device_idle_share` readers: 100 x (1 - busy / slice) of
    the traced slice, chips averaged; nothing without a device plane."""
    tr = run.trace
    if not tr or not tr["window_s"] or not tr["chips"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def step_ms(run: "Run") -> Optional[float]:
    """The `*_step_ms` readers: median distance between the completions
    of consecutive steps, each read after the step's result was ready."""
    v = run.samples.get("step_done")
    if not v or len(v) < 2:
        return None
    return 1e3 * percentile([b - a for a, b in zip(v, v[1:])], 50)


def recompiles_in_window(run: "Run") -> Optional[float]:
    """The `*_recompiles_in_window` readers: XLA compile requests of the
    whole process between the window's opening and its close, plus the
    Executor's own `recompiles_after_warmup` where the job reads it."""
    c = run.counters.get("compiles_in_window")
    if c is None:
        return None
    return c + run.counters.get("executor_recompiles", 0)


def mfu(run: "Run", rate_metric: str) -> Optional[float]:
    """The `*_mfu` readers: operations the forward and backward passes
    need per item (perf/flops.py, from the configuration's sizes) times
    the items a second this run's window read, over chips times the
    bf16 peak of perf/peaks.json.  End-to-end utilisation, not a
    kernel's roofline share."""
    per_item = run.counters.get("train_flops_per_item")
    rate = run.end_to_end.get(rate_metric)
    if not per_item or not rate:
        return None
    return 100.0 * per_item * rate / (
        run.cell.chips * run.peaks["bf16_flops_per_s"])


def peaks_for(kind: str) -> dict:
    table = load_json(os.path.join(PERF_DIR, "peaks.json"))["devices"]
    if kind not in table:
        raise SystemExit(f"device kind {kind!r} has no entry in "
                         f"perf/peaks.json ({sorted(table)})")
    return table[kind]


class GcWatch:
    """Times the interpreter's garbage collections while armed: a long
    one stops every thread of the process, the scheduler included, and
    shows as a stall that no span explains."""

    def __init__(self):
        self.pauses: List[tuple] = []    # (perf_counter at start, s, gen)
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict):
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            self.pauses.append((self._t0, time.perf_counter() - self._t0,
                                info.get("generation")))

    def arm(self):
        import gc

        gc.callbacks.append(self)

    def disarm(self):
        import gc

        if self in gc.callbacks:
            gc.callbacks.remove(self)

    def counters(self, t_open: float, t_close: float) -> dict:
        inside = [p for p in self.pauses if t_open <= p[0] < t_close]
        return {"gc_collections_in_window": len(inside),
                "gc_pause_total_ms": 1e3 * sum(p[1] for p in inside),
                "gc_pause_max_ms": 1e3 * max([p[1] for p in inside],
                                             default=0.0)}


class SpanTap:
    """Collects the program's own spans (observability.tracing) while
    armed, without turning the export buffer on.  Only the traced run
    arms it: a live span costs a few microseconds a site."""

    def __init__(self):
        self.records: List[dict] = []
        self._armed = False

    def __call__(self, rec: dict):
        self.records.append({"name": rec["name"], "ts": rec["ts"],
                             "dur": rec["dur"]})

    def arm(self):
        from paddle_tpu.observability import tracing

        if not self._armed:
            tracing.add_span_listener(self)
            self._armed = True

    def disarm(self):
        from paddle_tpu.observability import tracing

        if self._armed:
            tracing.remove_span_listener(self)
            self._armed = False


class TraceWindow:
    """A few seconds of the measured window under jax.profiler, taken
    on a thread of its own so that the job's loop is not in its way.

    `start()` returns at once; the thread waits `delay` seconds,
    records for `length` seconds between two annotations, stops the
    profiler and reduces the capture with perf/reduce_trace.py, giving
    the program's spans (from the `SpanTap`) to the gap attribution on
    the trace's clock: one `perf.clock_sync` annotation is read beside
    `time.time()`, which is the clock the spans carry."""

    def __init__(self, cell: Cell, tap: SpanTap, delay: float,
                 length: float):
        self.cell, self.tap = cell, tap
        self.delay, self.length = delay, length
        self.result: Optional[dict] = None
        self.error: Optional[BaseException] = None
        self._thread = threading.Thread(target=self._body, daemon=True,
                                        name="perf-trace")

    def start(self):
        self._thread.start()

    def _body(self):
        try:
            import jax

            out = self.cell.scratch_dir("trace")
            time.sleep(self.delay)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(out, profiler_options=opts)
            sync_wall = time.time()
            with jax.profiler.TraceAnnotation(CLOCK_SYNC):
                pass
            with jax.profiler.TraceAnnotation(WINDOW_MARKER):
                time.sleep(self.length)
            jax.profiler.stop_trace()
            self._sync_wall = sync_wall
            self._dir = out
        except BaseException as e:      # reported by finish()
            self.error = e

    def finish(self) -> Optional[dict]:
        """Join the thread and reduce the capture (after the measured
        window: the reduction is host work the window must not pay)."""
        self._thread.join()
        if self.error is not None:
            raise self.error
        pbs = glob.glob(self._dir + "/**/*.xplane.pb", recursive=True)
        if not pbs:
            raise RuntimeError("jax.profiler wrote no .xplane.pb")
        tr = reduce_trace.Trace.from_file(pbs[0])
        sync = tr.annotation(CLOCK_SYNC)
        spans = []
        if sync is not None:
            for rec in self.tap.records:
                if rec["dur"] > self.length / 2:
                    continue    # covers half the slice: explains no gap
                a = (rec["ts"] - self._sync_wall) * 1e9 + sync[0]
                spans.append((a, a + rec["dur"] * 1e9, rec["name"]))
        self.result = reduce_trace.reduce(tr, WINDOW_MARKER, spans)
        shutil.rmtree(self._dir, ignore_errors=True)
        return self.result
