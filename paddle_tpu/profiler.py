"""Profiler: per-op/segment range events with an aggregated summary table,
plus XLA trace capture.

Reference: /root/reference/paddle/fluid/platform/profiler.{h,cc}
(thread-local EventList, RecordEvent RAII around every op in
Executor::Run, EnableProfiler/DisableProfiler -> sorted table of
calls/total/min/max/ave) and python/paddle/v2/fluid/profiler.py
(`profiler` and `cuda_profiler` context managers).

TPU mapping: interpreter/segmented modes time each op (or compiled
segment) with `block_until_ready` fencing — the analogue of the
reference's cudaEvent timing on the op stream.  Whole-block compiled mode
is one fused XLA executable, so per-op attribution comes from
`xla_profiler` (jax.profiler trace, viewable in TensorBoard/Perfetto)
instead — the TPU answer to `cuda_profiler`'s nvprof output.
"""
from __future__ import annotations

import contextlib
import logging
import threading
import time
from typing import Dict, List, Optional

from .observability import tracing as _tracing

__all__ = [
    "enable_profiler",
    "disable_profiler",
    "reset_profiler",
    "profiler",
    "cuda_profiler",
    "xla_profiler",
    "record_event",
    "profiler_summary",
    "profile_compiled_ops",
    "hlo_scopes",
    "scope_seconds",
    "register_jitted",
    "lowered_ir_text",
    "event_totals",
    "host_blocked_fraction",
]


def lowered_ir_text(lowered) -> str:
    """Debug-info MLIR text of a `jax.jit(...).lower(...)` result — the
    loc() metadata carries the per-op named_scope the compiled executor
    emits, so scope assertions and debugging work on it."""
    return lowered.as_text(debug_info=True)


_enabled = False
_events: Dict[str, List[float]] = {}
# events are recorded from the prefetch worker thread too
# (reader/pipeline.py): the store must tolerate concurrent
# record_event vs event_totals/profiler_summary readers
_events_lock = threading.Lock()


def is_enabled() -> bool:
    return _enabled


@contextlib.contextmanager
def record_event(name: str, sync=None):
    """RAII range event (reference platform::RecordEvent).  `sync` is
    called before reading the clock (device fence, e.g. block_until_ready).

    When trace-span recording is on (observability.tracing), the range
    also opens a span, so profiler events land in the Chrome trace with
    real wall-clock placement alongside the subsystem spans."""
    if not _enabled and not _tracing.enabled():
        yield
        return
    t0 = time.perf_counter()
    span_cm = _tracing.span(name)
    span_cm.__enter__()
    try:
        yield
    finally:
        try:
            if sync is not None:
                sync()
        finally:
            # close the span AFTER the fence so span and event time the
            # same range, but ALWAYS close it (inner finally): a raising
            # fence must not leave the context pushed on the thread's
            # span stack, which would mis-parent every later span.  Exc
            # info deliberately not forwarded: a raising op still
            # records its range, same as the event list.
            span_cm.__exit__(None, None, None)
            if _enabled:
                dt = time.perf_counter() - t0
                with _events_lock:
                    _events.setdefault(name, []).append(dt)


def enable_profiler(state: str = "All"):
    global _enabled
    assert state in ("CPU", "GPU", "TPU", "All"), state
    _enabled = True


def reset_profiler():
    with _events_lock:
        _events.clear()
        _hlo_text_providers.clear()


def disable_profiler(sorted_key: Optional[str] = None, print_table=True):
    """Stop profiling; print/return the aggregated table
    (reference DisableProfiler + PrintProfiler)."""
    global _enabled
    _enabled = False
    table = profiler_summary(sorted_key)
    if print_table:
        print(format_summary(table))
    return table


def profiler_summary(sorted_key: Optional[str] = None):
    """Aggregated rows; `sorted_key=None` defaults to "total" descending
    (the reference PrintProfiler's default ordering — insertion order was
    a bug: the table's point is ranking hotspots).  Pass "insertion" to
    keep recording order."""
    rows = []
    with _events_lock:
        snapshot = {name: list(ts) for name, ts in _events.items()}
    for name, ts in snapshot.items():
        rows.append({
            "name": name, "calls": len(ts), "total": sum(ts),
            "min": min(ts), "max": max(ts), "ave": sum(ts) / len(ts),
        })
    key = sorted_key if sorted_key is not None else "total"
    if key in ("calls", "total", "min", "max", "ave"):
        rows.sort(key=lambda r: -r[key])
    return rows


def event_totals() -> Dict[str, float]:
    """{event name: total seconds} recorded so far — the programmatic
    view of the summary table, for user telemetry over the pipeline
    stage events (feed.pack / pipeline.*; see docs/performance.md).
    bench.py measures its loops directly instead: enabling the profiler
    fences compiled-mode dispatches and would serialize what it times."""
    with _events_lock:
        return {name: sum(ts) for name, ts in _events.items()}


def host_blocked_fraction(wall_seconds: float, events) -> float:
    """Fraction of `wall_seconds` spent inside the named host-side
    events.  Which events block the loop depends on the loop: a
    hand-written serial loop blocks in `feed.pack` (DataFeeder) +
    `pipeline.h2d`; under `Trainer.train` the prefetch worker absorbs
    those, and the loop itself only blocks in `pipeline.wait` (nothing
    prepared yet) and `pipeline.fetch_sync` (LazyFetch reads) — pass
    the event set matching the loop measured."""
    if wall_seconds <= 0:
        return 0.0
    with _events_lock:
        total = sum(sum(_events.get(e, ())) for e in events)
    return min(total / wall_seconds, 1.0)


def format_summary(rows) -> str:
    out = [f"{'Event':<40}{'Calls':>8}{'Total(ms)':>12}{'Min(ms)':>10}"
           f"{'Max(ms)':>10}{'Ave(ms)':>10}"]
    for r in rows:
        out.append(
            f"{r['name']:<40}{r['calls']:>8}{r['total'] * 1e3:>12.3f}"
            f"{r['min'] * 1e3:>10.3f}{r['max'] * 1e3:>10.3f}"
            f"{r['ave'] * 1e3:>10.3f}")
    return "\n".join(out)


@contextlib.contextmanager
def profiler(state: str = "CPU", sorted_key: Optional[str] = None,
             print_table=True):
    """`with profiler.profiler('All', 'total'):` (reference
    fluid/profiler.py:76)."""
    enable_profiler(state)
    reset_profiler()
    try:
        yield
    finally:
        disable_profiler(sorted_key, print_table=print_table)


@contextlib.contextmanager
def xla_profiler(log_dir: str = "/tmp/paddle_tpu_trace"):
    """Capture an XLA device trace via jax.profiler (TensorBoard/Perfetto
    viewable) — the TPU replacement for nvprof capture."""
    import jax

    jax.profiler.start_trace(log_dir)
    try:
        yield log_dir
    finally:
        jax.profiler.stop_trace()


# API-compat alias: reference scripts say cuda_profiler; on this stack the
# device tracer is the XLA profiler.
@contextlib.contextmanager
def cuda_profiler(output_file=None, output_mode=None, config=None):
    with xla_profiler() as d:
        yield d


# ---------------------------------------------------------------------------
# compiled-mode per-op table (reference profiler.h:120-146 semantics for
# whole-block XLA executables)
# ---------------------------------------------------------------------------


# The scope a LOOP instruction gets in the tables: a device trace times
# a `while` as one event AND every instruction of its body as events of
# their own, so the loop's seconds are its body's over again and
# `scope_seconds` leaves them out.
LOOP_SCOPE = "<loop>"


def _scope_tables(hlo_text: str):
    """(HLO instruction name -> source op_name metadata, names that
    inherited theirs).  The metadata carries the per-op
    jax.named_scope the compiled executor emits.  An instruction the
    compiler made itself has none (on the TPU: the relayout
    `reshape(fusion.N)` of a gathered tensor, async copies and slices);
    it takes the scope of its first operand that has one, its
    producer's, and is listed in the second value: a choice of producer
    over consumer, not compiler metadata.  Only where no producer has
    one (a weight's slices inside a loop's body) does it take its first
    consumer's."""
    import re

    out = {}
    orphans = []
    users = {}              # an instruction's first consumer
    for m in re.finditer(r"%?([\w.\-]+) = ([^\n]*)", hlo_text):
        name, rest = m.groups()
        meta = re.search(r"metadata={[^}]*op_name=\"([^\"]+)\"", rest)
        operands = re.findall(r"%([\w.\-]+)", rest)
        for o in operands:
            users.setdefault(o, name)
        if " while(" in rest and " body=" in rest:
            out[name] = LOOP_SCOPE
        elif meta:
            out[name] = meta.group(1)
        else:
            orphans.append((name, operands))
    inherited = set()
    for _ in range(3):      # a short chain: copy-done(copy-start(fusion))
        for name, operands in orphans:
            if name not in out:
                scope = next((out[o] for o in operands if o in out), None)
                if scope is not None:
                    out[name] = scope
                    inherited.add(name)
    # What no producer names takes its first CONSUMER's scope.  Inside
    # a loop's body the weights are elements of the body's parameter
    # tuple, which carries no metadata, so the slices the compiler
    # brings a weight in with (`slice-done(slice-start(gte))`) have no
    # producer to ask; the matmul that waits for them is their consumer.
    for _ in range(3):      # slice-start <- slice-done <- the matmul
        for name, _ in orphans:
            if name not in out and users.get(name) in out:
                out[name] = out[users[name]]
                inherited.add(name)
    return out, inherited


def _scope_map(hlo_text: str) -> Dict[str, str]:
    return _scope_tables(hlo_text)[0]


# Device time by scope: the step executables this process built, each
# as a zero-argument provider of its compiled (optimized) HLO text.
# An owner registers one at its first compile or warm-up; nothing is
# lowered or read until `hlo_scopes()` asks.  A provider keeps its
# jitted function and the shapes it first ran with alive (never device
# buffers), also after the owner's close(), so the registry is bounded
# to the last few executables.  Tables, once read, replace the text.
_HLO_PROVIDERS_CAP = 8
_hlo_text_providers: List[list] = []       # [label, provider, tables]
_METADATA_KEY_OPTION = "compilation_cache_include_metadata_in_key"


def _register_hlo_text(label: str, provider) -> None:
    with _events_lock:
        _hlo_text_providers.append([label, provider, None])
        del _hlo_text_providers[:-_HLO_PROVIDERS_CAP]


def register_jitted(label: str, jitted, *args,
                    compiler_scopes: Optional[Dict[str, str]] = None
                    ) -> None:
    """Register the jitted step `jitted`, as called with `args`, under
    `label` ("executor.block", "parallel_executor.step",
    "paged_decoder.step"): what `Executor`, `ParallelExecutor` and
    `GenerationServer` do at their first compile or warm-up.  Several
    executables may share a label (a startup and a main program).
    Keeps the function and the arguments' shapes and shardings, no
    buffer; lowers and compiles only when `hlo_scopes()` asks.

    `compiler_scopes` is the owner's word on instructions the compiler
    REWRITES under an `op_name` of its own, losing the scope they were
    traced under ({the compiler's op_name: the scope}): the TPU
    compiler turns every `ragged_dot` into a grouped-matmul call named
    `ragged-dot-none`, and only the owner knows that all of its
    `ragged_dot`s lie under one scope."""
    specs = _arg_specs(*args)

    def text():
        out = _compiled_text(lambda: jitted.lower(*specs))
        for made, scope in (compiler_scopes or {}).items():
            out = out.replace(f'op_name="{made}"', f'op_name="{scope}"')
        return out

    _register_hlo_text(label, text)


def _arg_specs(*args):
    """`jax.ShapeDtypeStruct`s (shape, dtype, weak type and, for device
    arrays, sharding) standing in for `args` when a provider lowers a
    jitted function again: they keep no buffer alive.  Leaves that are
    no arrays pass through."""
    import jax
    import numpy as np

    def spec(x):
        if isinstance(x, jax.Array):
            return jax.ShapeDtypeStruct(
                x.shape, x.dtype, sharding=x.sharding,
                weak_type=getattr(x, "weak_type", False))
        if isinstance(x, (np.ndarray, np.generic)):
            return jax.ShapeDtypeStruct(x.shape, x.dtype)
        return x

    return jax.tree_util.tree_map(spec, args)


class ScopeNamesUnavailable(RuntimeError):
    """This JAX cannot key a compile with metadata, so compiled text
    cannot be trusted to carry this build's scope names."""


def _compiled_text(lower) -> str:
    """Compiled HLO text of `lower() -> jax.stages.Lowered`, carrying
    THIS build's scope names.  JAX keys its compile caches (the
    process's and the persistent one) without metadata, so the
    executable a cache hit hands back, which may well be the one the
    process runs, names the scopes, files and lines of whichever build
    compiled it first.  This compile is therefore keyed WITH metadata
    (the option is set for this thread alone, so a compile on another
    thread keeps its key) and apart from the running executable (a
    compiler option at its default value): a full compile in a cache
    another build filled, a hit in one this build filled.  XLA is
    deterministic, so the instruction names are those of the
    executable that runs."""
    import jax
    from jax._src import config as jax_config

    keyed = getattr(jax_config, _METADATA_KEY_OPTION, None)
    if keyed is None:
        raise ScopeNamesUnavailable(
            f"jax {jax.__version__} has no {_METADATA_KEY_OPTION}")
    with keyed(True):
        return lower().compile(
            compiler_options={"xla_embed_ir_in_executable": False}
        ).as_text()


def _hlo_tables(label: Optional[str]):
    """{key: (table, inherited names)} behind `hlo_scopes`."""
    with _events_lock:
        providers = list(_hlo_text_providers)
    out = {}
    seen: Dict[str, int] = {}
    for entry in providers:
        lbl, provider, tables = entry
        if label is not None and lbl != label:
            continue
        seen[lbl] = seen.get(lbl, 0) + 1
        if tables is None:
            try:
                tables = entry[2] = _scope_tables(provider())
            except ScopeNamesUnavailable:
                raise       # no table can be had at all: say so loudly
            except Exception as e:      # the other tables still count
                logging.getLogger(__name__).warning(
                    "hlo_scopes: no compiled text for %s: %r", lbl, e)
                continue
        out[lbl if seen[lbl] == 1 else f"{lbl}#{seen[lbl]}"] = tables
    return out


def hlo_scopes(label: Optional[str] = None) -> Dict[str, Dict[str, str]]:
    """{label: {HLO instruction name: named scope}} for the step
    executables this process built: the join from a device trace's
    operation names (`fusion.12`, `reshape.3`) back to the
    `jax.named_scope` they were traced under (`paged_decoder/kv_gather`,
    `mul:fc_0.tmp_0`).  A second and further executable under one label
    is keyed `<label>#2`, `<label>#3`...; `label` keeps that label's
    tables only.  A provider that fails is logged and left out; a JAX
    that cannot give this build's names at all raises
    `ScopeNamesUnavailable`."""
    return {k: t for k, (t, _) in _hlo_tables(label).items()}


def scope_seconds(op_seconds: Dict[str, float], label: str,
                  inherited_only: bool = False) -> Dict[str, float]:
    """Device seconds by named scope: a device trace's seconds per HLO
    instruction name (`op_seconds`) joined with `hlo_scopes(label)`.
    Where several executables share the label, the table that covers
    the most of these seconds is taken.  Instructions the table does
    not name (other executables, transfers) go under ""; the values
    add up to `op_seconds`' total, less the seconds of LOOP
    instructions (`LOOP_SCOPE`: their bodies' instructions are in
    `op_seconds` themselves).  With `inherited_only`, only the
    seconds of instructions that carry no metadata of their own and
    took their producer's scope (`_scope_tables`): the part of each
    scope's seconds that is a heuristic, not the compiler's word."""
    best, inherited = {}, set()
    covered = -1.0
    for table, names in _hlo_tables(label).values():
        c = sum(t for op, t in op_seconds.items() if op in table)
        if c > covered:
            best, inherited, covered = table, names, c
    out: Dict[str, float] = {}
    for op, t in op_seconds.items():
        if inherited_only and op not in inherited:
            continue
        scope = best.get(op, "")
        if scope != LOOP_SCOPE:
            out[scope] = out.get(scope, 0.0) + t
    return out


def profile_compiled_ops(run_fn, steps: int = 3, hlo_text: str = "",
                         print_table: bool = True):
    """Per-op timing table for a COMPILED block: trace `steps` calls of
    `run_fn` with jax.profiler, digest the xplane into the reference's
    sorted calls/total/min/max/ave table (profiler.h:120-146) — compiled
    -mode hotspots become rankable without leaving the framework.

    Whole-block jit means the interpreter's per-op RecordEvent cannot
    see inside the fused executable; the device trace can: each XLA op
    (fusions included) is one event.  Pass the executable's
    `.as_text()` as `hlo_text` to annotate rows with the originating
    `named_scope` (framework op) each fused op belongs to.

    Returns rows: [{"name", "scope", "calls", "total", "min", "max",
    "ave"}] sorted by total desc (seconds, like profiler_summary).
    """
    import glob
    import shutil
    import tempfile

    import jax

    tmp = tempfile.mkdtemp(prefix="pt_prof_")
    try:
        with jax.profiler.trace(tmp):
            for _ in range(steps):
                out = run_fn()
                jax.block_until_ready(out)
        per_op: Dict[str, List[float]] = {}
        pbs = glob.glob(tmp + "/**/*.xplane.pb", recursive=True)
        if not pbs:
            raise RuntimeError("jax.profiler produced no xplane capture")
        pd = jax.profiler.ProfileData.from_file(pbs[0])
        for plane in pd.planes:
            for line in plane.lines:
                for ev in line.events:
                    hlo = dict(ev.stats).get("hlo_op")
                    if not hlo or ev.duration_ns <= 0:
                        continue
                    per_op.setdefault(str(hlo), []).append(
                        ev.duration_ns / 1e9)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)

    scopes = _scope_map(hlo_text) if hlo_text else {}
    rows = []
    for name, ts in per_op.items():
        rows.append({
            "name": name,
            "scope": scopes.get(name, ""),
            "calls": len(ts), "total": sum(ts),
            "min": min(ts), "max": max(ts), "ave": sum(ts) / len(ts),
        })
    rows.sort(key=lambda r: -r["total"])
    if print_table:
        print(format_op_table(rows))
    return rows


def format_op_table(rows, limit: int = 30) -> str:
    out = [f"{'XLA op':<44}{'Scope':<36}{'Calls':>6}{'Total(ms)':>11}"
           f"{'Min(ms)':>9}{'Max(ms)':>9}{'Ave(ms)':>9}"]
    for r in rows[:limit]:
        out.append(
            f"{r['name'][:43]:<44}{r['scope'][-35:]:<36}{r['calls']:>6}"
            f"{r['total'] * 1e3:>11.3f}{r['min'] * 1e3:>9.3f}"
            f"{r['max'] * 1e3:>9.3f}{r['ave'] * 1e3:>9.3f}")
    return "\n".join(out)
