"""Executor: runs a Program's block against a Scope.

Two execution modes over the same op lowerings (core/execution.py):

  * **interpreter** — op-by-op eager execution, the debuggable analogue of the
    reference's `Executor::Run` loop
    (/root/reference/paddle/fluid/framework/executor.cc:80-151), minus its
    known inefficiencies (ops are NOT re-created and re-shape-inferred every
    step; there is no per-step scope rebuild).
  * **compiled** — the whole block is traced into one jax function and
    jit-compiled for XLA; executables are cached keyed by
    (program fingerprint, feed/state shapes+dtypes+LoD, fetch list), which is
    the TPU answer to OpKernel dispatch: one fused executable per
    program+shape bucket instead of per-op kernel launches.

State handling: persistable vars (parameters, optimizer accumulators,
learning-rate vars) live in the root Scope and are threaded through the
compiled function as inputs/outputs; buffers of read-write states are donated
so parameter updates are in-place at the XLA level (the reference gets this
via Param/ParamOut aliasing in optimizer ops, e.g. sgd_op.cc).
"""
from __future__ import annotations

import itertools
import operator
import time
import warnings
import weakref
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import registry
from ..observability import flightrecorder
from ..observability import metrics as obs_metrics
from ..observability import tracing as obs_tracing
from ..observability.attribution import IterationClock
from .compile_cache import compile_cache_dir
from .execution import DictEnv, ExecContext, ScopeEnv, run_op, step_key
from .flags import get_flag, trace_flags
from .framework import Program, Variable, default_main_program
from .lod import LoDTensor
from .scope import Scope


def _dp_replicated_sharding(ops):
    """If any op in `ops` is a parallel_do, a replicated NamedSharding over
    its device mesh (so jitted inputs land on the full device set);
    else None."""
    n = 0
    for op in ops:
        if op.type == "parallel_do":
            n = max(n, int(op.attrs.get("num_places", 1)))
    if n == 0:
        return None
    from ..parallel.mesh import make_mesh, replicated
    return replicated(make_mesh({"dp": min(n, len(jax.devices()))}))


def _run_op_instrumented(ctx, op, env):
    """run_op + optional profiling (reference executor.cc:124 RecordEvent)
    and nan/inf scanning (executor.cc:132-140 FLAGS_check_nan_inf).
    Only eager (interpreter / host-segment) op execution goes through here —
    ops inside a jit trace are compile-time and get no per-op events; compiled
    executions are timed as whole-segment/block events by their callers."""
    from paddle_tpu import profiler

    sync = (lambda: _op_sync(env, op)) if get_flag("benchmark") else None
    if profiler.is_enabled():
        with profiler.record_event(op.type, sync=sync):
            run_op(ctx, op, env)
    else:
        run_op(ctx, op, env)
        if sync is not None:
            sync()
    if get_flag("check_nan_inf"):
        _check_nan_inf(env, op)


def _op_sync(env, op):
    for n in op.output_names():
        v = env.get(n)
        if v is not None:
            jax.tree_util.tree_map(
                lambda x: x.block_until_ready()
                if hasattr(x, "block_until_ready") else x, v)


def _check_nan_inf(env, op):
    for n in op.output_names():
        v = env.get(n)
        if v is None:
            continue
        for leaf in jax.tree_util.tree_leaves(v):
            arr = np.asarray(leaf)
            if np.issubdtype(arr.dtype, np.floating) and \
                    not np.isfinite(arr).all():
                raise RuntimeError(
                    f"Operator {op.type!r} output {n!r} contains "
                    "NaN/Inf (check_nan_inf)")

__all__ = ["CPUPlace", "TPUPlace", "CUDAPlace", "Executor",
           "global_scope", "scope_guard", "switch_scope"]


# ---------------------------------------------------------------------------
# Places (reference platform/place.h:24-53)
# ---------------------------------------------------------------------------


class CPUPlace:
    accelerator = False

    def jax_device(self):
        return jax.devices("cpu")[0]

    def __repr__(self):
        return "CPUPlace"

    def __eq__(self, o):
        return isinstance(o, CPUPlace)


class TPUPlace:
    """Accelerator place; device_id indexes jax.devices("tpu").  There
    is no CPU stand-in: a process that finds no TPU (or no such index)
    raises here, and the CPU is reached only through CPUPlace."""

    accelerator = True

    def __init__(self, device_id: int = 0):
        self.device_id = device_id

    def jax_device(self):
        try:
            devs = jax.devices("tpu")
        except RuntimeError as e:
            raise RuntimeError(
                f"{self!r}: this process has no TPU backend ({e}); use "
                "CPUPlace() to run on the host") from None
        if not 0 <= self.device_id < len(devs):
            raise RuntimeError(
                f"{self!r}: only {len(devs)} TPU device(s) are visible")
        return devs[self.device_id]

    def __repr__(self):
        return f"TPUPlace({self.device_id})"

    def __eq__(self, o):
        return isinstance(o, TPUPlace) and o.device_id == self.device_id


# API-compat alias: reference models say CUDAPlace; on this stack it is the
# accelerator place.
CUDAPlace = TPUPlace

_global_scope = Scope()


def global_scope() -> Scope:
    return _global_scope


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------


def _place_feed(v, device):
    """Feed value -> (device value, fresh): `fresh` is True when the
    executor just created the device buffer from host data, i.e. no
    caller-held reference can alias it — the ownership precondition for
    donating the buffer to the jitted step.  A value that arrives as a
    jax array may BE the caller's buffer (device_put to the same device
    is a no-op returning it), so it is never marked fresh."""
    if isinstance(v, LoDTensor):
        return LoDTensor(jax.device_put(np.asarray(v.data), device),
                         v.lod), True
    if isinstance(v, jnp.ndarray):
        # already a jax array: placing it directly avoids a device->host
        # round-trip and keeps a weak dtype weak (np.asarray would do
        # both); one that rests there (a batch the trainer's pipeline
        # staged) is what `device_put` would hand back
        return (v if _rests_on(v, device)
                else jax.device_put(v, device)), False
    if isinstance(v, (int, float, bool)) and not isinstance(v, np.generic):
        # same weak-typing rule as _commit below: a Python scalar fed to
        # a bf16 program must not arrive as a strong f32/i64 array
        return jax.device_put(v, device), True
    if isinstance(v, (np.ndarray, jnp.ndarray, np.generic)):
        return jax.device_put(np.asarray(v), device), True
    return v, False  # opaque host object


def _to_device_value(v, device):
    """Feed value -> device arrays (LoDTensor wrapper preserved)."""
    return _place_feed(v, device)[0]


# caps for the liveness-artifact caches: a long-lived executor serving
# many programs (or one whose version keeps bumping — every mutation is
# a fresh fingerprint) must not accumulate plans, and especially not
# full program clones, without bound
_MEMOPT_CACHE_CAP = 16
_PLAN_CACHE_CAP = 256


def _bounded_put(cache: dict, key, value, cap: int):
    """FIFO-evicting insert: dicts iterate in insertion order, so the
    oldest entry goes first once `cap` is reached."""
    while len(cache) >= cap:
        cache.pop(next(iter(cache)))
    cache[key] = value


def _to_numpy(v):
    if isinstance(v, LoDTensor):
        return LoDTensor(np.asarray(v.data), v.lod)
    if isinstance(v, jnp.ndarray):
        return np.asarray(v)
    return v


def _seed_word(seed):
    """The 32 bits `jax.random.key` keeps of a Python integer seed while
    64-bit types are off (it wraps the seed into an int32 before it
    seeds), as a NumPy scalar a jitted step can take traced: the key it
    makes of this is the key made of the integer, bit for bit."""
    return np.int64(seed).astype(np.int32)


_LEAF = str(jax.tree_util.tree_structure(0))


def _aval_key(v):
    """Hashable (structure, shapes, dtypes) key for one value."""
    if isinstance(v, jax.Array):  # a leaf: what the lines below give
        return _LEAF, ((tuple(v.shape), str(v.dtype)),)
    leaves, treedef = jax.tree_util.tree_flatten(v)
    return (
        str(treedef),
        tuple((tuple(x.shape), str(x.dtype)) for x in map(jnp.asarray, leaves)),
    )


def _commit(v, target):
    """Commit a state value to `target` (a device or sharding) WITHOUT a
    host round-trip.  jax.jit's internal cache keys on argument
    committed-ness: startup-program outputs are uncommitted (no committed
    inputs), while step outputs of the donated training jit are committed,
    so without normalization the second `exe.run` of an identical config
    re-traces and re-compiles the whole program (measured 384/305/1.5 ms
    on a small MLP).  device_put is a no-op
    returning the same buffer when the value is already committed there."""
    if isinstance(v, LoDTensor):
        return LoDTensor(_commit(v.data, target), v.lod)
    if isinstance(v, jnp.ndarray):
        return jax.device_put(v, target)
    if isinstance(v, (int, float, bool)) and not isinstance(v, np.generic):
        # Python scalars stay weakly typed (device_put direct): routing
        # them through np.asarray would mint a strong f64->f32/i64 array
        # and silently promote bf16 consumers in amp programs
        return jax.device_put(v, target)
    if isinstance(v, (np.ndarray, np.generic)):
        return jax.device_put(np.asarray(v), target)
    return v  # opaque host object


class _MissingState(KeyError):
    pass


def _keeps_identity(v) -> bool:
    """True for a value that cannot change while the scope holds the same
    object: a jax array, or a LoDTensor over one.  A NumPy array or a
    Python scalar may be written in place by its owner (and a scalar
    must stay weakly typed), so those are committed at every step."""
    if isinstance(v, LoDTensor):
        v = v.data
    return isinstance(v, jax.Array)


def _rests_on(v, target) -> bool:
    """True where `_commit(v, target)` would hand back `v`'s own buffer:
    `v` (which `_keeps_identity`) is committed, and where `target` (a
    device or a sharding) says."""
    a = v.data if isinstance(v, LoDTensor) else v
    if not a.committed:
        return False
    if isinstance(target, jax.sharding.Sharding):
        return a.sharding.is_equivalent_to(target, a.ndim)
    return a.devices() == {target}


def _NO_FN():
    """A dead weak reference's answer: the record knows no executable."""
    return None


class _StepRecord:
    """What `_run_compiled` learnt about one compiled step the last time
    it ran it: the analysis of the Program (which never changes under
    the same `_version`), and for every persistable state the object
    last `seen` in the scope, its committed `twin` and its `_aval_key`.
    A state whose scope object IS the one seen costs a dictionary read
    at the next step; anything else (replaced by `scope.set_var`, a
    NumPy value, a first sight) goes through `_commit` and `_aval_key`
    as if there were no record, for that state alone."""

    __slots__ = ("device", "flags", "state_in_names", "state_out_names",
                 "ro_names", "rw_names", "donatable", "repl", "target",
                 "seen", "twin", "keys", "feed_names", "feed_keys",
                 "don_names", "keep_names", "cache_key", "fn", "outs")

    def __init__(self, device, flags, feed_names, state_in_names,
                 state_out_names, repl):
        self.device, self.flags = device, flags
        # the feeds in the order every executable of this record takes
        # them: by name, whatever order the caller's dictionary has
        self.feed_names = tuple(sorted(feed_names))
        self.state_in_names = state_in_names
        self.state_out_names = state_out_names
        written = set(state_out_names)
        self.ro_names = [n for n in state_in_names if n not in written]
        self.rw_names = [n for n in state_in_names if n in written]
        # the feeds whose buffers the step may take, where the executor
        # itself made them (`fresh`): `_new_step_record` fills it in
        self.donatable = frozenset()
        self.repl = repl
        self.target = repl if repl is not None else device
        self.seen: Dict = {}
        self.twin: Dict = {}
        self.keys: Dict = {}
        self.feed_keys = self.don_names = self.keep_names = None
        # the executable-cache key of the last call while every part of
        # it still holds, None once a state's key moved; and a weak
        # reference to the executable under it (the executor's cache
        # owns it: a record names no program, and its executable's
        # closure does)
        self.cache_key, self.fn = None, _NO_FN
        # cache key -> {written state: its `_aval_key`}: an executable's
        # output shapes are fixed at compile time, so they are read from
        # its first outputs only
        self.outs: Dict = {}

    def gather(self, names, scope):
        """(the committed values of `names` from the scope, a tuple in
        their order, how many of them went through `_commit`)."""
        seen, twin, keys = self.seen, self.twin, self.keys
        found = scope.find_vars(names)
        if all(map(operator.is_, found, map(seen.get, names))):
            # the scope holds every one as the last step left it (the
            # common step): three passes at C speed, no line a state
            try:
                return tuple(map(twin.__getitem__, names)), 0
            except KeyError:
                pass  # a name neither in the scope nor ever seen
        vals, recommitted = [], 0
        for n, v in zip(names, found):
            if v is None:
                raise _MissingState(n)
            if v is seen.get(n):
                vals.append(twin[n])
                continue
            recommitted += 1
            c = _commit(v, self.target)
            vals.append(c)
            k = _aval_key(c)
            if keys.get(n) != k:
                keys[n], self.cache_key = k, None
            if _keeps_identity(v):
                seen[n], twin[n] = v, c
            else:
                seen.pop(n, None)
                twin.pop(n, None)
        return tuple(vals), recommitted

    def written(self, cache_key, state_out):
        """After the call: what the step wrote is what the scope now
        holds, and the donated read-write inputs are gone.  A written
        array that rests where `_commit` would put it (the outputs of a
        jit over committed arguments do; a startup program's, which
        has none, are uncommitted) is its own twin."""
        out_keys = self.outs.get(cache_key)
        if out_keys is None:
            out_keys = self.outs[cache_key] = {
                n: _aval_key(v) for n, v in state_out.items()
                if _keeps_identity(v) and _rests_on(v, self.target)}
        seen, twin, keys = self.seen, self.twin, self.keys
        for n in self.rw_names:
            k = out_keys.get(n)
            if k is None:
                seen.pop(n, None)
                twin.pop(n, None)
                continue
            seen[n] = twin[n] = state_out[n]
            old = keys.get(n)
            if k is not old:
                if k != old:
                    self.cache_key = None
                keys[n] = k


# ---------------------------------------------------------------------------
# process-wide XLA compile accounting (jax monitoring events)
# ---------------------------------------------------------------------------

# Every backend-compile request records a
# '/jax/core/compile/backend_compile_duration' event (the duration is
# the XLA compile, or the much cheaper persistent-cache deserialization
# on a hit); with the persistent cache armed (core/compile_cache.py) a
# request served from it additionally records cache_hits, and an
# executable worth persisting (JAX's write thresholds) records
# cache_misses when it is written.  Counting them gives an exact,
# backend-level "did anything compile?" signal that the serving
# warm-start contract pins (recompiles_after_warmup == 0 for a replica
# whose host cache is warm) — jit tracing alone cannot distinguish a
# real compile from a cache deserialization.
_xla_compile_counts = {"compiles": 0, "compile_seconds": 0.0,
                       "cache_hits": 0, "cache_misses": 0}
_xla_listeners_installed = False


def _install_xla_event_listeners():
    global _xla_listeners_installed
    if _xla_listeners_installed:
        return
    _xla_listeners_installed = True
    from jax._src import monitoring as jax_monitoring

    def _on_event(name, **kw):
        if name == "/jax/compilation_cache/cache_hits":
            _xla_compile_counts["cache_hits"] += 1
        elif name == "/jax/compilation_cache/cache_misses":
            _xla_compile_counts["cache_misses"] += 1

    def _on_duration(name, secs, **kw):
        if name == "/jax/core/compile/backend_compile_duration":
            _xla_compile_counts["compiles"] += 1
            _xla_compile_counts["compile_seconds"] += float(secs)

    jax_monitoring.register_event_listener(_on_event)
    jax_monitoring.register_event_duration_secs_listener(_on_duration)


def xla_compile_counts() -> Dict[str, float]:
    """Snapshot of this process's XLA compile activity: `compiles`
    (backend compile requests — each is a real XLA compile or a
    persistent-cache deserialization), `compile_seconds` (wall time
    inside those requests), and `cache_hits`/`cache_misses` (persistent
    compilation cache reads served / entries written; both stay 0
    while the cache is disabled).  Counters are process-wide and monotonic — take a
    snapshot before an operation and diff after it (what
    GenerationServer's warm-start accounting does)."""
    _install_xla_event_listeners()
    return dict(_xla_compile_counts)


# ---------------------------------------------------------------------------
# Executor
# ---------------------------------------------------------------------------

# cache/compile telemetry lives in the process metrics registry
# (observability.metrics), one label per Executor instance —
# cache_stats() below is a per-instance VIEW over these series.
# always=True: this telemetry predates the PADDLE_TPU_METRICS switch
# (cache_stats must count with metrics off), and lookups happen once
# per run, not per op, so the cost is immaterial.
_EXE_IDS = itertools.count()
_M_LOOKUPS = obs_metrics.counter(
    "paddle_tpu_executor_cache_lookups_total",
    "executable-cache lookups by result (hit/miss)",
    ("exe", "result"), always=True)
_M_COMPILE_S = obs_metrics.counter(
    "paddle_tpu_executor_compile_seconds_total",
    "wall seconds of first invocations (trace + XLA compile + first "
    "dispatch)", ("exe",), always=True)
_M_RECOMPILES = obs_metrics.counter(
    "paddle_tpu_executor_recompiles_after_warmup_total",
    "cache misses for a program that already reached steady state",
    ("exe",), always=True)
_M_STATE_COMMITS = obs_metrics.counter(
    "paddle_tpu_executor_state_commits_total",
    "persistable states a compiled step put through _commit (a step "
    "whose states are all as the last one left them adds none)",
    ("exe",), always=True)
_M_AUX_DISPATCHES = obs_metrics.counter(
    "paddle_tpu_executor_aux_dispatches_total",
    "executables run() sent to the device besides the step's own (the "
    "two that make a step key outside a compiled step)",
    ("exe",), always=True)
_M_ENTRIES = obs_metrics.gauge(
    "paddle_tpu_executor_cache_entries",
    "live executables in the cache", ("exe",), always=True)
_M_RUN_SECONDS = obs_metrics.histogram(
    "paddle_tpu_executor_run_seconds",
    "Executor.run wall latency by execution mode", ("exe", "mode"))


def run_clock() -> IterationClock:
    """The clock an executor keeps over its `run`s.  An iteration is
    the end of one `run` to the end of the next; its parts are
    `outside` (the caller's time before the call: with the results
    left on the device the caller's own wait for an older one lies
    here), `feed`, `dispatch` and the wait `fetch`.  A steady training
    step spreads by parts in 1e5, so a step of twice the reference has
    lost a whole step somewhere: it is slow."""
    return IterationClock(("outside", "feed", "dispatch"), wait="fetch",
                          slow_factor=2.0)


def end_run(clock: IterationClock, span) -> None:
    """Where `executor.run` ends: close the clock's iteration, tell a
    live span the seconds since the executor's previous `run` ended
    (`period_s`; absent on the first), and note a slow step to the
    flight recorder (`executor.slow_step`)."""
    since = clock.start
    rec = clock.end()
    if span is not None and since is not None:
        span.set_attr("period_s", clock.start - since)
    if rec is not None:
        flightrecorder.note("executor.slow_step", **rec)


class Executor:
    def __init__(self, place=None, seed: int = 0):
        self.place = place or CPUPlace()
        self._seed = seed
        self._step = 0
        self._cache: Dict = {}
        # weakref-keyed: an id()-keyed map held stale fingerprints past
        # program GC, and a recycled id could serve the WRONG fingerprint
        self._fp_cache: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()  # program -> (version, fp)
        # liveness artifacts, cached per (fingerprint, context): the
        # donation plan feeding donate_argnums, the dead-var free plan
        # the interpreter/segmented paths apply between ops, and the
        # memory-optimized program clones (rename pass)
        self._donation_plans: Dict = {}
        self._free_plans: Dict = {}
        self._memopt_cache: Dict = {}
        # scope -> program -> {(version, block, feeds, fetches, flags):
        # _StepRecord}: scope and program held weakly, each innermost
        # table bounded like the plans'
        self._step_records: "weakref.WeakKeyDictionary" = \
            weakref.WeakKeyDictionary()
        self._exe_id = str(next(_EXE_IDS))
        self._m_hits = _M_LOOKUPS.labels(exe=self._exe_id, result="hit")
        self._m_misses = _M_LOOKUPS.labels(exe=self._exe_id,
                                           result="miss")
        self._m_compile_s = _M_COMPILE_S.labels(exe=self._exe_id)
        self._m_recompiles = _M_RECOMPILES.labels(exe=self._exe_id)
        self._m_state_commits = _M_STATE_COMMITS.labels(exe=self._exe_id)
        self._m_aux_dispatches = _M_AUX_DISPATCHES.labels(exe=self._exe_id)
        self._m_entries = _M_ENTRIES.labels(exe=self._exe_id)
        self._warm_fps: set = set()
        self._clock = run_clock()
        compile_cache_dir()

    def slow_steps(self) -> List[dict]:
        """The newest 8 steps that took over twice the reference
        period, oldest first, kept with tracing on or off: where each
        went (`phase` of `outside`, `feed`, `dispatch`, `fetch`) and
        whether this thread worked, waited or was taken off the CPU
        (docs/observability.md "How a slow record reads")."""
        return [dict(r) for r in self._clock.slow]

    def cache_stats(self) -> Dict:
        """Dispatch/compile telemetry for this Executor's executable cache:
        `hits`/`misses` (cache lookups across compiled + segmented modes),
        `compile_s` (wall time of first invocations, i.e. trace + XLA
        compile + first dispatch), `entries` (live executables),
        `recompiles_after_warmup` — misses for a program that already had
        a steady-state hit, the signature of a shape/flag leak re-tracing
        the hot path (PADDLE_TPU_LOG_RECOMPILES=1 also warns per event) —
        and `state_commits`, the persistable states compiled steps put
        through `_commit`: a step that finds every state as the last one
        left it adds none, so a count that grows with the steps names a
        loop that replaces states or keeps NumPy values in the scope
        (docs/performance.md); `aux_dispatches`, the executables the
        runs sent to the device besides the steps' own: two a run that
        made its step key outside the step (the interpreted and
        segmented modes), none for a compiled one.

        A view over this instance's series in the process metrics
        registry (exported with everything else by
        observability.exporters; see docs/observability.md)."""
        return {"hits": int(self._m_hits.value),
                "misses": int(self._m_misses.value),
                "compile_s": self._m_compile_s.value,
                "recompiles_after_warmup": int(self._m_recompiles.value),
                "state_commits": int(self._m_state_commits.value),
                "aux_dispatches": int(self._m_aux_dispatches.value),
                "entries": len(self._cache)}

    def _note_lookup(self, hit: bool, fp, cache_key, once=None) -> None:
        """`once`: per-run set deduping the recompile counter/warning —
        a segmented run looks up one executable per device segment, but
        one odd-shaped batch is ONE hot-path re-trace, not k."""
        if hit:
            self._m_hits.inc()
            self._warm_fps.add(fp)
            return
        self._m_misses.inc()
        if fp in self._warm_fps and (once is None or fp not in once):
            if once is not None:
                once.add(fp)
            self._m_recompiles.inc()
            if get_flag("log_recompiles"):
                warnings.warn(
                    "Executor recompile after warmup: program fingerprint "
                    f"{fp[:12]}… missed the executable cache with key "
                    f"{cache_key!r} — a feed shape/dtype/LoD or trace-time "
                    "flag changed on the hot path (consider length "
                    "bucketing; see docs/performance.md)",
                    RuntimeWarning, stacklevel=4)

    # -- public API ----------------------------------------------------------
    def run(
        self,
        program: Optional[Program] = None,
        feed: Optional[Dict] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        compiled: Optional[bool] = None,
    ):
        """Execute block 0 of `program`.  Mirrors reference
        python/paddle/v2/fluid/executor.py:221 (feed/fetch are handled by the
        executor directly instead of injected feed/fetch ops)."""
        clock = self._clock
        clock.mark("outside")
        program = program or default_main_program()
        scope = scope or global_scope()
        feed = feed or {}
        fetch_names = [
            v.name if isinstance(v, Variable) else str(v)
            for v in (fetch_list or [])
        ]
        # static pre-flight (PADDLE_TPU_VERIFY=warn|error, default off —
        # preflight gates internally): catch bad graphs in ms instead of
        # minutes into a trace; cached per (program, version) so
        # steady-state loops pay one flag read + dict probe
        from ..analysis import preflight

        preflight(program, feed_names=feed.keys(),
                  fetch_names=fetch_names)
        block = program.global_block()

        # jit granularity (flag, docs/performance.md): 'block' = default
        # whole-block executables; 'segment' = the segment cache even for
        # pure-device programs; 'op' = the eager interpreter whose tiny
        # per-op kernels are cached by jax ACROSS programs — the coarse
        # compile-time escape hatch.  An explicit `compiled` arg wins.
        gran = str(get_flag("jit_granularity") or "block").lower()
        # what the last compiled run of this very call left behind
        # (_StepRecord), taken OUT of the table: only a step that ends
        # well puts it back, so a failed one leaves nothing half-updated
        record_key = (program._version, block.idx, tuple(feed),
                      tuple(fetch_names), bool(get_flag("memory_optimize")),
                      trace_flags())
        record = self._take_step_record(scope, program, record_key)
        # asked once, and only where the mode hangs on it; a record says
        # no without a walk over the ops: only a compiled run leaves one
        host_ops = (record is None
                    and (gran != "op" if compiled is None else compiled)
                    and self._has_host_ops(block))
        if compiled is None:
            if gran == "op":
                compiled = False
            elif not host_ops:
                compiled = True
        # the step's key is fold_in(key(seed), step).  A compiled step
        # takes the two numbers and makes the key itself (a Program that
        # draws nothing compiles it away); the other modes make it
        # here, as two dispatches to the device (`_outside_step_key`)
        seed, step = program.seed or self._seed, self._step
        self._step += 1

        if compiled:
            # host ops can't be jit-traced: "compiled" with host ops
            # means compile the maximal device segments between them
            mode = ("segmented"
                    if host_ops or gran == "segment"
                    else "compiled")
        elif compiled is None:
            # host ops present (else compiled was defaulted True above):
            # compile maximal device segments, interpret host ops
            # eagerly between them
            mode = "segmented"
        else:
            mode = "interpreted"
        if mode != "compiled" and any(
                getattr(block.vars.get(n), "donate", False) for n in feed):
            # the donate=True build-time guarantee holds on EVERY path:
            # the interpreter/segmented modes cannot fulfill a donation
            # (no jitted step), but an unsafe hint must still fail here
            # — not later, when the same program first hits the
            # compiled path in production
            self._donation_plan(program, feed.keys(), fetch_names, ())
        if get_flag("memory_optimize") and mode != "compiled":
            # liveness rename pass (buffer reuse on the interpreter
            # paths) applied to a cached CLONE keyed by (program, feed,
            # fetch): the caller's program is never mutated, and a later
            # run with a different fetch list gets its own clone with
            # THOSE names protected — fetch values can never be
            # silently clobbered by a rename from an earlier call
            program = self._memopt_program(program, feed.keys(),
                                           fetch_names)
            block = program.global_block()
        t0 = time.perf_counter()
        # children in compiled mode: executor.feed, executor.dispatch
        # (_run_compiled) and, below, executor.fetch
        with obs_tracing.span("executor.run", mode=mode) as run_span:
            aux = self._m_aux_dispatches.value if run_span is not None else 0
            if mode == "segmented":
                outs = self._run_segmented(
                    program, block, scope, feed, fetch_names,
                    self._outside_step_key(seed, step)
                )
            elif mode == "compiled":
                try:
                    outs = self._run_compiled(
                        program, block, scope, feed, fetch_names, seed, step,
                        record_key, record
                    )
                except _MissingState as e:
                    raise RuntimeError(
                        f"persistable variable {e.args[0]!r} has no value "
                        "in scope — run the startup program first"
                    ) from None
            else:
                outs = self._run_interpreted(
                    program, block, scope, feed, fetch_names,
                    self._outside_step_key(seed, step)
                )
            if run_span is not None:
                run_span.set_attr(
                    "aux_dispatches",
                    int(self._m_aux_dispatches.value - aux))
            clock.mark("dispatch")
            if obs_metrics.enabled():
                _M_RUN_SECONDS.labels(
                    exe=self._exe_id, mode=mode).observe(
                        time.perf_counter() - t0)
            if return_numpy:
                # the wait for the device: the step's results are read
                # (`np.asarray` of a device array queues its copy to the
                # host behind the step that makes it at once and then
                # waits: starting the copy earlier gains nothing)
                with obs_tracing.span("executor.fetch"):
                    outs = [_to_numpy(v) for v in outs]
            end_run(clock, run_span)
        return outs

    def _outside_step_key(self, seed, step):
        """The step's key made on the host's side of the step, for the
        modes that run ops eagerly: `key(seed)` and `fold_in` are each
        an executable of their own sent to the device, which
        `aux_dispatches` counts."""
        self._m_aux_dispatches.inc(2)
        return step_key(seed, step)

    def close(self):
        self._cache.clear()
        self._step_records.clear()
        self._m_entries.set(0)
        # reclaim this instance's registry series (cache_stats() keeps
        # reading the held child objects); processes that churn
        # Executors must not grow every dump without bound
        _M_LOOKUPS.remove(exe=self._exe_id, result="hit")
        _M_LOOKUPS.remove(exe=self._exe_id, result="miss")
        for fam in (_M_COMPILE_S, _M_RECOMPILES, _M_STATE_COMMITS,
                    _M_AUX_DISPATCHES, _M_ENTRIES):
            fam.remove(exe=self._exe_id)
        for mode in ("interpreted", "segmented", "compiled"):
            _M_RUN_SECONDS.remove(exe=self._exe_id, mode=mode)

    # -- memory optimization (flag `memory_optimize`) ------------------------
    def _memopt_program(self, program, feed_names, fetch_names):
        """Memory-optimized clone of `program` for one (feed, fetch)
        config, cached: the liveness rename pass runs with the live
        feed/fetch lists auto-skipped, on a deep copy — the user's
        program stays untouched."""
        key = (self._fingerprint(program), tuple(sorted(feed_names)),
               tuple(fetch_names))
        clone = self._memopt_cache.get(key)
        if clone is None:
            from ..memory_optimization_transpiler import memory_optimize

            clone = program.clone()
            memory_optimize(clone,
                            skip_vars=list(feed_names)
                            + list(fetch_names))
            _bounded_put(self._memopt_cache, key, clone,
                         cap=_MEMOPT_CACHE_CAP)
        return clone

    def _free_plan(self, program, fetch_names):
        """Cached {op index -> dead names} for the interpreter/segmented
        paths (memory_optimization_transpiler.plan_dead_frees)."""
        key = (self._fingerprint(program), tuple(fetch_names))
        plan = self._free_plans.get(key)
        if plan is None:
            from ..memory_optimization_transpiler import plan_dead_frees

            plan = plan_dead_frees(program, fetch_names)
            _bounded_put(self._free_plans, key, plan,
                         cap=_PLAN_CACHE_CAP)
        return plan

    def _donation_plan(self, program, feed_names, fetch_names, rw_names):
        """Cached liveness donation plan for one (program, feeds, fetch,
        states) config; raises DonationError for unsafe explicit
        `donate` hints (build time — before any tracing)."""
        key = (self._fingerprint(program), tuple(sorted(feed_names)),
               tuple(fetch_names), tuple(sorted(rw_names)))
        plan = self._donation_plans.get(key)
        if plan is None:
            from ..memory_optimization_transpiler import plan_donation

            block = program.global_block()
            hinted = [n for n in feed_names
                      if n in block.vars
                      and getattr(block.vars[n], "donate", False)]
            plan = plan_donation(program, feed_names, fetch_names,
                                 state_rw_names=rw_names, requested=hinted)
            _bounded_put(self._donation_plans, key, plan,
                         cap=_PLAN_CACHE_CAP)
        return plan.check()

    # -- interpreter ---------------------------------------------------------
    def _has_host_ops(self, block) -> bool:
        return any(self._op_is_host(op) for op in block.ops)

    def _scope_env(self, program, scope, local):
        """ScopeEnv routing persistable writes to the root scope
        (executor.cc:88-117); shared by interpreted and segmented modes."""
        persistable = {v.name for v in program.list_vars() if v.persistable}
        root = scope
        while root.parent is not None:
            root = root.parent

        class _Env(ScopeEnv):
            def get(self, name):
                v = super().get(name)
                if v is None and name in persistable \
                        and name not in self.written:
                    # same diagnosis the compiled path gives via
                    # _MissingState — not a raw op-level AttributeError
                    raise RuntimeError(
                        f"persistable variable {name!r} has no value in "
                        "scope — run the startup program first")
                return v

            def set(self, name, value):
                if name in persistable:
                    root.set_var(name, value)
                else:
                    self.scope.set_var(name, value, local=True)
                self.written.add(name)

        return _Env(local)

    @staticmethod
    def _fetch(env, fetch_names):
        missing = [n for n in fetch_names if not env.has(n)]
        if missing:
            raise KeyError(
                f"fetch variable(s) {missing} were never produced by "
                "the program")
        return [env.get(n) for n in fetch_names]

    def _run_interpreted(self, program, block, scope, feed, fetch_names, key):
        device = self.place.jax_device()
        local = scope.new_scope()
        # dead-var freeing (memory_optimize flag): drop the local-scope
        # reference of every var right after its liveness-proven last
        # use, so footprint tracks LIVE values, not program size
        frees = (self._free_plan(program, fetch_names)
                 if get_flag("memory_optimize") else None)
        try:  # finally: a raising op must not leak the local scope
            env = self._scope_env(program, scope, local)
            with jax.default_device(device):
                for name, v in feed.items():
                    env.set(name, _to_device_value(v, device))
                ctx = ExecContext(key, scope=local, executor=self)
                for i, op in enumerate(block.ops):
                    _run_op_instrumented(ctx, op, env)
                    if frees:
                        for n in frees.get(i, ()):
                            local.erase(n)
                outs = self._fetch(env, fetch_names)
        finally:
            scope.kids.remove(local)
        return outs

    # -- segmented: compiled device segments between eager host ops ---------
    def _op_is_host(self, op) -> bool:
        try:
            info = registry.get_op_info(op.type)
        except KeyError:
            return True
        if info.host:
            return True
        if op.attrs.get("force_cpu"):
            # init_on_cpu(): keep the op out of compiled device programs
            # (its numpy result stays in host memory)
            return True
        sub = op.sub_block() if "sub_block" in op.attrs else None
        return sub is not None and self._has_host_ops(sub)

    def _segments(self, block):
        """Split ops into maximal (is_host, [ops]) runs."""
        segs = []
        for op in block.ops:
            h = self._op_is_host(op)
            if segs and segs[-1][0] == h:
                segs[-1][1].append(op)
            else:
                segs.append((h, [op]))
        return segs

    def _run_segmented(self, program, block, scope, feed, fetch_names, key):
        """Interpreter-shaped env, but each maximal run of non-host ops is
        traced+jitted once and cached — host ops (save/load/print/metrics)
        run eagerly between compiled segments.  The per-op PRNG keys are
        derived from op identity (execution.py:_op_rng_tag), so randomness
        is identical across interpreted/compiled/segmented modes."""
        device = self.place.jax_device()
        local = scope.new_scope()
        # dead-var freeing at segment granularity (memory_optimize flag):
        # names whose last use falls inside a segment are dropped from
        # the local scope once that segment completes
        frees = (self._free_plan(program, fetch_names)
                 if get_flag("memory_optimize") else None)
        try:  # finally: a raising op must not leak the local scope
            env = self._scope_env(program, scope, local)
            fp = self._fingerprint(program)
            with jax.default_device(device):
                for name, v in feed.items():
                    env.set(name, _to_device_value(v, device))
                ctx = ExecContext(key, scope=local, executor=self)
                once = set()  # one recompile count per run, not per seg
                op_idx = 0
                for seg_idx, (is_host, ops) in enumerate(
                        self._segments(block)):
                    if is_host:
                        for op in ops:
                            _run_op_instrumented(ctx, op, env)
                    else:
                        self._run_segment_compiled(fp, seg_idx, ops, env,
                                                   key, device, once)
                    if frees:
                        for i in range(op_idx, op_idx + len(ops)):
                            for n in frees.get(i, ()):
                                local.erase(n)
                    op_idx += len(ops)
                outs = self._fetch(env, fetch_names)
        finally:
            scope.kids.remove(local)
        return outs

    def _run_segment_compiled(self, fp, seg_idx, ops, env, key, device,
                              once=None):
        # names this segment reads from the surrounding env
        read, written = [], set()
        for op in ops:
            for n in op.input_names():
                if n not in written and n not in read and env.has(n):
                    read.append(n)
            written.update(op.output_names())
        repl = _dp_replicated_sharding(ops)
        in_vals = {n: _commit(env.get(n), repl if repl is not None else device)
                   for n in read}
        cache_key = (
            fp, "seg", seg_idx,
            tuple((n, _aval_key(v)) for n, v in sorted(in_vals.items())),
            trace_flags(),
        )
        fn = self._cache.get(cache_key)
        miss = fn is None
        self._note_lookup(not miss, fp, cache_key, once)
        if miss:
            def fn(vals, rng_key, _ops=tuple(ops)):
                seg_env = DictEnv(vals)
                seg_ctx = ExecContext(rng_key, executor=self, compiled=True)
                for op in _ops:
                    run_op(seg_ctx, op, seg_env)
                return {n: seg_env.d[n] for n in seg_env.written
                        if n in seg_env.d}
            if repl is not None:
                fn = jax.jit(fn, in_shardings=(repl, repl))
            else:
                fn = jax.jit(fn)
            self._cache[cache_key] = fn
        from paddle_tpu import profiler

        t0 = time.perf_counter() if miss else None
        if profiler.is_enabled():
            with profiler.record_event(f"xla_segment_{seg_idx}"):
                out = fn(in_vals, key)
                jax.block_until_ready(out)
        else:
            out = fn(in_vals, key)
        if miss:
            self._m_compile_s.inc(time.perf_counter() - t0)
            self._m_entries.set(len(self._cache))
        for n, v in out.items():
            env.set(n, v)

    # -- compiled ------------------------------------------------------------
    def _fingerprint(self, program) -> str:
        ent = self._fp_cache.get(program)
        if ent is not None and ent[0] == program._version:
            return ent[1]
        fp = program.fingerprint()
        self._fp_cache[program] = (program._version, fp)
        return fp

    @staticmethod
    def _analyze_states(program, block, feed_names):
        """Persistable vars read (before being written) and written by ops."""
        persistable = {v.name for v in program.list_vars() if v.persistable}

        def visit(blk, written, reads, writes):
            for op in blk.ops:
                for n in op.input_names():
                    if n in persistable and n not in written:
                        reads.add(n)
                sub = op.sub_block() if "sub_block" in op.attrs else None
                if sub is not None:
                    visit(sub, written, reads, writes)
                for n in op.output_names():
                    if n in persistable:
                        writes.add(n)
                        written.add(n)

        reads, writes = set(), set()
        visit(block, set(feed_names), reads, writes)
        return sorted(reads), sorted(writes)

    def _take_step_record(self, scope, program, record_key):
        try:
            return self._step_records[scope][program].pop(record_key, None)
        except KeyError:
            return None

    def _keep_step_record(self, scope, program, record_key, record):
        by_program = self._step_records.setdefault(
            scope, weakref.WeakKeyDictionary())
        _bounded_put(by_program.setdefault(program, {}), record_key, record,
                     cap=_PLAN_CACHE_CAP)

    def _new_step_record(self, program, block, feed_names, fetch_names,
                         device):
        rec = _StepRecord(device, trace_flags(), feed_names,
                          *self._analyze_states(program, block, feed_names),
                          _dp_replicated_sharding(block.ops))
        # liveness donation plan (memory_optimization_transpiler): which
        # buffers die inside this step.  Read-write states are always
        # donated (the in-place param update); feed buffers are donated
        # under the memory_optimize flag or an explicit per-var `donate`
        # hint — but only when the executor itself created the device
        # buffer (`fresh`), so a caller-held array is never invalidated.
        # Unsafe explicit hints raise DonationError here, at build time.
        plan = self._donation_plan(program, feed_names, fetch_names,
                                   rec.rw_names)
        rec.donatable = plan.feeds if get_flag("memory_optimize") else {
            n for n in plan.feeds
            if n in block.vars and getattr(block.vars[n], "donate", False)}
        return rec

    def _run_compiled(self, program, block, scope, feed, fetch_names, seed,
                      step, record_key, rec):
        """`rec`: the `_StepRecord` `run` took out of the table for
        this call, or None; it goes back under `record_key` only when
        the step has run to its end.  `seed` and `step` are what the
        step makes its key from."""
        device = self.place.jax_device()
        # executor.feed: feeds placed on the device, states committed
        with obs_tracing.span("executor.feed") as feed_span:
            feed_vals, fresh = {}, set()
            for n, v in feed.items():
                feed_vals[n], is_fresh = _place_feed(v, device)
                if is_fresh:
                    fresh.add(n)
            if rec is None or rec.device != device:
                rec = self._new_step_record(program, block, feed_vals.keys(),
                                            fetch_names, device)
            don_names = tuple(n for n in rec.feed_names
                              if n in rec.donatable and n in fresh)
            ro, n_ro = rec.gather(rec.ro_names, scope)
            rw, n_rw = rec.gather(rec.rw_names, scope)
            if n_ro + n_rw:
                self._m_state_commits.inc(n_ro + n_rw)
            if feed_span is not None:
                feed_span.set_attr("states", len(rec.state_in_names))
                feed_span.set_attr("recommitted", n_ro + n_rw)
        self._clock.mark("feed")
        # executor.dispatch: cache lookup and the jitted call
        with obs_tracing.span("executor.dispatch"):
            feed_keys = tuple((n, _aval_key(feed_vals[n]))
                              for n in rec.feed_names)
            if (rec.cache_key is None or feed_keys != rec.feed_keys
                    or don_names != rec.don_names):
                keys = rec.keys
                rec.feed_keys, rec.don_names = feed_keys, don_names
                rec.keep_names = tuple(n for n in rec.feed_names
                                       if n not in don_names)
                rec.cache_key = (
                    self._fingerprint(program),
                    block.idx,
                    feed_keys,
                    tuple((n, keys[n]) for n in rec.ro_names),
                    tuple((n, keys[n]) for n in rec.rw_names),
                    tuple(fetch_names),
                    str(device),
                    don_names,  # donation is baked into the executable
                    rec.flags,
                )
                rec.fn = _NO_FN
            # no state's key and no feed's moved: the last call's tuple
            # and the executable found under it (a tuple of several
            # hundred tuples is hashed anew at every lookup)
            cache_key, fn = rec.cache_key, rec.fn()
            if fn is None:
                fn = self._cache.get(cache_key)
            miss = fn is None
            self._note_lookup(not miss, cache_key[0], cache_key)
            if miss:
                fn = self._build_compiled_fn(
                    block, fetch_names, rec.state_out_names,
                    (don_names, rec.keep_names, rec.ro_names, rec.rw_names),
                    rec.repl
                )
                self._cache[cache_key] = fn
            rec.fn = weakref.ref(fn)
            # the step's arguments in the record's order, as tuples: a
            # dictionary of several hundred states is sorted by name at
            # every call of a jitted function, a tuple is walked
            args = (tuple(feed_vals[n] for n in don_names),
                    tuple(feed_vals[n] for n in rec.keep_names),
                    ro, rw, _seed_word(seed), np.uint32(step))
            from paddle_tpu import profiler

            if miss:
                # device time by scope: hlo_scopes() can read this
                # executable's compiled text later (shapes, no buffers)
                profiler.register_jitted("executor.block", fn, *args)
            t0 = time.perf_counter() if miss else None
            if profiler.is_enabled():
                with profiler.record_event("xla_block"):
                    fetches, state_out = fn(*args)
                    jax.block_until_ready((fetches, state_out))
            else:
                fetches, state_out = fn(*args)
            if miss:
                self._m_compile_s.inc(time.perf_counter() - t0)
                self._m_entries.set(len(self._cache))
            for n, v in state_out.items():
                scope.set_var(n, v)
            rec.written(cache_key, state_out)
            self._keep_step_record(scope, program, record_key, rec)
        return fetches

    def _build_compiled_fn(self, block, fetch_names, state_out_names, names,
                           repl=None):
        """The jitted step.  `names`: the names of its first four
        arguments' values (donated feeds, kept feeds, read-only states,
        read-write states), each a tuple in that order; then the seed
        and the step count, two traced scalars the key is made from."""
        don_names, keep_names, ro_names, rw_names = map(tuple, names)

        def fn(don_feeds, keep_feeds, ro, rw, seed, step):
            env = DictEnv(zip(ro_names + rw_names + keep_names + don_names,
                              ro + rw + keep_feeds + don_feeds))
            ctx = ExecContext(step_key(seed, step), executor=self,
                              compiled=True)
            for op in block.ops:
                run_op(ctx, op, env)
            fetches = [env.get(n) for n in fetch_names]
            state_out = {
                n: env.d[n]
                for n in state_out_names
                if n in env.written and n in env.d
            }
            return fetches, state_out

        # donation plan (core/executor._run_compiled): arg 0 carries the
        # liveness-dead feed buffers, arg 3 the read-write states whose
        # old values die with the in-place update — XLA reuses both HBM
        # regions for intermediates/outputs
        if repl is not None:
            # a parallel_do op constrains values to a multi-device mesh:
            # land every input replicated on that device set so the
            # partitioner may shard the annotated subgraph (single-device
            # committed args would conflict with the mesh)
            return jax.jit(fn, donate_argnums=(0, 3),
                           in_shardings=(repl,) * 6)
        return jax.jit(fn, donate_argnums=(0, 3))


def program_to_fn(program: Program, feed_names, fetch_names, block_idx=0):
    """Expose a Program block as a pure jax function
    `(feeds, states, rng_key) -> (fetches, new_states)` for direct use with
    jax transforms (jit/pjit/shard_map) — the bridge used by
    __graft_entry__ and the parallel package."""
    block = program.blocks[block_idx]
    state_in, state_out = Executor._analyze_states(program, block, feed_names)

    def fn(feeds, states, rng_key):
        env = DictEnv({**states, **feeds})
        ctx = ExecContext(rng_key, compiled=True)
        for op in block.ops:
            run_op(ctx, op, env)
        fetches = {n: env.get(n) for n in fetch_names}
        # pass read-only states through so callers can loop
        # `states = fn(...)[1]` without re-merging
        new_states = {
            n: env.d[n]
            for n in sorted(set(state_in) | set(state_out))
            if n in env.d
        }
        return fetches, new_states

    fn.state_in_names = state_in
    fn.state_out_names = state_out
    # liveness donation plan for callers that jit this fn themselves
    # (parallel.ParallelExecutor, perf/rehearse_compile.py): which feed
    # buffers die inside the step, and therefore may ride donate_argnums
    from ..memory_optimization_transpiler import plan_donation

    rw = [n for n in state_in if n in state_out]
    fn.donation_plan = plan_donation(program, feed_names, fetch_names,
                                     state_rw_names=rw).check()
    return fn


def switch_scope(scope: Scope) -> Scope:
    """Replace the global scope, returning the previous one (reference
    executor.py switch_scope / pybind _switch_scope)."""
    global _global_scope
    prev = _global_scope
    _global_scope = scope
    return prev


class scope_guard:
    """`with fluid.scope_guard(scope): ...` — run with a different global
    scope (reference executor.py scope_guard)."""

    def __init__(self, scope: Scope):
        self._scope = scope
        self._prev = None

    def __enter__(self):
        self._prev = switch_scope(self._scope)
        return self._scope

    def __exit__(self, *exc):
        switch_scope(self._prev)
        return False
