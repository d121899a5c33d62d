"""Runtime flag registry — the gflags analogue.

Reference: the reference scatters `DEFINE_bool/int32/double` through the
C++ (utils/Flags.cpp:18-85 legacy; executor.cc:29-32 FLAGS_benchmark /
FLAGS_check_nan_inf) and plumbs python argv via `core.init_gflags`
(framework/init.cc).  Here flags are a simple process-global registry,
settable from code (`set_flags`) or `PADDLE_TPU_<NAME>` environment
variables at import.
"""
from __future__ import annotations

import os
from typing import Any, Dict

_DEFS: Dict[str, Any] = {}
_VALUES: Dict[str, Any] = {}
_ON_CHANGE: Dict[str, list] = {}


def on_flag_change(name: str, callback):
    """Register `callback()` to run whenever `set_flags` touches `name` —
    for flags that must take effect immediately rather than at the next
    consumer read (e.g. trace_dir arming the tracer)."""
    _ON_CHANGE.setdefault(name, []).append(callback)


def define_flag(name: str, default, help_str: str = ""):
    _DEFS[name] = (default, help_str)
    env = os.environ.get("PADDLE_TPU_" + name.upper())
    if env is not None:
        if isinstance(default, bool):
            _VALUES[name] = env.lower() in ("1", "true", "yes", "on")
        elif isinstance(default, int):
            _VALUES[name] = int(env)
        elif isinstance(default, float):
            _VALUES[name] = float(env)
        else:
            _VALUES[name] = env
    else:
        _VALUES[name] = default


def get_flag(name: str):
    return _VALUES[name]


def set_flags(flags: Dict[str, Any]):
    for k, v in flags.items():
        if k not in _DEFS:
            raise KeyError(f"unknown flag {k!r}; defined: {sorted(_DEFS)}")
        _VALUES[k] = v
    for k in flags:
        for cb in _ON_CHANGE.get(k, ()):
            cb()


def flag_defaults():
    return {k: d for k, (d, _) in _DEFS.items()}


# The flags read while a step is traced or built.  Flipping one leaves
# the input avals as they were, so an executable made under one set of
# values would silently serve another: Executor puts trace_flags() in
# both of its cache keys, ParallelExecutor and PipelineExecutor rebuild
# their jitted step when it changes.  A flag that changes the trace is
# added HERE and nowhere else (tests/test_trace_flags.py flips each).
TRACE_FLAGS = ("amp_bf16", "flash_min_seq_k")
# read when the parallel executors build their step: feed donation and
# the overlap step's gradient buckets (the serial Executor keys on the
# donated names themselves)
PARALLEL_TRACE_FLAGS = TRACE_FLAGS + ("memory_optimize",
                                      "overlap_bucket_bytes")


def trace_flags(parallel: bool = False) -> tuple:
    names = PARALLEL_TRACE_FLAGS if parallel else TRACE_FLAGS
    return tuple(_VALUES[n] for n in names)


# -- the reference's executor/debug flags -----------------------------------
define_flag("check_nan_inf", False,
            "scan every op output for nan/inf in interpreter mode "
            "(executor.cc FLAGS_check_nan_inf)")
define_flag("benchmark", False,
            "per-op sync + timing logs (executor.cc FLAGS_benchmark)")
define_flag("amp_bf16", False,
            "mixed precision: whitelisted MXU ops (mul/matmul/conv) cast "
            "float32 operands to bfloat16; optimizer ops keep float32 "
            "master params (dtype promotion upcasts bf16 grads)")
define_flag("flash_min_seq_k", -1,
            "override the flash-attention Pallas/XLA crossover for ops "
            "that did not set min_seq_k explicitly: -1 = kernel policy "
            "default (~2k), 0 = always use the Pallas kernel.  Below the "
            "crossover the XLA composition is faster for ISOLATED "
            "attention, but in a full training step it materializes "
            "scores+probs (f32 after the softmax upcast) for backward — "
            "at large d_model that dominates HBM traffic and memory, so "
            "training runs force the kernel.  Read at TRACE time: one of "
            "trace_flags()")
define_flag("log_recompiles", False,
            "warn (RuntimeWarning) whenever the Executor misses its "
            "executable cache for a program that already reached "
            "steady-state (had a cache hit) — the signature of a feed "
            "shape/dtype/LoD or trace-time-flag leak re-tracing the hot "
            "path.  Counted unconditionally in Executor.cache_stats()"
            "['recompiles_after_warmup']")
define_flag("verify", "off",
            "static program verification before execution "
            "(paddle_tpu.analysis): 'off' = skip; 'warn' = run every "
            "registered analysis pass and RuntimeWarning on "
            "error/warning diagnostics; 'error' = additionally raise "
            "ProgramVerificationError on error-severity diagnostics.  "
            "Applies to Executor, ParallelExecutor, PipelineExecutor "
            "and io.load_inference_model; results are cached per "
            "(program, version) so steady-state loops verify once.  "
            "Explicit Program.verify(level=...) calls ignore this flag")
define_flag("sync_every_n", 1,
            "default Trainer.train fetch-sync cadence: K > 1 hands "
            "EndIteration a LazyFetch cost (device->host copy deferred "
            "until read) and fences the dispatch queue every K steps; "
            "1 materializes every step.  Per-call "
            "override: Trainer.train(sync_every_n=K)")
define_flag("metrics", False,
            "arm the observability metrics instruments "
            "(paddle_tpu.observability.metrics): counters/gauges/"
            "histograms over the executor, trainer, reader pipeline, "
            "serving, pserver transport and resilience hot paths.  Off "
            "(default): every instrument is a boolean-test no-op; "
            "telemetry-API metrics (Executor.cache_stats, "
            "InferenceServer.stats) count regardless.  Export via "
            "observability.exporters (Prometheus text / HTTP / JSON) "
            "or PADDLE_TPU_METRICS_DUMP=<path> at exit")
define_flag("trace_dir", "",
            "directory for Chrome-trace JSON dumps "
            "(paddle_tpu.observability.tracing): setting it enables "
            "span recording (trace/span/parent ids, propagated over "
            "the pserver wire protocol and to worker threads) and "
            "auto-writes trace_<pid>.json at process exit — open in "
            "chrome://tracing or Perfetto (docs/observability.md)")
define_flag("comm_bucket_bytes", 4 << 20,
            "size cap (bytes) for fused pserver transfers: send ops "
            "pack grads into arrival-order buckets (DDP-style) and "
            "ship each bucket as ONE SEND_BATCH frame "
            "(parallel/comm.py + parallel/pserver.py).  0 disables "
            "fusion — every var goes in its own legacy SEND frame "
            "(the pre-bucketing wire path; also the automatic "
            "fallback against a server that predates the batch "
            "verbs).  An oversized var still ships, alone in its "
            "bucket")
define_flag("overlap_bucket_bytes", 4 << 20,
            "size cap (bytes) for the compute/collective-overlap "
            "gradient buckets of the spmd path (docs/performance.md "
            "'Multichip sharding'): ParallelExecutor(overlap="
            "'bucketed'|'auto') concatenates parameter gradients in "
            "production (backward) order into buckets of at most this "
            "many bytes and issues ONE lax.psum per bucket, so early "
            "buckets' all-reduces overlap with the remaining backward "
            "compute (DDP-style).  0 puts every gradient in its own "
            "bucket; the bucket count is pinned structurally via "
            "compiled_collectives")
define_flag("memory_optimize", False,
            "whole-program memory optimization "
            "(memory_optimization_transpiler + docs/performance.md "
            "'Memory'): the Executor derives a liveness-backed donation "
            "plan and donates every feed buffer whose last use is "
            "inside the jitted step (read-write state donation is "
            "always on), frees dead local-scope vars between ops/"
            "segments on the interpreter paths, and applies the "
            "liveness rename pass (buffer reuse) to interpreted/"
            "segmented programs, auto-skipping the current feed and "
            "fetch lists.  The rename runs on a cached clone — the "
            "caller's Program is never mutated — and re-keys per-op "
            "PRNG streams of renamed temporaries: same distribution, "
            "different draws than the unrenamed program")
define_flag("remat", False,
            "default rematerialization for model builders that accept "
            "remat=None (models.resnet, models.transformer): wrap each "
            "residual/attention block in layers.recompute "
            "(jax.checkpoint) so block-internal activations re-run in "
            "backward instead of living in HBM — the bytes-for-FLOPs "
            "trade of Chen et al. (sublinear memory cost).  Read at "
            "BUILD time (program construction), not trace time")
define_flag("jit_granularity", "block",
            "how much program one executable covers: 'block' (default) "
            "traces whole block 0 into one XLA program; 'segment' "
            "compiles maximal device segments (the mode host ops "
            "already force) even for pure-device programs; 'op' runs "
            "the eager interpreter — each jax op compiles tiny "
            "kernels cached ACROSS programs, the coarse-compile "
            "escape hatch when whole-program XLA compile time "
            "dominates short runs (docs/performance.md).  An explicit "
            "Executor.run(compiled=...) argument overrides it")
