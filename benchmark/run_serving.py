"""Open-loop mixed-length generation serving load: scheduling AND the
algorithmic serving optimizations, measured one ablation at a time.

The generator is OPEN-LOOP: request arrival times come from the rate
schedule alone (never from completions), which is what exposes a
serving architecture's real saturation behavior — a closed loop slows
its own arrivals down exactly when the server struggles and hides the
collapse.  The request mix is deliberately mixed-length (mostly short
answers plus a tail of long ones): under drain-then-refill scheduling
every batch runs at the speed of its LONGEST member, which is exactly
the pathology continuous batching removes (finished sequences leave
immediately and queued requests take their slots between ticks).

On top of the PR 8 static-vs-continuous comparison this bench drives
the SHARED-PREFIX workload (a configurable pool of system prompts +
hit ratio — the millions-of-users shape) through the ablation ladder:

  static_batch   drain-then-refill baseline
  continuous     PR 8 scheduling (prefix cache off, no draft)
  prefix         + block-level prefix caching
  spec           + speculative decoding (draft model)
  prefix+spec    both
  kernels        + the Pallas serving-kernel tier (serving_kernels=on:
                 fused paged-attention decode instead of the XLA
                 gather composition; interpret mode off-TPU, so the
                 CPU row demonstrates the PATH and its bit-identical
                 numerics, not kernel speed — the speed argument is
                 the static roofline section below)

Every row runs the same request set and reports sustained tokens/s,
p50/p99 request latency, shed rate, peak/mean KV-pool utilization,
prefix-cache hit rate, draft accept rate, and peak resident sequences.
Speculative rows TRAIN the target and a smaller draft briefly on a
cyclic-motif stream first (a random-init draft agrees with a
random-init target at ~1/vocab — no real serving deployment runs an
untrained draft, and the accept rate is the whole mechanism).

A final section sizes KV QUANTIZATION: same device byte budget, pool
blocks re-derived per kv_dtype, long-lived requests — reporting how
many sequences each precision holds resident at once.

The ROOFLINE section closes the loop on the serving-kernel tier:
before/after static rows for the decode step (XLA gather composition
vs fused Pallas paged attention) on the quantized-KV mix, plus a
static_vs_measured calibration of the kernel-backed estimates against
XLA's per-step cost analysis (band: flops [0.5, 2.5]x, bytes
[0.4, 3]x — tests/test_cost_model.py's documented tolerance).

Usage: python benchmark/run_serving.py [--requests 48] [--rate 0]
       [--slots 4] [--kv-blocks 56] [--block-size 8] [--d-model 128]
       [--layers 2] [--heads 4] [--prefix-pool 3] [--prefix-len 24]
       [--prefix-hit 0.75] [--spec-k 4] [--no-spec] [--no-quant]
       [--no-kernels] [--prom_out serving_prom.txt]
(--rate 0 = saturation: the whole request set arrives up front.)
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np

VOCAB = 211
MOTIF = [3, 17, 42, 9, 88, 120, 5, 61, 199, 14, 73]


def _train_lm(d_model, n_layers, n_heads, max_len, iters=120, lr=3e-3,
              batch=8, seed=0):
    """Teach one decoder-only LM the cyclic motif (teacher-forced next-
    token loss) and return its trained state dict, extracted under the
    SAME unique-name discipline build_lm_paged_decoder uses.  A few
    seconds on CPU — the motif is trivial — but it makes greedy decode
    PREDICTABLE, which is what gives a smaller draft a real accept
    rate against the target."""
    import paddle_tpu as fluid
    import paddle_tpu.core.framework as fw
    from paddle_tpu.models.transformer import transformer_lm

    fw.reset_unique_names()
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        ids = fluid.layers.data(name="ids", shape=[max_len],
                                dtype="int64")
        lbl = fluid.layers.data(name="lbl", shape=[max_len, 1],
                                dtype="int64")
        probs = transformer_lm(ids, VOCAB, d_model=d_model,
                               n_heads=n_heads, n_layers=n_layers,
                               max_len=max_len)
        p2 = fluid.layers.reshape(probs, shape=[-1, VOCAB])
        l2 = fluid.layers.reshape(lbl, shape=[-1, 1])
        loss = fluid.layers.mean(
            fluid.layers.cross_entropy(input=p2, label=l2))
        fluid.Adam(learning_rate=lr).minimize(loss)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup, scope=scope)
    r = np.random.RandomState(seed)
    motif = np.asarray(MOTIF, np.int64)
    for _ in range(iters):
        offs = r.randint(0, len(motif), batch)
        rows = np.stack([
            motif[(np.arange(max_len + 1) + o) % len(motif)]
            for o in offs])
        exe.run(main, feed={
            "ids": rows[:, :max_len].astype(np.int32),
            "lbl": rows[:, 1:, None].astype(np.int32)},
            fetch_list=[loss], scope=scope)
    params = [v.name for v in main.global_block().all_parameters()]
    return {n: np.asarray(scope.find_var(n)) for n in params}


def _build_decoder(d_model, n_layers, n_heads, block_size, max_blocks,
                   kv_dtype=None, states=None, platform="cpu"):
    """`platform`: the backend the decoder will run on — every server
    in this file is placed on CPUPlace, so "cpu" is the default even on
    a TPU host (bench.py's kernel microbench passes its own)."""
    import paddle_tpu as fluid
    import paddle_tpu.core.framework as fw
    from paddle_tpu.models.transformer import build_lm_paged_decoder

    fw.reset_unique_names()
    startup, dec = build_lm_paged_decoder(
        VOCAB, block_size, max_blocks, d_model=d_model, n_heads=n_heads,
        n_layers=n_layers, kv_dtype=kv_dtype, platform=platform)
    if states is None:
        scope = fluid.Scope()
        fluid.Executor(fluid.CPUPlace()).run(startup, scope=scope)
        states = {n: np.asarray(scope.find_var(n))
                  for n in dec.state_names}
    return dec, states


def make_requests(n, max_len, rng, long_every=4, prefix_pool=0,
                  prefix_len=0, prefix_hit=0.0):
    """Mixed-length open-loop mix: 1 long pole per `long_every`
    requests, the rest short — the shape that separates the two
    schedulers (a drain-then-refill batch always waits for its pole).

    With `prefix_pool` > 0, a fraction `prefix_hit` of requests draw
    their first `prefix_len` tokens from a pool of `prefix_pool`
    distinct shared prefixes (system prompts) — the workload shape
    block-level prefix caching converts into skipped prefill."""
    prefixes = [list(rng.randint(0, VOCAB, prefix_len))
                for _ in range(prefix_pool)]
    reqs = []
    for i in range(n):
        if prefixes and rng.rand() < prefix_hit:
            prompt = (prefixes[rng.randint(len(prefixes))]
                      + list(rng.randint(0, VOCAB, rng.randint(2, 9))))
        else:
            prompt = list(rng.randint(0, VOCAB, rng.randint(2, 9)))
        if i % long_every == long_every - 1:
            max_new = max_len - len(prompt) - 8   # long pole
        else:
            max_new = int(rng.randint(4, 9))      # short answer
        reqs.append((prompt, max_new))
    return reqs


def run_load(dec, states, reqs, *, static_batch=False, slots=4,
             kv_blocks=56, rate_rps=0.0, deadline_ms=None, place=None,
             prefix_cache=False, draft=None, draft_states=None,
             spec_k=4, mode_label=None):
    """Drive one request set through one server configuration; returns
    the measured row (tokens/s, latency percentiles, shed rate, KV
    util, prefix hit rate, draft accept rate, peak residency)."""
    import paddle_tpu as fluid
    from paddle_tpu.serving import GenerationServer, ServerSaturated

    server = GenerationServer(
        dec, states, slots=slots, kv_blocks=kv_blocks,
        static_batch=static_batch, place=place or fluid.CPUPlace(),
        prefix_cache=prefix_cache, draft_decoder=draft,
        draft_states=draft_states,
        spec_k=spec_k if draft is not None else None)
    n = len(reqs)
    lat = [None] * n
    toks = [0] * n
    shed = [False] * n
    waiters = []
    util_samples = []
    resident_samples = []
    stop_sampling = threading.Event()

    def sample_util():
        while not stop_sampling.wait(0.02):
            st = server.stats()
            util_samples.append(st["kv_pool_utilization"])
            resident_samples.append(st["active_sequences"])

    sampler = threading.Thread(target=sample_util, daemon=True)
    sampler.start()

    def wait_for(i, t0, stream):
        try:
            out = stream.result(timeout=300)
            lat[i] = time.perf_counter() - t0
            toks[i] = len(out)
        except Exception:
            shed[i] = True

    t_start = time.perf_counter()
    for i, (prompt, max_new) in enumerate(reqs):
        if rate_rps > 0:
            target = t_start + i / rate_rps
            delay = target - time.perf_counter()
            if delay > 0:
                time.sleep(delay)
        t0 = time.perf_counter()
        try:
            stream = server.submit(prompt, max_new, seed=i,
                                   deadline_ms=deadline_ms)
        except ServerSaturated:
            shed[i] = True
            continue
        w = threading.Thread(target=wait_for, args=(i, t0, stream),
                             daemon=True)
        w.start()
        waiters.append(w)
    for w in waiters:
        w.join(timeout=300)
    wall = time.perf_counter() - t_start
    stop_sampling.set()
    sampler.join(timeout=1)
    stats = server.stats()
    server.close()

    done_lat = [l for l in lat if l is not None]
    total_tokens = sum(toks)
    lookups = stats["prefix_hits"] + stats["prefix_misses"]
    if mode_label is None:
        mode_label = "static_batch" if static_batch else "continuous"
    return {
        "mode": mode_label,
        "requests": n,
        "completed": len(done_lat),
        "tokens": total_tokens,
        "wall_s": round(wall, 3),
        "tokens_per_sec": round(total_tokens / wall, 1) if wall else 0.0,
        "latency_p50_s": round(float(np.percentile(done_lat, 50)), 4)
        if done_lat else None,
        "latency_p99_s": round(float(np.percentile(done_lat, 99)), 4)
        if done_lat else None,
        "shed_rate": round(sum(shed) / n, 4),
        "kv_util_peak": round(max(util_samples), 3) if util_samples
        else None,
        "kv_util_mean": round(float(np.mean(util_samples)), 3)
        if util_samples else None,
        "resident_peak": int(max(resident_samples))
        if resident_samples else None,
        "decode_ticks": stats["ticks"],
        "decode_kernel": stats.get("decode_kernel", "xla"),
        "prefix_hit_rate": round(stats["prefix_hits"] / lookups, 3)
        if lookups else None,
        "draft_accept_rate": round(
            stats["draft_accepted"] / stats["draft_proposed"], 3)
        if stats["draft_proposed"] else None,
    }


def _quant_residency(d_model, n_layers, n_heads, block_size, max_blocks,
                     states, kv_blocks_fp32, place=None):
    """Same device byte budget per precision, pool blocks re-derived
    from bytes_per_block, long-lived concurrent requests: how many
    sequences does each kv_dtype hold resident at once?"""
    import paddle_tpu as fluid

    rows = {}
    rng = np.random.RandomState(7)
    budget = None
    for kv_dtype in ("fp32", "bf16", "int8"):
        dec, _ = _build_decoder(d_model, n_layers, n_heads, block_size,
                                max_blocks, kv_dtype=kv_dtype,
                                states=states)
        if budget is None:
            budget = kv_blocks_fp32 * dec.bytes_per_block
        kv_blocks = max(1, budget // dec.bytes_per_block)
        from paddle_tpu.serving import GenerationServer

        srv = GenerationServer(dec, states, slots=64,
                               kv_blocks=int(kv_blocks),
                               place=place or fluid.CPUPlace())
        max_len = block_size * max_blocks
        n_req = int(kv_blocks) // max(1, dec.max_blocks_per_seq) + 6
        streams = [srv.submit(list(rng.randint(0, VOCAB, 4)),
                              max_len - 12)
                   for _ in range(n_req)]
        peak = 0
        deadline = time.monotonic() + 120
        while (any(not s.done for s in streams)
               and time.monotonic() < deadline):
            peak = max(peak, srv.stats()["active_sequences"])
            time.sleep(0.01)
        srv.close()
        rows[kv_dtype] = {"kv_blocks": int(kv_blocks),
                          "bytes_per_block": dec.bytes_per_block,
                          "resident_peak": peak}
    rows["int8_vs_fp32_residency"] = round(
        rows["int8"]["resident_peak"]
        / max(rows["fp32"]["resident_peak"], 1), 2)
    rows["byte_budget"] = int(budget)
    return rows


def _build_kernel_decoder(d_model, n_layers, n_heads, block_size,
                          max_blocks, kv_dtype=None, states=None,
                          platform="cpu"):
    """`_build_decoder` with the serving-kernel tier forced ON for the
    duration of the build (kernel selection happens at build time),
    restoring the user's flag after."""
    from paddle_tpu.core import flags as core_flags

    prev = core_flags.get_flag("serving_kernels")
    core_flags.set_flags({"serving_kernels": "on"})
    try:
        return _build_decoder(d_model, n_layers, n_heads, block_size,
                              max_blocks, kv_dtype=kv_dtype,
                              states=states, platform=platform)
    finally:
        core_flags.set_flags({"serving_kernels": prev})


def _measured_step_cost(d_model, n_layers, n_heads, block_size,
                        max_blocks, kv_dtype, slots, kernels_on):
    """XLA-measured (flops, bytes accessed) for ONE compiled decode
    tick of a freshly built decoder — the calibration denominator.

    The probe right-sizes the KV pool (`max_blocks` blocks) and parks
    every cursor at full context: XLA's accounting is per-OP (a gather
    "accesses" its whole operand), so an oversized pool inflates
    measured bytes with buffer size — traffic the per-step static
    model deliberately does not charge."""
    import jax
    import jax.numpy as jnp

    # the probe lowers on the default backend, so build for it
    build = _build_kernel_decoder if kernels_on else _build_decoder
    dec, states = build(d_model, n_layers, n_heads, block_size,
                        max_blocks, kv_dtype=kv_dtype,
                        platform=jax.default_backend())
    sj = {n: jnp.asarray(v) for n, v in states.items()}
    pool_k, pool_v = dec.init_pool(max_blocks)
    tables = jnp.zeros((slots, max_blocks), jnp.int32)
    positions = jnp.full((slots,), block_size * max_blocks - 1,
                         jnp.int32)
    zi = jnp.zeros((slots,), jnp.int32)
    lowered = dec.step.lower(sj, pool_k, pool_v, tables, positions,
                             zi, zi, jnp.zeros((slots,), jnp.float32),
                             jnp.ones((slots,), bool))
    ca = lowered.compile().cost_analysis()
    backend = dec.kernels.get("paged_attention_decode", "xla")
    return (float(ca.get("flops", 0.0)),
            float(ca.get("bytes accessed", 0.0)), backend)


def kernel_roofline(d_model, n_layers, n_heads, block_size, max_blocks,
                    slots, kv_dtypes=("fp32", "int8"), calibrate=True):
    """Before/after roofline rows for the decode step — the XLA gather
    composition vs the fused Pallas paged-attention kernel — on the
    quantized-KV mix, plus the static_vs_measured calibration of the
    kernel-backed estimates.  Band per tests/test_cost_model.py:
    flops within [0.5, 2.5]x and bytes within [0.4, 3]x of XLA's
    per-step cost analysis (estimated / measured)."""
    from paddle_tpu.analysis.cost_model import (DEFAULT_DEVICE,
                                                roofline_seconds,
                                                serving_kernel_cost)

    ctx = block_size * max_blocks
    # the floors are a STATIC estimate for a named target chip, not a
    # reading of whatever device this process runs on
    out = {"slots": slots, "context": ctx, "rows": [],
           "floor_device": DEFAULT_DEVICE,
           "band": {"flops": [0.5, 2.5], "bytes": [0.4, 3.0]},
           "pallas_vs_xla_bytes": {}}
    in_band = True
    for kv_dtype in kv_dtypes:
        spec = dict(d_model=d_model, n_layers=n_layers,
                    n_heads=n_heads, vocab_size=VOCAB,
                    block_size=block_size,
                    max_blocks_per_seq=max_blocks, kv_dtype=kv_dtype)
        pair = {}
        for kernels_on, backend in ((False, "xla"), (True, "pallas")):
            est = serving_kernel_cost(
                "paged_decode_step", spec, slots=slots, context=ctx,
                kv_dtype=kv_dtype, backend=backend)
            row = {"kv_dtype": kv_dtype, "backend": backend,
                   "est_flops": est["flops"],
                   "est_bytes": est["bytes"],
                   "ai_flop_per_byte": est["ai_flop_per_byte"],
                   "bound": est["bound"],
                   "floor_s": roofline_seconds(est["flops"],
                                               est["bytes"],
                                               DEFAULT_DEVICE)}
            if calibrate:
                mf, mb, built = _measured_step_cost(
                    d_model, n_layers, n_heads, block_size,
                    max_blocks, kv_dtype, slots, kernels_on)
                fr = est["flops"] / mf if mf else None
                br = est["bytes"] / mb if mb else None
                row.update(
                    xla_flops=mf, xla_bytes=mb, built_kernel=built,
                    flops_ratio=round(fr, 3) if fr else None,
                    bytes_ratio=round(br, 3) if br else None)
                ok = (fr is not None and br is not None
                      and 0.5 < fr < 2.5 and 0.4 < br < 3.0)
                row["in_band"] = ok
                in_band = in_band and ok
            out["rows"].append(row)
            pair[backend] = est
        out["pallas_vs_xla_bytes"][kv_dtype] = round(
            pair["pallas"]["bytes"] / pair["xla"]["bytes"], 3)
    if calibrate:
        out["static_vs_measured_ok"] = in_band
    return out


def run_serving_bench(requests=48, rate_rps=0.0, slots=4, kv_blocks=56,
                      block_size=8, max_blocks=12, d_model=128,
                      n_layers=2, n_heads=4, deadline_ms=None,
                      prom_out="", trials=2, prefix_pool=3,
                      prefix_len=24, prefix_hit=0.75, spec_k=4,
                      draft_d_model=32, draft_layers=1, with_spec=True,
                      with_quant=True, with_kernels=True):
    """BENCH_SERVING entry point (bench.py): the scheduler ablation
    ladder over the same shared-prefix mixed-length open-loop request
    set; best-of-`trials` per mode; optional Prometheus dump of the
    serving series."""
    from paddle_tpu.observability import exporters
    from paddle_tpu.observability import metrics as obs_metrics

    # armed only for the duration of this bench: later bench.py
    # sections (convergence, book matrix) must run exactly as the
    # user's PADDLE_TPU_METRICS setting asks
    metrics_were_on = obs_metrics.enabled()
    obs_metrics.set_enabled(True)
    try:
        max_len = block_size * max_blocks
        t0 = time.perf_counter()
        states = draft_states = None
        if with_spec:
            states = _train_lm(d_model, n_layers, n_heads, max_len)
            draft_states = _train_lm(draft_d_model, draft_layers,
                                     n_heads, max_len, iters=120,
                                     seed=1)
        train_s = round(time.perf_counter() - t0, 1)
        dec, states = _build_decoder(d_model, n_layers, n_heads,
                                     block_size, max_blocks,
                                     states=states)
        draft = None
        if with_spec:
            draft, draft_states = _build_decoder(
                draft_d_model, draft_layers, n_heads, block_size,
                max_blocks, states=draft_states)
        reqs = make_requests(requests, max_len, np.random.RandomState(0),
                             prefix_pool=prefix_pool,
                             prefix_len=prefix_len,
                             prefix_hit=prefix_hit)
        ladder = [
            ("static_batch", dict(static_batch=True)),
            ("continuous", dict()),
            ("prefix", dict(prefix_cache=True)),
        ]
        if with_spec:
            ladder += [
                ("spec", dict(draft=draft, draft_states=draft_states,
                              spec_k=spec_k)),
                ("prefix+spec", dict(prefix_cache=True, draft=draft,
                                     draft_states=draft_states,
                                     spec_k=spec_k)),
            ]
        kdec = None
        if with_kernels:
            # kernel selection happens at BUILD time; same trained
            # weights through the same unique-name discipline, so the
            # rung isolates the attention path swap
            kdec, _ = _build_kernel_decoder(
                d_model, n_layers, n_heads, block_size, max_blocks,
                states=states)
            kkw = dict(prefix_cache=True)
            if with_spec:
                kkw.update(draft=draft, draft_states=draft_states,
                           spec_k=spec_k)
            ladder.append(("kernels", kkw))
        rows = {}
        for label, kw in ladder:
            best = None
            # the kernels rung runs Pallas in interpret mode off-TPU:
            # one trial — the row demonstrates the path, not CPU speed
            for _ in range(1 if label == "kernels" else trials):
                row = run_load(kdec if label == "kernels" else dec,
                               states, reqs, slots=slots,
                               kv_blocks=kv_blocks, rate_rps=rate_rps,
                               deadline_ms=deadline_ms,
                               mode_label=label, **kw)
                if best is None or row["tokens_per_sec"] > best[
                        "tokens_per_sec"]:
                    best = row
            rows[label] = best
        base = rows["continuous"]["tokens_per_sec"]
        out = {
            "bench": "serving",
            "slots": slots, "kv_blocks": kv_blocks,
            "block_size": block_size, "d_model": d_model,
            "layers": n_layers, "rate_rps": rate_rps,
            "prefix_pool": prefix_pool, "prefix_len": prefix_len,
            "prefix_hit": prefix_hit,
            "spec_k": spec_k if with_spec else 0,
            "train_s": train_s,
            "ablation": rows,
            "continuous_speedup": round(
                base / max(rows["static_batch"]["tokens_per_sec"],
                           1e-9), 2),
            "prefix_speedup": round(
                rows["prefix"]["tokens_per_sec"] / max(base, 1e-9), 2),
        }
        if with_spec:
            out["spec_speedup"] = round(
                rows["spec"]["tokens_per_sec"] / max(base, 1e-9), 2)
            out["stacked_speedup"] = round(
                rows["prefix+spec"]["tokens_per_sec"]
                / max(base, 1e-9), 2)
        if with_kernels:
            out["kernels_vs_continuous"] = round(
                rows["kernels"]["tokens_per_sec"] / max(base, 1e-9), 2)
            out["roofline"] = kernel_roofline(
                d_model, n_layers, n_heads, block_size, max_blocks,
                slots)
        if with_quant:
            out["kv_quantization"] = _quant_residency(
                d_model, n_layers, n_heads, block_size, max_blocks,
                states, kv_blocks)
        out["phase_breakdown"] = phase_breakdown(
            decode_backend=rows["kernels"]["decode_kernel"]
            if with_kernels else None)
        if prom_out:
            out["prometheus_dump"] = exporters.write_prometheus(prom_out)
        return out
    finally:
        obs_metrics.set_enabled(metrics_were_on)


def phase_breakdown(decode_backend=None):
    """This process's per-phase attribution (lifetime sums of the
    paddle_tpu_*_phase_seconds families), as rows plus the rendered
    `cli why` table — the artifact's "where did the bench spend its
    time" section.

    `decode_backend` (the kernels rung's selection, "pallas" or a
    fallback reason) is stamped onto the generation decode/draft_verify
    rows so `cli why` readers see WHAT ran the attention math, not just
    where the time went."""
    from paddle_tpu.observability import attribution, exporters
    from paddle_tpu.observability.collector import parse_prometheus_text

    try:
        parsed = parse_prometheus_text(exporters.prometheus_text())
        rows = attribution.why_rows_from_parsed(parsed)
        if decode_backend:
            for r in rows:
                if (r.get("kind") == "generation"
                        and r.get("phase") in ("decode",
                                               "draft_verify")):
                    r["backend"] = decode_backend
        out = {"rows": rows,
               "table": attribution.format_why_table(rows)}
        if decode_backend:
            out["decode_backend"] = decode_backend
        return out
    except Exception as e:  # attribution must never fail the bench
        return {"error": f"{type(e).__name__}: {e}"}


def write_bench_artifact(out, directory=".", prefix="BENCH_SERVING"):
    """Write `out` as the next free ``<prefix>_rNN.json`` revision in
    `directory` (the repo's committed-artifact convention: BENCH_r05,
    BOOK_MATRIX_r05, ...).  Returns the path."""
    n = 1
    while True:
        path = os.path.join(directory, f"{prefix}_r{n:02d}.json")
        if not os.path.exists(path):
            break
        n += 1
    with open(path, "w") as f:
        json.dump(out, f, indent=1, default=str)
    return path


# ---------------------------------------------------------------------------
# --ramp: open-loop load ramp against a LIVE autoscaling fleet
# (the ROADMAP-4 acceptance driver; reused by tools/mini_fleet.py's
# autoscale drill and tests/test_autoscaler.py)
# ---------------------------------------------------------------------------


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


def run_fleet_ramp_bench(*, requests=64, peak_rps=20.0, phase_s=6.0,
                         min_replicas=1, max_replicas=3,
                         backlog_high=64.0, backlog_low=8.0,
                         sustain_s=1.0, idle_sustain_s=4.0,
                         cooldown_s=4.0, d_model=32, n_layers=1,
                         n_heads=2, block_size=4, max_blocks=8,
                         slots=2, kv_blocks=24,
                         workdir=None, spawn_timeout_s=300.0,
                         decode_delay_s=0.02, phase_hook=None,
                         post_hook=None, env_extra=None):
    """BENCH_SERVING_RAMP entry point: save a model dir, front it with
    ReplicaRouter + Autoscaler spawning REAL `cli serve` replicas,
    drive the open-loop ramp, and report per-phase serving stats
    alongside the scaling timeline and each surviving replica's
    warmup accounting (compiles vs compile-cache hits).

    This is a CPU-fleet bench: several replicas share one host and a
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

    Chaos-drill hooks (tools/mini_fleet.py --drill autoscale rides
    this function rather than re-building the fleet):
    `phase_hook(phase, rate, router, scaler)` fires after each ramp
    phase (e.g. SIGKILL an owned replica at the peak);
    `post_hook(record, router, scaler)` fires on the finished record
    BEFORE teardown (the autoscaler/router metric series are reclaimed
    on close, so a telemetry scrape must happen here); `env_extra`
    merges into the replica environment."""
    import shutil
    import tempfile

    from paddle_tpu.cloud.autoscaler import (Autoscaler,
                                             AutoscalerPolicy,
                                             SubprocessReplicaLauncher)
    from paddle_tpu.cloud.router import ReplicaRouter
    from paddle_tpu.serving import save_generation_model
    from paddle_tpu.serving.replica import replica_call

    own_workdir = workdir is None
    workdir = workdir or tempfile.mkdtemp(prefix="paddle_ramp_")
    model_dir = os.path.join(workdir, "model")
    max_len = block_size * max_blocks
    dec, states = _build_decoder(d_model, n_layers, n_heads,
                                 block_size, max_blocks)
    save_generation_model(
        model_dir, states,
        {"vocab_size": VOCAB, "d_model": d_model, "n_heads": n_heads,
         "n_layers": n_layers, "block_size": block_size,
         "max_blocks_per_seq": max_blocks, "slots": slots,
         "kv_blocks": kv_blocks})

    router = ReplicaRouter(desired=max_replicas * 2, refresh_s=0.1)
    policy = AutoscalerPolicy(
        min_replicas, max_replicas, p99_high_s=30.0,
        backlog_high=backlog_high, backlog_low=backlog_low,
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
    reqs = make_requests(requests, max_len, np.random.RandomState(0))
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
            "bench": "serving_ramp",
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
        if own_workdir:
            shutil.rmtree(workdir, ignore_errors=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--requests", type=int, default=48)
    ap.add_argument("--rate", type=float, default=0.0,
                    help="open-loop arrival rate, req/s (0=all up "
                    "front: saturation)")
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--kv-blocks", type=int, default=56)
    ap.add_argument("--block-size", type=int, default=8)
    ap.add_argument("--max-blocks", type=int, default=12)
    ap.add_argument("--d-model", type=int, default=128)
    ap.add_argument("--layers", type=int, default=2)
    ap.add_argument("--heads", type=int, default=4)
    ap.add_argument("--deadline-ms", type=float, default=None)
    ap.add_argument("--trials", type=int, default=2)
    ap.add_argument("--prefix-pool", type=int, default=3,
                    help="distinct shared prefixes (system prompts)")
    ap.add_argument("--prefix-len", type=int, default=24)
    ap.add_argument("--prefix-hit", type=float, default=0.75,
                    help="fraction of requests drawing a pooled prefix")
    ap.add_argument("--spec-k", type=int, default=4)
    ap.add_argument("--no-spec", action="store_true",
                    help="skip the speculative-decoding rows (and the "
                    "brief target/draft training they need)")
    ap.add_argument("--no-quant", action="store_true",
                    help="skip the KV-quantization residency section")
    ap.add_argument("--no-kernels", action="store_true",
                    help="skip the serving-kernel rung and the "
                    "roofline before/after + calibration section")
    ap.add_argument("--prom_out", default="",
                    help="write the Prometheus text dump here")
    ap.add_argument("--ramp", action="store_true",
                    help="instead of the ablation ladder, run the "
                    "open-loop load ramp against a LIVE autoscaling "
                    "fleet (router + autoscaler + `cli serve` "
                    "replicas): rate ramps up then down, reporting "
                    "per-phase tokens/s, p99, shed rate, the scaling "
                    "timeline, and new-replica warm-start accounting")
    ap.add_argument("--ramp-peak", type=float, default=24.0,
                    help="peak arrival rate req/s at the ramp top")
    ap.add_argument("--ramp-phase-s", type=float, default=6.0)
    ap.add_argument("--ramp-max", type=int, default=3,
                    help="max replicas the autoscaler may spawn")
    ap.add_argument("--artifact-dir", default="",
                    help="also write the result as the next free "
                    "BENCH_SERVING_rNN.json (BENCH_SERVING_RAMP_rNN "
                    "for --ramp) revision in this directory")
    a = ap.parse_args()
    if a.ramp:
        out = run_fleet_ramp_bench(
            requests=a.requests, peak_rps=a.ramp_peak,
            phase_s=a.ramp_phase_s, max_replicas=a.ramp_max,
            d_model=a.d_model, n_layers=a.layers, n_heads=a.heads,
            block_size=a.block_size, max_blocks=a.max_blocks,
            slots=a.slots)
        out["phase_breakdown"] = phase_breakdown()
        if a.artifact_dir:
            out["artifact"] = write_bench_artifact(
                out, a.artifact_dir, prefix="BENCH_SERVING_RAMP")
        print(json.dumps(out))
        return
    out = run_serving_bench(
        requests=a.requests, rate_rps=a.rate, slots=a.slots,
        kv_blocks=a.kv_blocks, block_size=a.block_size,
        max_blocks=a.max_blocks, d_model=a.d_model, n_layers=a.layers,
        n_heads=a.heads, deadline_ms=a.deadline_ms, trials=a.trials,
        prefix_pool=a.prefix_pool, prefix_len=a.prefix_len,
        prefix_hit=a.prefix_hit, spec_k=a.spec_k,
        with_spec=not a.no_spec, with_quant=not a.no_quant,
        with_kernels=not a.no_kernels, prom_out=a.prom_out)
    if a.artifact_dir:
        out["artifact"] = write_bench_artifact(out, a.artifact_dir)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
