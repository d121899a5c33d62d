"""Job `serve_lm_closed`: `serve_closed`'s closed loop for a
configuration that DESCRIBES its decoder block: `"block"` in the
configuration file holds the fields of `paddle_tpu.models.lm_block
.BlockSpec`, literal (`spec`) or under the source's own key names
(`from_keys`), and which key is the FFN's or one expert's width
(`d_inner`); `"compare"` holds the limits of the comparison with the
reference.  Nothing here names an architecture: another block that
`lm_block` can build is another configuration file and reference.

The clients, the load, the permutation of the length table and the
weights are `serve_closed`'s own, imported.  What is new here is the
part `serve_closed.run` has inline and cannot lend: building the decoder
from the description (`build_server`), the comparison through the
reference's `compare` (`check_against_reference`), and the window with
its accounting (`measure`).

End-to-end readings are `serve_closed`'s, from the clients' own clocks:
serve_tokens_per_s, ttft_p95_ms, itl_p95_ms.
"""
from __future__ import annotations

import gc
import os
import time

import numpy as np

import common

serve_closed = common.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "serve_closed.py"))


def block_of(m: dict):
    """(block description, width of the FFN or of one expert) from the
    configuration's `block`."""
    from paddle_tpu.models import lm_block

    b = m["block"]
    fields = dict(b["spec"], **{f: m[k] for f, k in b["from_keys"].items()})
    return lm_block.BlockSpec(**fields), m[b["d_inner"]]


def system_outputs(dec, g, toks, slots: int):
    """`toks` through the step AS THE SERVER RUNS IT: `slots` lanes (the
    resident step's shape: at one lane the TPU compiler multiplies a
    single row in float32 and the served rounding would not show), the
    sequence in lane 0 and the other lanes idle, position by position
    through `step`, each attending to the paged cache of those before
    it.  -> ([positions, vocab] logits, the routing of every position
    stacked on axis 1, or None for a block that does not route)."""
    import jax

    n = len(toks)
    need = -(-n // dec.block_size)
    pool_k, pool_v = dec.init_pool(need + 1, jax.devices()[0])
    tables = np.zeros((slots, dec.max_blocks_per_seq), np.int32)
    tables[0, :need] = 1 + np.arange(need)
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    act = np.arange(slots) == 0
    got, routed = [], []
    for pos in range(n):
        args = (g, pool_k, pool_v, tables, np.where(act, pos, 0).astype(
            np.int32), np.where(act, toks[pos], 0).astype(np.int32),
            zs, zt, act)
        if dec.step_routing is not None:
            logits, routing = dec.step_routing(*args)
            routed.append({k: np.asarray(v)[:, :1]
                           for k, v in routing.items()})
        else:
            logits = dec.step_logits(*args)
        got.append(np.asarray(logits)[:1])
        _, pool_k, pool_v, *_ = dec.step(*args)
    return np.concatenate(got), ({
        k: np.concatenate([r[k] for r in routed], 1) for k in routed[0]}
        if routed else None)


def check_against_reference(cell, dec, g, n_tokens: int):
    """One seeded sequence through `system_outputs`, then the
    reference's `compare(states, config, ids, logits, routing)` over
    the same tokens, whose numbers the configuration's
    `compare.limits` bound ({number: [least, most]}, either may be
    null).  A traced run also reports the reference's reading one
    precision `below`, which the limits must refuse."""
    m, ref = cell.config, cell.reference()
    rng = np.random.default_rng([common.seed31(cell.seed), 0xC0DE])
    toks = rng.integers(0, m["vocab_size"], n_tokens).astype(np.int32)
    out = ref.compare(g, m, toks, *system_outputs(
        dec, g, toks, int(cell.traffic["slots"])))
    limits = m["compare"]["limits"]
    out.update(positions=n_tokens, limits=limits, ok=bool(
        out["finite"] and all(
            (lo is None or out[k] >= lo) and (hi is None or out[k] <= hi)
            for k, (lo, hi) in limits.items())))
    if cell.trace and hasattr(ref, "below"):
        out["below"] = ref.below(g, m, toks)
    return out


def build_server(cell, run_):
    """Decoder from the configuration's block, weights on the device
    from the seed, the comparison with the reference, and the warm
    server: all of it set-up.  -> (decoder, server)."""
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.core import framework as fw
    from paddle_tpu.models.transformer import build_lm_paged_decoder
    from paddle_tpu.serving import GenerationServer

    m, t = cell.config, cell.traffic
    platform = jax.devices()[0].platform
    place = fluid.TPUPlace() if platform == "tpu" else fluid.CPUPlace()
    dtype = jnp.bfloat16 if m["dtype"] == "bfloat16" else jnp.float32
    max_blocks = int(t["context"]) // int(t["block_size"])
    slots = int(t["slots"])
    block, d_inner = block_of(m)

    fw.reset_unique_names()
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], int(t["block_size"]), max_blocks,
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_layers=m["num_hidden_layers"], d_inner=d_inner,
        kv_dtype=t["kv_dtype"], platform=platform, block=block)
    cell.mark("decoder built")
    g = serve_closed.make_weights(dec.state_shapes, cell.seed, dtype)
    jax.block_until_ready(g)
    cell.mark("weights made on the device")
    run_.notes["reference"] = check_against_reference(
        cell, dec, g, int(t["correct_tokens"]))
    cell.mark("compared with the reference")
    states = jax.device_get(g)
    del g
    gc.collect()
    cell.mark("weights copied to the host")
    server = GenerationServer(
        dec, states, slots=slots, kv_blocks=slots * max_blocks,
        place=place, max_queue=int(t["max_queue"]),
        prefix_cache=bool(t["prefix_cache"]))
    del states
    cell.mark("server built and warm")
    return dec, server


def count_slot_ticks(cell, server) -> dict:
    """The scheduler's own account of each tick, taken around `_tick`
    (the traced run only): slot-ticks spent teacher-forcing a prompt
    position, which deliver no token, against all slot-ticks."""
    slot_ticks = {"prefill": 0, "all": 0, "ticks": 0, "open": False}
    if cell.trace:
        inner = server._tick

        def counted_tick(seqs):
            if slot_ticks["open"]:
                slot_ticks["ticks"] += 1
                slot_ticks["all"] += len(seqs)
                slot_ticks["prefill"] += sum(
                    1 for s in seqs if s.cur < s.prompt_len - 1)
            return inner(seqs)

        server._tick = counted_tick
    return slot_ticks


def attention_kernel_in_step(dec, server, slots: int) -> float:
    """`serve_closed.pallas_in_step` for a step that may hold the TPU
    compiler's own grouped-matmul calls: 1 where the compiled resident
    step holds a Mosaic custom call that is NOT one of `ragged_dot`'s
    (which the compiler also emits as `tpu_custom_call`), 0 where the
    XLA gather runs."""
    import jax

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    i32 = jax.ShapeDtypeStruct((slots,), np.int32)
    text = dec.step.lower(
        jax.tree_util.tree_map(spec, server._states),
        jax.tree_util.tree_map(spec, server._pool_k),
        jax.tree_util.tree_map(spec, server._pool_v),
        jax.ShapeDtypeStruct((slots, dec.max_blocks_per_seq), np.int32),
        i32, i32, jax.ShapeDtypeStruct((slots,), np.uint32),
        jax.ShapeDtypeStruct((slots,), np.float32),
        jax.ShapeDtypeStruct((slots,), np.bool_)).compile().as_text()
    return 1.0 if any("tpu_custom_call" in line
                      and "ragged-dot" not in line
                      for line in text.splitlines()) else 0.0


def device_share_by_scope(run_) -> dict:
    """Percent of the traced slice's device seconds under each part of
    the resident step (`paged_decoder/<part>`; what the step's scope
    table does not name is "other"), for the line people read: the
    readers under perf/metrics/ each take their one share from the same
    join."""
    from paddle_tpu import profiler

    by_scope = profiler.scope_seconds(run_.trace["op_seconds"],
                                      "paged_decoder.step")
    total = sum(by_scope.values())
    out = {}
    for scope, t in by_scope.items():
        part = (scope.split("paged_decoder/")[1].split("/")[0]
                if "paged_decoder/" in scope else "other")
        out[part] = out.get(part, 0.0) + 100.0 * t / total
    return {k: round(v, 3) for k, v in
            sorted(out.items(), key=lambda kv: -kv[1])} if total else {}


def measure(cell, run_, dec, server, slot_ticks, records, stop, joiners):
    """The measured window and its accounting, for load that is already
    running: opens the window now, holds it `cell.seconds`, calls
    `stop()`, closes the server, joins `joiners`, and fills `run_` from
    `records` (the clients' own stamps).  Nothing is reset at the
    opening and every rate is all the work over all the seconds."""
    from paddle_tpu.core.executor import xla_compile_counts

    t = cell.traffic
    slots = int(t["slots"])
    tap = common.SpanTap()
    kv_peak = 0.0
    gcw = common.GcWatch()
    gcw.arm()
    c0 = xla_compile_counts()
    trace = None
    if cell.trace:
        tap.arm()
        trace = common.TraceWindow(cell, tap,
                                   float(t["trace_delay_seconds"]),
                                   float(t["trace_seconds"]))
    run_.t_window_open = t_open = time.perf_counter()
    slot_ticks["open"] = True
    if trace is not None:
        trace.start()
    t_close = t_open + cell.seconds
    while True:
        left = t_close - time.perf_counter()
        if left <= 0:
            break
        if cell.trace:
            kv_peak = max(kv_peak, server.stats()["kv_pool_utilization"])
        time.sleep(min(0.25, left))
    run_.t_window_close = t_close = time.perf_counter()
    slot_ticks["open"] = False
    c1 = xla_compile_counts()
    tap.disarm()
    gcw.disarm()

    stop()
    stats = server.stats()
    records = list(records)
    if trace is not None:
        run_.trace = trace.finish()
        run_.spans = tap.records
        # the traced slice on the spans' clock (time.time()), for
        # readers that set a span's counts against the slice's seconds
        run_.notes["trace_slice_wall"] = (
            trace._sync_wall, trace._sync_wall + trace.length)
        run_.notes["device_share_by_scope"] = device_share_by_scope(run_)
        run_.counters["decode_kernel_pallas"] = attention_kernel_in_step(
            dec, server, slots)
    server.close()                      # fails what is still in flight
    for j in joiners:
        j.join(timeout=30)

    window = t_close - t_open
    slice_s = float(t["slice_seconds"])
    in_window = []
    ttft, itl, slices = [], [], [0] * max(1, round(window / slice_s))
    attempted = failed = 0
    for rec in records:
        stamps = rec["stamps"]
        if stamps and t_open <= stamps[0] < t_close:
            ttft.append((stamps[0] - rec["submit"]) * 1e3)
        for a, b in zip(stamps, stamps[1:]):
            if t_open <= b < t_close:
                itl.append((b - a) * 1e3)
        for s in stamps:
            if t_open <= s < t_close:
                in_window.append(s)
                slices[min(len(slices) - 1,
                           int((s - t_open) / slice_s))] += 1
        if rec["done"] is not None and t_open <= rec["done"] < t_close:
            attempted += 1
            if rec["error"] is not None or len(stamps) != rec["want"]:
                failed += 1
    run_.attempted, run_.failed = attempted, failed
    run_.end_to_end = {
        "serve_tokens_per_s": len(in_window) / window,
        "ttft_p95_ms": common.percentile(ttft, 95) if ttft else None,
        "itl_p95_ms": common.percentile(itl, 95) if itl else None,
    }
    run_.samples = {"ttft_ms": ttft, "itl_ms": itl,
                    "tokens_per_slice": slices}
    in_window.sort()
    gaps = np.diff(in_window) if len(in_window) > 1 else np.zeros(1)
    worst = int(np.argmax(gaps))
    run_.counters.update(gcw.counters(t_open, t_close))
    run_.counters.update({
        "delivery_gap_max_ms": 1e3 * float(gaps[worst]),
        "delivery_gap_max_at_s": float(in_window[worst] - t_open)
        if in_window else None,
        "itl_max_ms": max(itl) if itl else None,
        "compiles_in_window": c1["compiles"] - c0["compiles"],
        "slot_ticks_prefill": slot_ticks["prefill"],
        "slot_ticks_all": slot_ticks["all"],
        "ticks_in_window": slot_ticks["ticks"],
        "kv_pool_util_peak": kv_peak,
        "requests_started": len(records),
        "ttft_samples": len(ttft),
    })
    run_.notes["server"] = {
        k: stats[k] for k in (
            "decode_kernel", "kv_dtype", "recompiles_after_warmup",
            "warm_start", "warmup_s", "prefix_hits", "shed",
            "deadline_expired") if k in stats}
    run_.notes["slices"] = {"seconds": slice_s, "tokens": slices}
    run_.correct = bool(run_.notes["reference"]["ok"] and failed == 0
                        and attempted > 0
                        and stats["recompiles_after_warmup"] == 0)
    return run_


def run(cell):
    run_ = common.Run()
    m, t = cell.config, cell.traffic
    dec, server = build_server(cell, run_)
    slot_ticks = count_slot_ticks(cell, server)
    load = serve_closed.Load(
        cell, server,
        serve_closed.permuted_table(t["lengths"], cell.seed),
        m["vocab_size"])
    # the ramp: clients start one by one over `stagger_seconds`, so that
    # the slots are out of step from the start, and run on until
    # `ramp_seconds` are over
    t_ramp = time.perf_counter()
    gap = float(t["stagger_seconds"]) / len(load.clients)
    for i, c in enumerate(load.clients):
        time.sleep(max(0.0, t_ramp + i * gap - time.perf_counter()))
        c.start()
    time.sleep(max(0.0, t_ramp + float(t["ramp_seconds"])
                   - time.perf_counter()))
    return measure(cell, run_, dec, server, slot_ticks, load.records,
                   load.stop.set, load.clients)
