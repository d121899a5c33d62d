"""Job `serve_closed`: N clients in a closed loop on one
`GenerationServer` over a `PagedKVCache`, at saturation.

Set-up (all of it inside `setup_s`): build the paged decoder at the
configuration's sizes, make the weights on the device from the seed in
one jitted call in the served type, compare the decoder with the plain
reference, build the server (which warms its one resident step), start
the clients and let them run for `ramp_seconds` so that the slots have
fallen out of step.  Then the window opens.  Nothing is reset at the
opening: the clients just keep going, and every token is stamped by the
client that received it.

End-to-end readings, all from the clients' own clocks:
  serve_tokens_per_s  output tokens received inside the window over its
                      seconds, whether or not their request finished
  ttft_p95_ms         submit -> first token, over requests whose first
                      token fell in the window
  itl_p95_ms          gap between a stream's consecutive tokens, over
                      gaps that ended in the window
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

import common

# Paged decoder (bf16 weights, bf16 KV pool) against the float32
# reference on the same weights: largest |logit difference| over 48
# positions, relative to the largest |logit|.  The decoder's residual
# stream is float32 after the first block (bf16 weights promote) but
# its matrix multiplications run one bf16 pass on the MXU and K/V are
# rounded to bf16 in the pool: over 24 blocks that measured 5.1e-3 to
# 5.7e-3 on the v5e in every run (my chip runs, PR 23).  Rounding K/V
# to int8 or dropping a block lands several times higher, so 2.5e-2
# leaves room above what bf16 does and below what a fault does.
LOGITS_REL_TOL = 2.5e-2


def permuted_table(lengths: dict, seed: int):
    """The literal table in the seed's order: blocks shuffled, and the
    rows inside each block shuffled.  No length is ever drawn."""
    rng = np.random.default_rng([common.seed31(seed), 0x7AB1E])
    table = [tuple(int(v) for v in row) for row in lengths["table"]]
    size = int(lengths["block"])
    blocks = [table[i:i + size] for i in range(0, len(table), size)]
    out = []
    for b in rng.permutation(len(blocks)):
        rows = blocks[int(b)]
        out.extend(rows[int(i)] for i in rng.permutation(len(rows)))
    return out


def make_weights(shapes: dict, seed: int, dtype):
    """Every parameter of the decoder in ONE jitted call on the device:
    normal(0, 0.02) matrices and vectors, LayerNorm scales around 1."""
    import jax
    import jax.numpy as jnp

    names = sorted(shapes)

    @jax.jit
    def gen(key):
        out = {}
        for i, n in enumerate(names):
            v = 0.02 * jax.random.normal(jax.random.fold_in(key, i),
                                         shapes[n], jnp.float32)
            if ".scale_" in n:
                v = 1.0 + v
            out[n] = v.astype(dtype)
        return out

    return gen(jax.random.key(common.seed31(seed)))


def check_against_reference(cell, dec, g, m, n_tokens: int):
    """One seeded sequence, token by token through `step` (which writes
    the paged cache) with `step_logits` read at every position, against
    the reference's full causal forward over the same tokens."""
    import jax
    import jax.numpy as jnp

    ref = cell.reference()
    rng = np.random.default_rng([common.seed31(cell.seed), 0xC0DE])
    toks = rng.integers(0, m["vocab_size"], n_tokens).astype(np.int32)
    want = np.asarray(ref.logits(
        ref.structure(g, m["num_hidden_layers"]), toks,
        m["num_attention_heads"]), np.float32)

    nb = dec.max_blocks_per_seq
    need = -(-n_tokens // dec.block_size)
    pool_k, pool_v = dec.init_pool(need + 1, jax.devices()[0])
    tables = np.zeros((1, nb), np.int32)
    tables[0, :need] = 1 + np.arange(need)
    zs, zt = np.zeros(1, np.uint32), np.zeros(1, np.float32)
    act = np.ones(1, bool)
    got = []
    for pos in range(n_tokens):
        args = (g, pool_k, pool_v, tables, np.full(1, pos, np.int32),
                toks[pos:pos + 1], zs, zt, act)
        got.append(dec.step_logits(*args))
        _, pool_k, pool_v = dec.step(*args)
    got = np.asarray(jnp.concatenate(got, 0), np.float32)
    del pool_k, pool_v
    rel = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    return {"logits_rel_err": rel, "logits_rel_tol": LOGITS_REL_TOL,
            "positions": n_tokens,
            "argmax_agree": float(np.mean(got.argmax(-1)
                                          == want.argmax(-1))),
            "ok": bool(np.isfinite(got).all() and rel <= LOGITS_REL_TOL)}


class Client(threading.Thread):
    """One caller: submit, read the stream to its end, submit again."""

    def __init__(self, load: "Load", idx: int):
        super().__init__(daemon=True, name=f"perf-client-{idx}")
        self.load = load

    def run(self):
        load = self.load
        while not load.stop.is_set():
            req = load.next_request()
            rec = {"idx": req[0], "want": req[2], "stamps": [],
                   "submit": time.perf_counter(), "error": None,
                   "done": None}
            load.records.append(rec)
            try:
                stream = load.server.submit(
                    req[1], req[2], temperature=load.temperature,
                    seed=req[0], eos_id=load.eos_id)
                stamps = rec["stamps"]
                for _ in stream:
                    stamps.append(time.perf_counter())
                rec["done"] = time.perf_counter()
            except Exception as e:      # judged by when it happened
                rec["error"] = f"{type(e).__name__}: {e}"[:200]
                rec["done"] = time.perf_counter()


class Load:
    def __init__(self, cell, server, table, vocab: int):
        t = cell.traffic
        self.server, self.table, self.vocab = server, table, vocab
        self.seed = common.seed31(cell.seed)
        self.temperature = float(t["temperature"])
        self.eos_id = t["eos_id"]
        self.stop = threading.Event()
        self.records = []               # list.append is atomic
        self._n = 0
        self._lock = threading.Lock()
        self.clients = [Client(self, i) for i in range(int(t["clients"]))]

    def next_request(self):
        with self._lock:
            idx = self._n
            self._n += 1
        prompt_len, out_len = self.table[idx % len(self.table)]
        rng = np.random.default_rng([self.seed, 0x70C5, idx])
        prompt = rng.integers(0, self.vocab, prompt_len).astype(np.int32)
        return idx, prompt, out_len


def run(cell):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as fluid
    from paddle_tpu.core import framework as fw
    from paddle_tpu.core.executor import xla_compile_counts
    from paddle_tpu.models.transformer import build_lm_paged_decoder
    from paddle_tpu.serving import GenerationServer

    run_ = common.Run()
    m, t = cell.config, cell.traffic
    platform = jax.devices()[0].platform
    place = fluid.TPUPlace() if platform == "tpu" else fluid.CPUPlace()
    dtype = jnp.bfloat16 if m["dtype"] == "bfloat16" else jnp.float32
    max_blocks = int(t["context"]) // int(t["block_size"])
    slots = int(t["slots"])
    kv_blocks = slots * max_blocks

    fw.reset_unique_names()
    _, dec = build_lm_paged_decoder(
        m["vocab_size"], int(t["block_size"]), max_blocks,
        d_model=m["hidden_size"], n_heads=m["num_attention_heads"],
        n_layers=m["num_hidden_layers"], d_inner=m["ffn_dim"],
        kv_dtype=t["kv_dtype"], platform=platform)
    cell.mark("decoder built")
    g = make_weights(dec.state_shapes, cell.seed, dtype)
    jax.block_until_ready(g)
    cell.mark("weights made on the device")
    check = check_against_reference(cell, dec, g, m,
                                    int(t["correct_tokens"]))
    run_.notes["reference"] = check
    cell.mark("compared with the reference")
    states = jax.device_get(g)
    del g
    gc.collect()
    cell.mark("weights copied to the host")

    server = GenerationServer(
        dec, states, slots=slots, kv_blocks=kv_blocks, place=place,
        max_queue=int(t["max_queue"]),
        prefix_cache=bool(t["prefix_cache"]))
    del states
    cell.mark("server built and warm")

    # the scheduler's own account of each tick, taken around `_tick`
    # (the traced run only): slot-ticks spent teacher-forcing a prompt
    # position, which deliver no token, against all slot-ticks
    tap = common.SpanTap()
    slot_ticks = {"prefill": 0, "all": 0, "ticks": 0, "open": False}
    kv_peak = 0.0
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

    load = Load(cell, server, permuted_table(t["lengths"], cell.seed),
                m["vocab_size"])
    # the ramp: clients start one by one over `stagger_seconds`, so that
    # the slots are out of step from the start instead of moving as one
    # wave, and run on until `ramp_seconds` are over
    t_ramp = time.perf_counter()
    gap = float(t["stagger_seconds"]) / len(load.clients)
    for i, c in enumerate(load.clients):
        time.sleep(max(0.0, t_ramp + i * gap - time.perf_counter()))
        c.start()
    time.sleep(max(0.0, t_ramp + float(t["ramp_seconds"])
                   - time.perf_counter()))
    gcw = common.GcWatch()
    gcw.arm()

    # ---- the measured window ---------------------------------------------
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

    # ---- after the window ------------------------------------------------
    load.stop.set()
    stats = server.stats()
    records = list(load.records)
    if trace is not None:
        run_.trace = trace.finish()
        run_.spans = tap.records
        run_.counters["decode_kernel_pallas"] = pallas_in_step(
            dec, server, slots)
    server.close()                      # fails what is still in flight
    for c in load.clients:
        c.join(timeout=30)

    window = t_close - t_open
    in_window = []
    ttft, itl, slices = [], [], [0] * max(1, round(
        window / float(t["slice_seconds"])))
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
                slices[min(len(slices) - 1, int(
                    (s - t_open) / float(t["slice_seconds"])))] += 1
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
        # the longest pause in delivery over all streams together (one
        # tick where nothing stalls), and when in the window it began
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
    run_.notes["slices"] = {"seconds": float(t["slice_seconds"]),
                            "tokens": slices}
    run_.correct = bool(check["ok"] and failed == 0 and attempted > 0
                        and stats["recompiles_after_warmup"] == 0)
    return run_


def pallas_in_step(dec, server, slots: int) -> float:
    """1 where the compiled resident step holds a Mosaic custom call
    (the paged-attention kernel), 0 where the XLA gather runs: read
    from the step's compiled text, not from what selection reported."""
    import jax

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    i32 = jax.ShapeDtypeStruct((slots,), np.int32)
    lowered = dec.step.lower(
        jax.tree_util.tree_map(spec, server._states),
        jax.tree_util.tree_map(spec, server._pool_k),
        jax.tree_util.tree_map(spec, server._pool_v),
        jax.ShapeDtypeStruct((slots, dec.max_blocks_per_seq), np.int32),
        i32, i32, jax.ShapeDtypeStruct((slots,), np.uint32),
        jax.ShapeDtypeStruct((slots,), np.float32),
        jax.ShapeDtypeStruct((slots,), np.bool_))
    return 1.0 if "tpu_custom_call" in lowered.compile().as_text() else 0.0
