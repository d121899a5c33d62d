"""Job `serve_lm_loop`: `serve_lm_closed` for a block whose ONE stack of
layers runs several passes a token (`BlockSpec.passes`), each (pass,
layer) pair with a plane of the paged pool to itself, on a server whose
POOL IS SIZED BY THE TRAFFIC FILE (`kv_blocks`) and not by `slots x
context / block_size`: a position costs `passes` times a plain block's
bytes, so the chip's memory, through `PagedKVCache.can_admit`, and not
the slot count says how many sequences run.  The clients, the load, the
window and the accounting are `serve_lm_closed`'s, the walk, the
reference's thread and the served comparison `serve_lm_ring`'s and
`serve_lm_state`'s: imported, unedited.  `correct` is decided twice, as
`serve_lm_state` decides it:

  before the window  one seeded sequence (`correct_tokens` positions:
        prompt, then decode through the pool's planes) walked through
        lane 0 of the served `slots`-lane step, the other lanes idle:
        `decoder.step` writes every pass's K/V, `decoder.step_routing`
        reads the logits, x_t of every pass and the exit gates; held
        against the reference's `compare` (`serve_lm_ring
        .check_against_reference` through `serve_lm_closed
        .system_outputs`, the reference's reading one precision `below`
        computed on a thread under the walk and reported by every run).
  after the window  what the SERVER delivered while it was measured
        (`serve_lm_state.check_served`): `served_requests` requests
        that ended in the window, half of them in lanes and blocks an
        earlier request had filled, each teacher-forced through the
        reference over its first `served_tokens` positions.

What is new here: the ORDER of the requests (the traffic file's table
as stored, whatever the seed: with 12 lanes and some 80 requests a
window the order alone spreads `itl_p95_ms` over the seeds by more than
a new cell is admitted under), the pool's size (`build_server`), the weights
(`make_weights`: the configuration's `assumed.weights` draws the
embedding at sigma 1 and the output norms' scales around 0.1), and the line people
read (`device_share_by_scope`: a loop's body names its parts one level
further down).

The reference's five `faults` are not run here (a run has 360 s): the
tests read them at toy widths, and the configuration's
`compare.readings` hold what they read on the chip.
"""
from __future__ import annotations

import math
import os
import time

import numpy as np

import common

_HERE = os.path.dirname(os.path.abspath(__file__))
state = common.load_module(os.path.join(_HERE, "serve_lm_state.py"))
base, ring = state.base, state.ring

# the draws the configuration's `assumed.weights` names beside the
# other cells' normal(0, 0.02): the embedding's sigma, and what the
# output norms' scales lie around
SIGMA, SIGMA_EMBEDDING, POST_NORM_SCALE = 0.02, 1.0, 0.1


def make_weights(shapes: dict, seed: int, dtype):
    """`serve_lm_state.make_weights`'s slicing (an array a slice of its
    leading axis at a time, each slice rounded to `dtype` as it is
    made) over `serve_closed`'s distribution (normal(0, 0.02), norm
    scales 1 + noise), but for the embedding at sigma 1 and the two
    OUTPUT norms' scales of a layer around 0.1."""
    import jax
    import jax.numpy as jnp

    def gen(key, shape, sigma, around):
        parts = math.gcd(shape[0], 64)

        def part(k):
            v = sigma * jax.random.normal(
                k, (shape[0] // parts,) + shape[1:], jnp.float32)
            return (around * (1.0 + v) if around else v).astype(dtype)

        return jax.lax.map(part, jax.random.split(key, parts)).reshape(
            shape)

    gen = jax.jit(gen, static_argnums=(1, 2, 3))
    key = jax.random.key(common.seed31(seed))
    return {n: gen(jax.random.fold_in(key, i), tuple(shapes[n]),
                   SIGMA_EMBEDDING if n == "tok_embedding.w_0" else SIGMA,
                   POST_NORM_SCALE if "_post_norm.scale_" in n
                   else float(".scale_" in n))
            for i, n in enumerate(sorted(shapes))}


def build_server(cell, run_):
    """`serve_lm_closed.build_server`, the server's pool taken from the
    traffic file: that function gives every server `slots x context /
    block_size` blocks, so the class it constructs is wrapped for the
    length of the call.  -> (decoder, server)."""
    import paddle_tpu.serving as serving

    t = cell.traffic
    kv_blocks = int(t["kv_blocks"])
    server_class = serving.GenerationServer

    def sized(*args, **kw):
        return server_class(*args, **dict(kw, kv_blocks=kv_blocks))

    serving.GenerationServer = sized
    try:
        dec, server = base.build_server(cell, run_)
    finally:
        serving.GenerationServer = server_class
    run_.notes["pool"] = {
        "kv_blocks": kv_blocks,
        "slots_x_context": int(t["slots"]) * int(t["context"])
        // int(t["block_size"]),
        "planes": dec.kv_planes, "passes": dec.passes,
        "bytes_per_block": dec.bytes_per_block,
        "bytes": (kv_blocks + 1) * dec.bytes_per_block}
    return dec, server


def device_share_by_scope(run_) -> dict:
    """`serve_lm_closed.device_share_by_scope` for a step whose layers
    lie in a loop's body: there a part's scope reads
    `paged_decoder/while/body/.../paged_decoder/<part>/...`, so the
    part is what follows the LAST `paged_decoder/`."""
    from paddle_tpu import profiler

    by_scope = profiler.scope_seconds(run_.trace["op_seconds"],
                                      "paged_decoder.step")
    total = sum(by_scope.values())
    out = {}
    for scope, t in by_scope.items():
        part = (scope.rsplit("paged_decoder/", 1)[1].split("/")[0]
                if "paged_decoder/" in scope else "other")
        out[part] = out.get(part, 0.0) + 100.0 * t / total
    return {k: round(v, 3) for k, v in
            sorted(out.items(), key=lambda kv: -kv[1])} if total else {}


def run(cell):
    ring.system_outputs = base.system_outputs
    base.serve_closed.make_weights = make_weights
    base.attention_kernel_in_step = ring.attention_kernel_in_step
    base.check_against_reference = ring.check_against_reference
    base.device_share_by_scope = device_share_by_scope
    run_ = common.Run()
    m, t = cell.config, cell.traffic
    state.hbm_marks(cell, run_)
    dec, server = build_server(cell, run_)
    streams = ring.record_streams(server)
    slot_ticks = base.count_slot_ticks(cell, server)
    # the table in the order the traffic file stores it, whatever the
    # seed (the file's `order` says why): the seed makes the token ids
    # and the weights
    load = base.serve_closed.Load(
        cell, server, [tuple(int(v) for v in row)
                       for row in t["lengths"]["table"]], m["vocab_size"])
    # `serve_lm_closed.run`'s ramp: clients start one by one over
    # `stagger_seconds` and run on until `ramp_seconds` are over
    t_ramp = time.perf_counter()
    gap = float(t["stagger_seconds"]) / len(load.clients)
    for i, c in enumerate(load.clients):
        time.sleep(max(0.0, t_ramp + i * gap - time.perf_counter()))
        c.start()
    time.sleep(max(0.0, t_ramp + float(t["ramp_seconds"])
                   - time.perf_counter()))
    watch = state.ClockWatch()
    watch.start()
    base.measure(cell, run_, dec, server, slot_ticks, load.records,
                 load.stop.set, load.clients)
    watch.stop.set()
    run_.counters.update(
        host_clock_gap_max_ms=1e3 * watch.worst,
        host_clock_gap_max_at_s=watch.at - run_.t_window_open)
    cell.mark("window measured")
    served = {i: (s.prompt, s.tokens_so_far()) for i, s in streams.items()}
    out = run_.notes["served"] = state.check_served(
        cell, run_, server, list(load.records), served)
    # how varied the compared requests' tokens are (1: all distinct):
    # a greedy stream that fell into one token compares nothing
    out["distinct_token_share"] = float(np.mean(
        [len(set(served[i][1])) / len(served[i][1])
         for i in out.get("requests", [])] or [0.0]))
    cell.mark("served requests compared")
    run_.correct = bool(run_.correct and out["ok"])
    return run_
