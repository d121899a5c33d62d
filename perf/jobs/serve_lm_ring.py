"""Job `serve_lm_ring`: `serve_lm_closed` for a block whose sliding
layers keep a RING of blocks beside the paged table (`BlockSpec
.layer_types`): the same clients, load, weights, window and accounting,
imported.  `correct` is decided twice here:

  before the window  `serve_lm_closed`'s comparison of one seeded
        sequence, logits and routing, walked through the served
        `slots`-lane step: `decoder.step` writes every position into
        table and ring, `decoder.step_routing` reads what it sampled
        from.  Two parts of `serve_lm_closed` assume ONE table and
        are replaced in its module: `check_against_reference` (the
        pair of tables, lane 0's ring, and only lane 0's rows leave
        the device: 1152 positions, not 48) and
        `attention_kernel_in_step` (lowers with the pair).
  after the window  what the SERVER delivered while it was measured
        (`check_served`): requests it decoded with every slot live,
        past the ring's wrap, half of them in slots an earlier request
        had filled, each teacher-forced through the reference and
        every delivered token held against the reference's logits
        there.  This is what covers admission, eviction and the reuse
        of rings under the tick-ahead scheduler; the walk above cannot.

A run has 360 s, on a machine that may hold no compiled program, and
ramp and window take 128 of them.  So the weights are made by
`make_weights` here (nine small programs for `serve_closed`'s one large
one), the reference compiles on a thread under the walk, and the
reference's two `faults`, which the limits must refuse beside its
`below`, are NOT run here: the tests read them at toy widths, and the
configuration's `compare.readings` hold what they read on the chip.

The ramp of `serve_lm_closed.run` is repeated in `run`, which has to
stand between the server's construction and the load to record the
streams.
"""
from __future__ import annotations

import gc
import math
import os
import threading
import time

import numpy as np

import common

base = common.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "serve_lm_closed.py"))

# requests of the window held against the reference, each over its
# first `correct_tokens` positions (one compiled forward pass for all)
SERVED_REQUESTS = 6


def make_weights(shapes: dict, seed: int, dtype):
    """`serve_closed.make_weights`'s distribution (normal(0, 0.02)
    matrices and vectors, norm scales around 1), each array made a
    slice of its leading axis at a time: the one program over whole
    [64, 2304, 896] arrays compiles for 40 s on a machine with no
    cache, these nine (one a shape) for 6."""
    import jax
    import jax.numpy as jnp

    def gen(key, shape, scale):
        parts = math.gcd(shape[0], 64)
        v = 0.02 * jax.lax.map(
            lambda k: jax.random.normal(
                k, (shape[0] // parts,) + shape[1:], jnp.float32),
            jax.random.split(key, parts)).reshape(shape)
        return ((1.0 + v) if scale else v).astype(dtype)

    gen = jax.jit(gen, static_argnums=(1, 2))
    key = jax.random.key(common.seed31(seed))
    return {n: gen(jax.random.fold_in(key, i), tuple(shapes[n]),
                   ".scale_" in n)
            for i, n in enumerate(sorted(shapes))}


def system_outputs(dec, g, toks, slots: int):
    """`toks` through the step AS THE SERVER RUNS IT: `slots` lanes, the
    sequence in lane 0 and the other lanes idle, position by position:
    `step` writes the position into table and ring, `step_routing`
    reads the logits `step` sampled from, each attending to the table
    and the ring of the positions before.  -> ([positions, vocab]
    logits, the routing of every position stacked on axis 1)."""
    import jax

    n, ring = len(toks), dec.window_blocks_per_seq
    need = -(-n // dec.block_size)
    pool_k, pool_v = dec.init_pool(need + 1, jax.devices()[0],
                                   window_blocks=ring + 1)
    tables = np.zeros((slots, dec.max_blocks_per_seq), np.int32)
    tables[0, :need] = 1 + np.arange(need)
    # lane 0's ring; the idle lanes write to the null block
    rings = np.zeros((slots, ring), np.int32)
    rings[0] = dec.slot_rings(1)[0]
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    act = np.arange(slots) == 0
    got, routed = [], []
    for pos in range(n):
        args = (g, pool_k, pool_v, (tables, rings),
                np.where(act, pos, 0).astype(np.int32),
                np.where(act, toks[pos], 0).astype(np.int32), zs, zt, act)
        # lane 0's rows alone leave the device
        logits, routing = dec.step_routing(*args)
        routed.append({k: v[:, :1] for k, v in routing.items()})
        got.append(logits[:1])
        _, pool_k, pool_v, *_ = dec.step(*args)
    return np.concatenate([np.asarray(x) for x in got]), {
        k: np.concatenate([np.asarray(r[k]) for r in routed], 1)
        for k in routed[0]}


def attention_kernel_in_step(dec, server, slots: int) -> float:
    """`serve_lm_closed.attention_kernel_in_step` with the tables the
    step takes, read from the LOWERED step: a Mosaic call there is a
    Pallas kernel (the compiler's own grouped-matmul calls for
    `ragged_dot` come later), and lowering costs a second where
    compiling the step once more costs 14 on a machine with no cache."""
    import jax

    def spec(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype)

    i32 = jax.ShapeDtypeStruct((slots,), np.int32)
    return float("tpu_custom_call" in dec.step.lower(
        jax.tree_util.tree_map(spec, server._states),
        jax.tree_util.tree_map(spec, server._pool_k),
        jax.tree_util.tree_map(spec, server._pool_v),
        jax.tree_util.tree_map(spec, server._step_tables()),
        i32, i32, jax.ShapeDtypeStruct((slots,), np.uint32),
        jax.ShapeDtypeStruct((slots,), np.float32),
        jax.ShapeDtypeStruct((slots,), np.bool_)).as_text())


def check_against_reference(cell, dec, g, n_tokens: int):
    """`serve_lm_closed.check_against_reference` through the
    `system_outputs` above, with the reference warmed under the walk:
    its float32 pass compiles for half a minute on a machine with no
    cache, and the walk (1152 positions, two dispatches of 28 ms each)
    leaves the host idle.  What the thread computes is the reference's
    reading one precision `below`, which needs no walk and which the
    limits must refuse: under the walk it costs no time, so every run
    reports it, traced or not."""
    m, ref = cell.config, cell.reference()
    rng = np.random.default_rng([common.seed31(cell.seed), 0xC0DE])
    toks = rng.integers(0, m["vocab_size"], n_tokens).astype(np.int32)
    below = {}
    warming = threading.Thread(
        target=lambda: below.update(ref.below(g, m, toks)),
        name="perf-reference")
    warming.start()
    got = system_outputs(dec, g, toks, int(cell.traffic["slots"]))
    warming.join()
    out = ref.compare(g, m, toks, *got)
    limits = m["compare"]["limits"]
    out.update(positions=n_tokens, limits=limits, ok=bool(
        out["finite"] and all(
            (lo is None or out[k] >= lo) and (hi is None or out[k] <= hi)
            for k, (lo, hi) in limits.items())), below=below)
    return out


def record_streams(server) -> dict:
    """{the request's seed (its index in the load): its stream} of
    every request submitted from now on; a stream keeps its prompt and
    the tokens delivered (`tokens_so_far`)."""
    streams = {}
    submit = server.submit

    def recorded(prompt, max_new, **kw):
        streams[kw["seed"]] = stream = submit(prompt, max_new, **kw)
        return stream

    server.submit = recorded
    return streams


def cursors_at(records, served, t: float, window: int) -> dict:
    """Where the requests in flight at `t` stood: the position of the
    token each was fed last (while its prompt runs: the prompt's share
    that the time to its first token had covered by then)."""
    cursors, in_prompt = [], 0
    for rec in records:
        stamps, submit = rec["stamps"], rec["submit"]
        if not stamps or submit > t or (rec["done"] or t + 1) <= t:
            continue
        prompt = len(served[rec["idx"]][0])
        out = sum(1 for s in stamps if s < t)
        in_prompt += not out
        cursors.append(prompt - 1 + out if out else int(
            prompt * (t - submit) / (stamps[0] - submit)))
    if not cursors:
        return {"requests": 0}
    q1, median, q3 = np.percentile(cursors, [25, 50, 75])
    return {"requests": len(cursors), "in_prompt": in_prompt,
            "min": min(cursors), "q1": float(q1), "median": float(median),
            "q3": float(q3), "max": max(cursors),
            "past_window_share": float(np.mean(
                np.asarray(cursors) >= window))}


def check_served(cell, run_, server, records, served) -> dict:
    """`SERVED_REQUESTS` requests of this run against the reference's
    `served`, each over its first `correct_tokens` positions (past the
    window): the latest-started that reached so far before the window
    closed, half of them from slots that an earlier request had used
    (index at or past `slots`: every client's first request finds a
    fresh slot), half from fresh ones.  The numbers are bounded by the
    configuration's `compare.served_limits`."""
    m, t = cell.config, cell.traffic
    ref, n, slots = cell.reference(), int(t["correct_tokens"]), \
        int(t["slots"])
    limits = m["compare"]["served_limits"]
    stamps = {r["idx"]: r["stamps"] for r in records}
    # position n - 1 samples a request's token number n - prompt
    reach = sorted(
        i for i, (p, toks) in served.items()
        if len(p) + len(toks) > n and len(stamps[i]) > n - len(p)
        and stamps[i][n - len(p)] < run_.t_window_close)
    half = SERVED_REQUESTS // 2
    take = ([i for i in reach if i >= slots][-half:]
            + [i for i in reach if i < slots][half - SERVED_REQUESTS:])
    take += [i for i in reversed(reach)
             if i not in take][:SERVED_REQUESTS - len(take)]
    if not take:
        return {"ok": False, "limits": limits,
                "why": f"no request reached {n + 1} positions"}
    requests = [(np.concatenate(served[i])[:n + 1].astype(np.int32),
                 len(served[i][0])) for i in take]
    in_window = sum(
        1 for i, (_, start) in zip(take, requests)
        for s in stamps[i][:n + 1 - start] if s >= run_.t_window_open)
    # the reference wants the room the pools held
    states = server._states
    server._pool_k = server._pool_v = server._inflight = None
    gc.collect()
    out = ref.served(states, m, requests)
    out.update(requests=take, reused_slots=sum(i >= slots for i in take),
               positions=n, tokens_in_window=in_window, limits=limits,
               ok=all(out[k] is not None
                      and (lo is None or out[k] >= lo)
                      and (hi is None or out[k] <= hi)
                      for k, (lo, hi) in limits.items()))
    return out


def run(cell):
    base.serve_closed.make_weights = make_weights
    base.attention_kernel_in_step = attention_kernel_in_step
    base.check_against_reference = check_against_reference
    run_ = common.Run()
    m, t = cell.config, cell.traffic
    dec, server = base.build_server(cell, run_)
    streams = record_streams(server)
    slot_ticks = base.count_slot_ticks(cell, server)
    load = base.serve_closed.Load(
        cell, server,
        base.serve_closed.permuted_table(t["lengths"], cell.seed),
        m["vocab_size"])
    # `serve_lm_closed.run`'s ramp: clients start one by one over
    # `stagger_seconds` and run on until `ramp_seconds` are over
    t_ramp = time.perf_counter()
    gap = float(t["stagger_seconds"]) / len(load.clients)
    for i, c in enumerate(load.clients):
        time.sleep(max(0.0, t_ramp + i * gap - time.perf_counter()))
        c.start()
    time.sleep(max(0.0, t_ramp + float(t["ramp_seconds"])
                   - time.perf_counter()))
    base.measure(cell, run_, dec, server, slot_ticks, load.records,
                 load.stop.set, load.clients)
    records = list(load.records)
    served = {i: (s.prompt, s.tokens_so_far()) for i, s in streams.items()}
    run_.notes["cursors_at_open"] = cursors_at(
        records, served, run_.t_window_open, int(dec.window))
    run_.notes["served"] = check_served(cell, run_, server, records,
                                        served)
    run_.correct = bool(run_.correct and run_.notes["served"]["ok"])
    return run_
