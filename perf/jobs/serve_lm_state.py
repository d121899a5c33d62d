"""Job `serve_lm_state`: `serve_lm_closed` for a block whose Mamba
layers keep a recurrent STATE a lane beside the paged table
(`BlockSpec.layer_types` "mamba"): the same clients, load, window and
accounting, imported from `serve_lm_closed` and `serve_lm_ring`
unedited.  `correct` is decided twice, as `serve_lm_ring` decides it
and for the same reason:

  before the window  one seeded sequence (`correct_tokens` positions:
        prompt, then decode; long enough that a wrong decay or a
        rounded state shows) walked through lane 0 of the served
        `slots`-lane step, the other lanes idle: `decoder.step`
        advances table, state and tail, `decoder.step_routing` reads
        the logits and the routing `step` sampled from; held against
        the reference's `compare`, which follows the system's experts
        (`serve_lm_ring.check_against_reference`, given the
        `system_outputs` below, which makes the pools with their
        lanes; the reference's reading one precision `below` is
        computed on a thread under the walk and reported by every
        run).
  after the window  what the SERVER delivered while it was measured
        (`check_served`): `served_requests` requests that ended in the
        window, half of them in lanes an earlier request had filled
        (its state still there when the lane's cursor went back to 0),
        each teacher-forced through the reference over its first
        `served_tokens` positions and every delivered token held
        against the reference's logits there.  Only this covers the
        reset of a lane's state under the tick-ahead scheduler, through
        admission, eviction and the reuse of lanes; the walk cannot.

The weights are made as `serve_lm_ring.make_weights` makes them but
for the arrays the configuration's `assumed.weights` names: a recurrence needs decays,
a step size and a convolution of a realistic size or its state adds
nothing and no wrong state could show.

The reference's four `faults` are not run here (a run has 360 s): the
tests read them at toy widths, and the configuration's
`compare.readings` hold what they read on the chip.
"""
from __future__ import annotations

import gc
import os
import threading
import time

import numpy as np

import common

_HERE = os.path.dirname(os.path.abspath(__file__))
base = common.load_module(os.path.join(_HERE, "serve_lm_closed.py"))
ring = common.load_module(os.path.join(_HERE, "serve_lm_ring.py"))


def make_weights(shapes: dict, seed: int, dtype):
    """`serve_lm_ring.make_weights`'s distribution and slicing
    (normal(0, 0.02), norm scales around 1, an array a slice of its
    leading axis at a time), each slice ROUNDED to `dtype` as it is
    made: that one holds a whole array in float32 first, and the 1.6
    GB of this embedding would stand in the process's peak beside
    everything it holds later.  Over it the draws `assumed.weights`
    names, small arrays made on the host from the same seed: the tied
    embedding at sigma 0.02 / 12, the convolution uniform in +-0.5,
    A_log = log U(1, 16), dt's bias = softplus^-1 of
    log-uniform(0.001, 0.1), D around 1."""
    import math

    import jax
    import jax.numpy as jnp

    def gen(key, shape, sigma, scale):
        parts = math.gcd(shape[0], 64)

        def part(k):
            v = sigma * jax.random.normal(
                k, (shape[0] // parts,) + shape[1:], jnp.float32)
            return ((1.0 + v) if scale else v).astype(dtype)

        return jax.lax.map(part, jax.random.split(key, parts)).reshape(
            shape)

    gen = jax.jit(gen, static_argnums=(1, 2, 3))
    key = jax.random.key(common.seed31(seed))
    rng = np.random.default_rng([common.seed31(seed), 0x55D])
    g = {}
    for i, name in enumerate(sorted(shapes)):
        shape = tuple(shapes[name])
        if name.endswith("ssm_conv.w_0"):
            v = rng.uniform(-0.5, 0.5, shape)
        elif name.endswith("ssm_a_log.w_0"):
            v = np.log(rng.uniform(1.0, 16.0, shape))
        elif name.endswith("ssm_dt.b_0"):
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            v = dt + np.log(-np.expm1(-dt))
        elif name.endswith("ssm_d.w_0"):
            v = 1.0 + 0.02 * rng.standard_normal(shape)
        else:
            g[name] = gen(jax.random.fold_in(key, i), shape,
                          0.02 / 12 if name == "tok_embedding.w_0"
                          else 0.02, ".scale_" in name)
            continue
        g[name] = jax.device_put(jnp.asarray(v, jnp.float32).astype(dtype),
                                 jax.devices()[0])
    return g


def system_outputs(dec, g, toks, slots: int):
    """`serve_lm_ring.system_outputs` for a step whose pools carry the
    lanes' states: `toks` through the step AS THE SERVER RUNS IT,
    `slots` lanes, the sequence in lane 0 from position 0 (where the
    step starts the lane's state from zero) and the other lanes idle:
    `step` advances table, state and tail, `step_routing` reads the
    logits `step` sampled from.  Lane 0's rows alone leave the device.
    -> ([positions, vocab] logits, the routing of every position
    stacked on axis 1, and under "state" lane 0's SSM states after the
    last position, [Mamba layers, H, P, N])."""
    import jax

    n = len(toks)
    need = -(-n // dec.block_size)
    pool_k, pool_v = dec.init_pool(need + 1, jax.devices()[0], lanes=slots)
    tables = np.zeros((slots, dec.max_blocks_per_seq), np.int32)
    tables[0, :need] = 1 + np.arange(need)
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    act = np.arange(slots) == 0
    got, routed = [], []
    for pos in range(n):
        args = (g, pool_k, pool_v, tables,
                np.where(act, pos, 0).astype(np.int32),
                np.where(act, toks[pos], 0).astype(np.int32), zs, zt, act)
        logits, routing = dec.step_routing(*args)
        routed.append({k: v[:, :1] for k, v in routing.items()})
        got.append(logits[:1])
        _, pool_k, pool_v, *_ = dec.step(*args)
    return np.concatenate([np.asarray(x) for x in got]), {
        "state": np.stack([np.asarray(h[0]) for h in pool_k[1]]),
        **{k: np.concatenate([np.asarray(r[k]) for r in routed], 1)
           for k in routed[0]}}


def check_served(cell, run_, server, records, served) -> dict:
    """`served_requests` requests of this run against the reference's
    `served`, each over its first `served_tokens` positions: the
    latest that ENDED inside the window, half of them from lanes that
    an earlier request had used (index at or past `slots`: every
    client's first request finds a fresh lane), half from fresh ones
    where the window still holds such.  The numbers are bounded by the
    configuration's `compare.served_limits`."""
    m, t = cell.config, cell.traffic
    ref, slots = cell.reference(), int(t["slots"])
    n, want = int(t["served_tokens"]), int(t["served_requests"])
    limits = m["compare"]["served_limits"]
    ended = sorted(
        (r["done"], r["idx"]) for r in records
        if r["done"] is not None and r["error"] is None
        and run_.t_window_open <= r["done"] < run_.t_window_close
        and len(served[r["idx"]][1]) == r["want"])
    ended = [i for _, i in ended]
    half = want // 2
    take = ([i for i in ended if i >= slots][-half:]
            + [i for i in ended if i < slots][half - want:])
    take += [i for i in reversed(ended) if i not in take][:want - len(take)]
    if not take:
        return {"ok": False, "limits": limits,
                "why": "no request ended inside the window"}
    requests = [(np.concatenate(served[i]).astype(np.int32),
                 len(served[i][0])) for i in take]
    # the reference wants the room the pools and the states held
    states = server._states
    server._pool_k = server._pool_v = server._inflight = None
    gc.collect()
    out = ref.served(states, m, requests, length=n)
    out.update(requests=take, reused_lanes=sum(i >= slots for i in take),
               positions=n, limits=limits,
               ok=all(out[k] is not None
                      and (lo is None or out[k] >= lo)
                      and (hi is None or out[k] <= hi)
                      for k, (lo, hi) in limits.items()))
    return out


def hbm_marks(cell, run_):
    """Beside every mark of set-up (`cell.mark`) what the chip holds
    and has held, in GB: `serve_hbm_peak_gb` is one number for the
    whole process, and the comparison with the reference, not the
    server, may set it."""
    import jax

    marks = run_.notes["hbm_marks"] = []
    inner = cell.mark

    def mark(what):
        inner(what)
        stats = jax.devices()[0].memory_stats() or {}
        marks.append((what, {k: round(stats[k] / 1e9, 3) for k in (
            "bytes_in_use", "peak_bytes_in_use", "bytes_reserved",
            "peak_bytes_reserved") if k in stats}))

    cell.mark = mark


class ClockWatch(threading.Thread):
    """A thread that only sleeps 20 ms at a time and notes the longest
    it overslept.  Some runs lose 2 to 5 s of deliveries in one gap
    (PERF.md section 7): where this thread slept through the same
    seconds the whole process stood still (the host), and where it kept
    its beat the gap is the device's or the runtime's."""

    def __init__(self):
        super().__init__(daemon=True, name="perf-clock-watch")
        self.stop, self.worst, self.at = threading.Event(), 0.0, 0.0

    def run(self):
        last = time.perf_counter()
        while not self.stop.wait(0.02):
            now = time.perf_counter()
            if now - last > self.worst:
                self.worst, self.at = now - last, last
            last = now


def run(cell):
    ring.system_outputs = system_outputs
    base.serve_closed.make_weights = make_weights
    base.attention_kernel_in_step = ring.attention_kernel_in_step
    base.check_against_reference = ring.check_against_reference
    run_ = common.Run()
    m, t = cell.config, cell.traffic
    hbm_marks(cell, run_)
    dec, server = base.build_server(cell, run_)
    run_.notes["state"] = {
        "layers": dec.state_layers,
        "bytes_per_lane": dec.state_bytes_per_lane,
        "bytes": server.stats()["state_bytes"]}
    streams = ring.record_streams(server)
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
    watch = ClockWatch()
    watch.start()
    base.measure(cell, run_, dec, server, slot_ticks, load.records,
                 load.stop.set, load.clients)
    watch.stop.set()
    run_.counters.update(
        host_clock_gap_max_ms=1e3 * watch.worst,
        host_clock_gap_max_at_s=watch.at - run_.t_window_open)
    cell.mark("window measured")
    served = {i: (s.prompt, s.tokens_so_far()) for i, s in streams.items()}
    run_.notes["served"] = check_served(cell, run_, server,
                                        list(load.records), served)
    cell.mark("served requests compared")
    run_.correct = bool(run_.correct and run_.notes["served"]["ok"])
    return run_
