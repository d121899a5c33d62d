"""Job `serve_lm_docqa_parallel`: `serve_lm_docqa`'s document sessions for
a block whose EVERY layer keeps a lane's Mamba-2 state and tail AND a
plane of the K/V table (`BlockSpec.parallel_attention`, lm_block's
fourteenth description) under the PREFIX CACHE, whose hits start a lane
from a SNAPSHOT of every layer's state beside the document's shared
blocks (`GenerationServer(state_snapshots=)`).  `serve_lm_docqa.run` is
what runs: the documents built in set-up, `DocLoad`, the ramp, the
window and its accounting, `check_served` (delivered tokens of requests
on two documents against the reference, every one of them served from a
restored snapshot and shared blocks), `warm_served` and the comparison
before the window (`serve_lm_ring.check_against_reference`) are imported
UNEDITED, as are `serve_lm_docqa_state.build_server` (the server with
`state_snapshots` from the traffic file) and `serve_lm_state.hbm_marks`.

Replaced in those modules before `serve_lm_docqa.run` runs, because they
name what this block does not have or lack what it needs:

  `serve_lm_docqa_state.make_weights` (its lines 72 to 96, which its
        `build_server` calls)  there `serve_lm_docqa.make_weights`'
        arrays at one sigma (the embedding at 1) and a delta rule's
        draws; here the same generator (a committed key folded by the
        name's place, a slice of the leading axis at a time, the
        programs compiled from a pool of threads) at the sigma the
        configuration's `weights.sigma` gives AN ARRAY, and over it a
        Mamba-2 mixer's own draws as `serve_lm_state.make_weights` (its
        lines 85 to 104) makes them: `A_log`, dt's bias, `D`, the
        convolution's taps.
  `serve_lm_latent.system_outputs` (its lines 99 to 128, which
        `serve_lm_docqa.run` hands to the comparison)  there the pools
        are made without lanes and every position's logits are kept;
        here `serve_lm_state.system_outputs`' walk (its lines 108 to
        140: pools with `lanes`, lane 0 from position 0), where the
        logits are read at the positions the reference's
        `compared_positions` names alone (the whole vocabulary at 2560
        positions is 2.7 GB, and half the walk's seconds were the 2304
        other dispatches) and by `step_logits`, which returns nothing
        else; at the LAST position the states lane 0 held before it
        are read from the lanes' pool and `step_routing` gives, from
        ONE program, the logits, what every layer's recurrence was
        given and what it left (every lane's states, 1.4 GB: once, when
        the reference's warming passes have long ended; at every
        compared position it stood beside them and the process's peak
        read 15.3 to 15.8 GB); and after the walk lane 0's STATES and
        TAILS of every layer are read from the lanes' pools and its K
        and V ROWS of every layer from the table: a layer of this block
        is in both.
  `serve_lm_latent.warm_reference` (its lines 203 to 221)  there the
        reference's two passes are warmed over every position's logits;
        here at the compared positions, the programs the comparison
        runs.
  `serve_lm_docqa.balance` (its lines 129 to 170)  there a sigmoid
        router's choice bias is fitted; this block routes nothing: {},
        and the walk's two programs are compiled in its place.
  `serve_lm_docqa.build_server` (its lines 184 to 227)  there the
        server is made without snapshots; here
        `serve_lm_docqa_state.build_server` (its lines 173 to 221),
        unedited.

After the run the notes gain the cache's snapshot counts, as
`serve_lm_docqa_state.run`'s (its lines 224 to 246), and `correct` also
needs every admitted request of the load to have restored a snapshot and
no hit to have been cut back for want of one.
"""
from __future__ import annotations

import os
import threading
import types

import numpy as np

import common

_HERE = os.path.dirname(os.path.abspath(__file__))
docqa = common.load_module(os.path.join(_HERE, "serve_lm_docqa.py"))
docqa_state = common.load_module(os.path.join(_HERE,
                                              "serve_lm_docqa_state.py"))
latent = docqa.latent
# the cell `run` was given: `make_weights` and `system_outputs` are
# called with shapes and tokens alone
_cell = {}
# a Mamba-2 mixer's arrays that are drawn at its own initialisation
HOST_DRAWN = ("ssm_conv.w_0", "ssm_a_log.w_0", "ssm_dt.b_0", "ssm_d.w_0")


def make_weights(shapes: dict, seed: int, dtype):
    """Normal(0, sigma) matrices, sigma an array's own
    (`weights.sigma` of the configuration, by the name's last part;
    "default" elsewhere), norm scales 1 + normal(0, default), made as
    `serve_lm_docqa.make_weights` makes its arrays; and a Mamba-2
    mixer's draws over them, small arrays made on the host from the same
    seed and committed to the device like the others."""
    import math
    from concurrent.futures import ThreadPoolExecutor

    import jax
    import jax.numpy as jnp

    sigma_of = _cell["cell"].config["weights"]["sigma"]

    def gen(key, shape, sigma, scale):
        parts = math.gcd(shape[0], 64)

        def part(k):
            v = sigma * jax.random.normal(
                k, (shape[0] // parts,) + shape[1:], jnp.float32)
            return ((1.0 + v) if scale else v).astype(dtype)

        return jax.lax.map(part, jax.random.split(key, parts)).reshape(
            shape)

    gen = jax.jit(gen, static_argnums=(1, 2, 3))
    device = jax.devices()[0]
    key = jax.device_put(jax.random.key(common.seed31(seed)), device)
    rng = np.random.default_rng([common.seed31(seed), 0x55D])
    names = sorted(shapes)

    def last(n):
        return n.split(".", 1)[-1] if n.startswith("layer_") else n

    def host(n, shape):
        if last(n) == "ssm_conv.w_0":
            return rng.uniform(-0.5, 0.5, shape)
        if last(n) == "ssm_a_log.w_0":
            return np.log(rng.uniform(1.0, 16.0, shape))
        if last(n) == "ssm_dt.b_0":
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            return dt + np.log(-np.expm1(-dt))
        return 1.0 + sigma_of["default"] * rng.standard_normal(shape)

    # the host's draws in the names' order (one generator), then the
    # device's programs side by side
    made = {n: jax.device_put(jnp.asarray(
        host(n, tuple(shapes[n])), jnp.float32).astype(dtype), device)
        for n in names if last(n) in HOST_DRAWN}

    def make(i):
        n = names[i]
        return made[n] if n in made else gen(
            jax.random.fold_in(key, i), tuple(shapes[n]),
            float(sigma_of.get(last(n), sigma_of["default"])),
            ".scale_" in n)

    with ThreadPoolExecutor(max_workers=8,
                            thread_name_prefix="perf-weights") as pool:
        return dict(zip(names, pool.map(make, range(len(names)))))


def _walk_of(dec, slots: int, n: int):
    """What a walk of n positions through lane 0 starts from: (its
    blocks, the pools with their lanes, the tables, zero seeds and
    temperatures, the lanes' mask)."""
    import jax

    need = -(-n // dec.block_size)
    pool_k, pool_v = dec.init_pool(need + 1, jax.devices()[0], lanes=slots)
    tables = np.zeros((slots, dec.max_blocks_per_seq), np.int32)
    tables[0, :need] = 1 + np.arange(need)
    return (need, pool_k, pool_v, tables, np.zeros(slots, np.uint32),
            np.zeros(slots, np.float32), np.arange(slots) == 0)


def system_outputs(dec, g, toks, slots: int):
    """`serve_lm_state.system_outputs` for a step whose every layer is in
    BOTH pools: `toks` through the step AS THE SERVER RUNS IT, `slots`
    lanes, the sequence in lane 0 from position 0 and the other lanes
    idle.  -> (the logits of the reference's `compared_positions`
    [rows, vocab], and what the walk left: under "state" and "tails"
    lane 0's after the last position [layers, ...], under "k_rows" and
    "v_rows" the table's rows of lane 0's blocks [layers, positions,
    Hkv * dh], and of the last position "state_before" (lane 0's states
    in the lanes' pool before it), "ssm_inputs" [layers, 1, ...] and
    "ssm_states" (what `step_routing` says each layer's recurrence was
    given there and left))."""
    cell = _cell["cell"]
    n = len(toks)
    keep = set(cell.reference().compared_positions(cell.config, n).tolist())
    need, pool_k, pool_v, tables, zs, zt, act = _walk_of(dec, slots, n)
    got, last = [], {}
    for pos in range(n):
        args = (g, pool_k, pool_v, tables,
                np.where(act, pos, 0).astype(np.int32),
                np.where(act, toks[pos], 0).astype(np.int32), zs, zt, act)
        if pos == n - 1:
            logits, routing = dec.step_routing(*args)
            last = {
                "state_before": np.stack(
                    [np.asarray(h[0]) for h in pool_k[1]]),
                "ssm_inputs": np.asarray(routing["ssm_inputs"][:, :1]),
                "ssm_states": np.asarray(routing["ssm_states"][:, 0])}
            del routing
        elif pos in keep:
            logits = dec.step_logits(*args)
        if pos in keep:
            # (to the host as they are made: 256 positions of the
            # vocabulary are a quarter of a gigabyte beside the weights
            # and the reference)
            got.append(np.asarray(logits[:1]))
        _, pool_k, pool_v, *_ = dec.step(*args)

    def rows(pool):
        return np.asarray(pool[0][:, 1:1 + need], np.float32).reshape(
            pool[0].shape[0], -1, pool[0].shape[-1])[:, :n]

    return np.concatenate(got), {
        "state": np.stack([np.asarray(h[0]) for h in pool_k[1]]),
        "tails": np.stack([np.asarray(t[0]) for t in pool_v[1]]),
        "k_rows": rows(pool_k), "v_rows": rows(pool_v), **last}


def warm_reference(cell, g, n_tokens: int):
    """`serve_lm_latent.warm_reference` at the compared positions: the
    reference's two passes (float32, and the bfloat16 of `below`)
    compiled on threads of their own.  -> the threads, to be joined."""
    import jax.numpy as jnp

    m, ref = cell.config, cell.reference()
    toks = np.zeros(n_tokens, np.int32)
    at = ref.compared_positions(m, n_tokens)
    threads = [threading.Thread(
        target=lambda dtype=dtype: ref.forward(g, m, toks, dtype=dtype,
                                               logits_at=at),
        name=f"perf-reference-warm-{i}")
        for i, dtype in enumerate((jnp.float32, jnp.bfloat16))]
    for t in threads:
        t.start()
    return threads


def balance(cell, dec, g, n_tokens: int) -> dict:
    """Nothing is routed: nothing to fit.  The walk's three programs
    compile here, at the comparison's pool shape, while the reference's
    compile on their threads (`serve_lm_docqa.check_against_reference`
    joins those next: a run with no compiled program stood 26 s there
    with nothing to do, and compiled these under the walk)."""
    import jax

    slots = int(cell.traffic["slots"])
    _, pool_k, pool_v, tables, zs, zt, act = _walk_of(dec, slots, n_tokens)
    args = (g, pool_k, pool_v, tables, np.zeros(slots, np.int32),
            np.zeros(slots, np.int32), zs, zt, act)
    for program in (dec.step_routing, dec.step_logits, dec.step):
        jax.block_until_ready(program(*args))
    return {}


def run(cell):
    _cell["cell"] = cell
    latent.system_outputs = system_outputs
    latent.warm_reference = warm_reference
    docqa.balance = balance
    docqa_state.make_weights = make_weights
    docqa.build_server = docqa_state.build_server
    marks = types.SimpleNamespace(notes={})
    docqa_state.state.hbm_marks(cell, marks)
    run_ = docqa.run(cell)
    run_.notes.update(marks.notes)
    snaps = {k: v
             for k, v in docqa_state._built["cache"].prefix_stats().items()
             if k.startswith(("state_snapshot", "prefix_blocks_cut"))}
    documents = len(cell.traffic["documents"]["lengths"])
    snaps["requests_started"] = int(run_.counters["requests_started"])
    run_.notes["snapshots"] = snaps
    # every request of the load that was ADMITTED started from a restored
    # snapshot beside its document's shared blocks, and no hit lost
    # blocks to a missing snapshot
    run_.correct = bool(
        run_.correct and snaps["prefix_blocks_cut"] == 0
        and snaps["state_snapshots_restored"] >= (
            snaps["requests_started"] - int(cell.traffic["clients"]))
        and snaps["state_snapshots_saved"] >= documents)
    return run_
