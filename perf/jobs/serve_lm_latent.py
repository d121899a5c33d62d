"""Job `serve_lm_latent`: `serve_lm_ring` for a block whose cache is ONE
latent row a position (`BlockSpec.kv_lora_rank`) and whose router is a
group-limited softmax without a choice bias: the clients, the load, the
two comparisons, the window and its accounting are `serve_lm_ring`'s,
imported unedited.  Three parts of it assume what this block has not,
and are replaced in its module:

  `system_outputs`  the walk of one seeded sequence through lane 0 of
        the served step hands `step` ONE table (there is no ring
        beside it) and takes the pools as `init_pool` gives them (one
        array and an empty tuple).
  `make_weights`  `serve_lm_ring`'s distribution and slicing, each
        slice rounded to the weights' dtype as it is made, but for the
        embedding at sigma 1 (the configuration's `assumed.weights`:
        at 0.02 a greedy server falls into one token).
  `check_against_reference`  runs after `balance` has made every
        layer's ROUTER MATRIX even, as data.

Why the router is fitted.  The model has no choice bias: a checkpoint's
balance comes from training losses that weights drawn from a seed have
no history of.  Every token's router input shares a large common
component (the mean over tokens), so each seed makes the experts whose
columns lie along it popular and starves the others; on a chip that
holds 40 of 160 experts and sees 2.4 rows an expert a layer that
decides how many held experts a tick touches, and with it the tick
(PERF.md section 6, PR 40).  `balance` walks `FIT_POSITIONS` positions
of seeded tokens through all the lanes of the served step and takes out
of each layer's router matrix its component along the MEAN router
input, so that every expert's mean logit is equal (zero); `FIT_PASSES`
walks, because a layer's input depends on the routing of the layers
before it.  No bias, no flag and no equation changes: decoder and
reference take the same arrays.
"""
from __future__ import annotations

import math
import os
import threading

import numpy as np

import common

ring = common.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "serve_lm_ring.py"))
_compare = ring.check_against_reference

# positions each lane walks for the fit (tokens: lanes x positions),
# and the walks
FIT_POSITIONS, FIT_PASSES = 64, 3
SIGMA, SIGMA_EMBEDDING = 0.02, 1.0


def make_weights(shapes: dict, seed: int, dtype):
    """normal(0, 0.02) matrices, norm scales 1 + that, the embedding at
    sigma 1; an array a slice of its leading axis at a time, each slice
    rounded to `dtype` as it is made (a whole [40, 5120, 1536] array in
    float32 would stand in the process's peak)."""
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
    # a key COMMITTED to the device commits every array made from it,
    # like the pools the walks make, the matrices `even` computes from
    # them and the states the server puts on the device: an uncommitted
    # argument is another jit signature, and with seeded weights left
    # uncommitted `step`, `step_routing` and the reference's four
    # float32 programs were compiled again after the fit and again
    # after the window (70 s of a run with no compiled program)
    key = jax.device_put(jax.random.key(common.seed31(seed)),
                         jax.devices()[0])
    return {n: gen(jax.random.fold_in(key, i), tuple(shapes[n]),
                   SIGMA_EMBEDDING if n == "tok_embedding.w_0" else SIGMA,
                   ".scale_" in n)
            for i, n in enumerate(sorted(shapes))}


def walk_blocks(dec, slots: int, n_tokens: int) -> int:
    """Blocks of the one pool shape that both walks before the window
    use, the fit's (every lane `FIT_POSITIONS` positions) and the
    comparison's (lane 0 `n_tokens`): a second pool shape is a second
    compile of `step` and of `step_routing`, 26 s of a run that starts
    with no compiled program."""
    bs = dec.block_size
    return max(-(-n_tokens // bs), slots * -(-FIT_POSITIONS // bs))


def system_outputs(dec, g, toks, slots: int):
    """`serve_lm_ring.system_outputs` for a decoder with one table and
    no ring: `toks` through the step AS THE SERVER RUNS IT, `slots`
    lanes, the sequence in lane 0 and the other lanes idle, position by
    position: `step` writes the position's latent row, `step_routing`
    reads the logits `step` sampled from.  -> ([positions, vocab]
    logits, the routing of every position stacked on axis 1); lane 0's
    rows alone leave the device."""
    import jax

    n = len(toks)
    need = -(-n // dec.block_size)
    pool_k, pool_v = dec.init_pool(walk_blocks(dec, slots, n) + 1,
                                   jax.devices()[0])
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
        k: np.concatenate([np.asarray(r[k]) for r in routed], 1)
        for k in routed[0]}


def even(w_router, mean_input):
    """The router matrix [d, E] without its component along the mean
    router input [d]: every expert's mean logit is then 0.  Computed in
    float32, kept in the matrix's own dtype (what is fitted is what is
    served)."""
    import jax.numpy as jnp

    w = w_router.astype(jnp.float32)
    u = mean_input / jnp.sqrt(jnp.sum(mean_input * mean_input))
    return (w - u[:, None] * (u @ w)[None, :]).astype(w_router.dtype)


def walk(dec, g, toks, slots: int, blocks: int):
    """`toks` [positions, slots] through every lane of the served step,
    each lane its own table blocks of a pool of `blocks`, position by
    position -> (the mean router input of every layer with experts
    [layers, d], the assignments each routed expert got [layers, E])."""
    import jax
    import jax.numpy as jnp

    n = len(toks)
    need = -(-n // dec.block_size)
    pool_k, pool_v = dec.init_pool(blocks + 1, jax.devices()[0])
    tables = np.zeros((slots, dec.max_blocks_per_seq), np.int32)
    tables[:, :need] = 1 + np.arange(slots * need).reshape(slots, need)
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    act = np.ones(slots, bool)
    e_n = next(s[1] for name, s in dec.state_shapes.items()
               if name.endswith("router.w_0"))

    @jax.jit
    def add(total, loads, routing):
        layers = jnp.arange(loads.shape[0])[:, None, None]
        return (total + routing["inputs"].sum(axis=1),
                loads.at[layers, routing["experts"]].add(1.0))

    total = jnp.zeros((dec.moe_layers, dec.d_model), jnp.float32)
    loads = jnp.zeros((dec.moe_layers, e_n), jnp.float32)
    for pos in range(n):
        args = (g, pool_k, pool_v, tables, np.full(slots, pos, np.int32),
                toks[pos], zs, zt, act)
        total, loads = add(total, loads, dec.step_routing(*args)[1])
        _, pool_k, pool_v, *_ = dec.step(*args)
    return total / (n * slots), loads


def balance(cell, dec, g, n_tokens: int) -> dict:
    """Make every layer's router matrix in `g` even, in place,
    `FIT_PASSES` walks over.  -> what the fit did to the loads, for the
    run's notes: the largest routed expert's load over the mean, a
    layer, under the seeded matrices and as the LAST walk found it
    (before that walk's own fit)."""
    m, slots = cell.config, int(cell.traffic["slots"])
    rng = np.random.default_rng([common.seed31(cell.seed), 0xB1A5])
    toks = rng.integers(0, m["vocab_size"],
                        (FIT_POSITIONS, slots)).astype(np.int32)
    names = sorted((n for n in g if n.endswith("router.w_0")),
                   key=lambda n: int(n.split(".")[0].split("_")[1]))
    found = []
    for _ in range(FIT_PASSES):
        means, loads = walk(dec, g, toks, slots,
                            walk_blocks(dec, slots, n_tokens))
        found.append([float(l.max() / l.mean()) for l in loads])
        for name, mean in zip(names, means):
            g[name] = even(g[name], mean)
    return {"tokens": int(toks.size), "layers": len(names),
            "passes": FIT_PASSES,
            "max_load_over_mean_seeded": [round(x, 3) for x in found[0]],
            "max_load_over_mean_fitted": [round(x, 3) for x in found[-1]]}


def warm_reference(cell, g, n_tokens: int):
    """Compile the reference's two forward passes (float32, and the
    bfloat16 of `below`) on threads of their own, under the fit's
    walks: on a machine with no compiled program they take 25 s each,
    which `serve_lm_ring.check_against_reference` would pay one after
    the other on its one thread (a cold run read 345 s of the 360 a run
    may take).  The programs depend on shapes and dtypes alone, so any
    tokens do.  -> the threads, to be joined."""
    import jax.numpy as jnp

    m, ref = cell.config, cell.reference()
    toks = np.zeros(n_tokens, np.int32)
    threads = [threading.Thread(
        target=lambda dtype=dtype: ref.forward(g, m, toks, dtype=dtype),
        name=f"perf-reference-warm-{i}")
        for i, dtype in enumerate((jnp.float32, jnp.bfloat16))]
    for t in threads:
        t.start()
    return threads


def check_against_reference(cell, dec, g, n_tokens: int):
    """`serve_lm_ring.check_against_reference` over weights whose
    router matrices `balance` has made even first: `g` is the dict
    `build_server` goes on to serve, so the server holds what was
    compared."""
    warming = warm_reference(cell, dict(g), n_tokens)
    fitted = balance(cell, dec, g, n_tokens)
    for t in warming:
        t.join()
    cell.mark("router matrices made even")
    out = _compare(cell, dec, g, n_tokens)
    out["balance"] = fitted
    return out


def run(cell):
    ring.make_weights = make_weights
    ring.system_outputs = system_outputs
    ring.check_against_reference = check_against_reference
    return ring.run(cell)
