"""Job `serve_lm_hybrid`: `serve_lm_state` for a block whose lanes keep a
delta-rule STATE and a convolution tail a layer BESIDE a latent table
(`BlockSpec.layer_types` "delta_rule" with `kv_lora_rank` > 0) under a
group-limited sigmoid router with a FITTED choice bias.
`serve_lm_state.run` is what runs: the clients, the load, the ramp, the
window and its accounting, `check_served` (delivered tokens of requests
that ended in the window, half of them from reused lanes), `hbm_marks`
and `ClockWatch`, imported UNEDITED, as are `serve_lm_latent` (the
committed key of `make_weights`, `walk_blocks`, `warm_reference`) and
`serve_lm_balanced` (the sign rule's constants `FIT_*`).

Replaced in those modules before `serve_lm_state.run` runs, because
they name what this block does not have or lack what it needs:

  `serve_lm_state.make_weights` (its lines 51 to 105)  there a Mamba-2
        mixer's arrays and an embedding at sigma 0.02 / 12; here
        `serve_lm_latent.make_weights` (its lines 54 to 86: normal at
        sigma 0.02, the embedding at sigma 1, a committed key) and over
        it the draws the configuration's `assumed` names: `delta_a_log`
        = log U(1, 16), `delta_dt` so that a channel's decay a step
        spreads over (0.9, 0.999) under the bounded gate, `delta_conv`
        uniform in +-1/2, the latent layer's `o_proj` at `LATENT_OUT_GAIN`
        times sigma (the mean of hundreds of random value rows is a
        fortieth of a delta layer's output: at 0.02 no wrong scale,
        rotation or gate on the ONE latent layer could show in the
        logits), and every `router_bias.b_0` float32 ZEROS (a second
        dtype would be a second compile of both steps and of the
        reference), to be fitted.
  `serve_lm_state.system_outputs` (its lines 108 to 140)  there lane 0's
        SSM states are read; here the same walk on the ONE pool shape of
        the walks before the window (`serve_lm_latent.walk_blocks`), and
        lane 0's delta states, its tails and the latent rows its table
        blocks hold are read after the last position, under "state",
        "tails" and "latent", for the reference's three `*_rms_err`.
  `serve_lm_ring.check_against_reference` (which `serve_lm_state.run`
        hands to `serve_lm_closed.build_server`)  kept, and run after
        `balance` has fitted every layer's choice bias, under
        `serve_lm_latent.warm_reference`'s threads.

`balance` is `serve_lm_balanced.balance` (its lines 107 to 144) with
three differences, which is why it is written out: the walk's pools are
made with `lanes` and ONE table (`router_scores`, for its lines 70 to
104); the loads that the sign rule evens are those of the router AS IT
CHOOSES, under the group limit (`chosen`, for the plain top-k of its
`fit`, lines 47 to 67: a bias fitted to the plain top-k of 512 would
leave the group-limited loads uneven, and a checkpoint's bias is trained
on the router it has), counted by argmax passes and not by `top_k` (a
sort on a TPU: 12 fits of 400 steps were 45 s of every run's set-up);
and the last pass fits on `LAST_WALKS` walks (`serve_lm_latent_bias`'s
lesson).
"""
from __future__ import annotations

import os

import numpy as np

import common

_HERE = os.path.dirname(os.path.abspath(__file__))
state = common.load_module(os.path.join(_HERE, "serve_lm_state.py"))
latent = common.load_module(os.path.join(_HERE, "serve_lm_latent.py"))
balanced = common.load_module(os.path.join(_HERE, "serve_lm_balanced.py"))
_compare = state.ring.check_against_reference

# a channel's decay a step at a zero projection, drawn uniform in this
# range (the configuration's `assumed.a_log_dt_bias`)
DECAY = (0.9, 0.999)
# the latent layer's output matrix over sigma 0.02 (`assumed.weights`)
LATENT_OUT_GAIN = 8.0
# walks of the fit's last pass (`serve_lm_latent_bias`'s lesson: the
# sample of the last fit is what keeps seeds' `itl_p95_ms` together)
LAST_WALKS = 4


def make_weights(shapes: dict, seed: int, dtype, floor: float):
    """`serve_lm_latent.make_weights`, and over it the draws `assumed`
    names, small arrays made on the host from the same seed and
    committed to the device like the others; `floor`: the log decay's
    lower bound (`kda_lower_bound`), under which `delta_dt` is solved
    for the drawn decay."""
    import jax
    import jax.numpy as jnp

    g = latent.make_weights(shapes, seed, dtype)
    rng = np.random.default_rng([common.seed31(seed), 0x11A6])
    device = jax.devices()[0]
    for name in sorted(shapes):
        shape = tuple(shapes[name])
        kind = dtype
        if name.endswith("delta_conv.w_0"):
            v = rng.uniform(-0.5, 0.5, shape)
        elif name.endswith("delta_a_log.w_0"):
            v = np.log(rng.uniform(1.0, 16.0, shape))
        elif name.endswith("router_bias.b_0"):
            v, kind = np.zeros(shape), jnp.float32
        elif name.endswith(".o_proj.w_0"):
            # a power of two: exact in the weights' dtype
            g[name] = (g[name] * LATENT_OUT_GAIN).astype(dtype)
            continue
        else:
            continue
        g[name] = jax.device_put(jnp.asarray(v, jnp.float32).astype(kind),
                                 device)
    for name in sorted(shapes):
        if not name.endswith("delta_dt.b_0"):
            continue
        # floor * sigmoid(exp(A_log) * dt) = ln(decay), a head's A_log as
        # the device holds it
        a_log = np.asarray(g[name.replace("delta_dt.b_0",
                                          "delta_a_log.w_0")], np.float64)
        share = np.log(rng.uniform(*DECAY, (len(a_log), shapes[name][0]
                                            // len(a_log)))) / floor
        v = (np.log(share / (1.0 - share)) / np.exp(a_log)[:, None])
        g[name] = jax.device_put(
            jnp.asarray(v.reshape(-1), jnp.float32).astype(dtype), device)
    return g


def _pools(dec, slots: int, blocks: int):
    import jax

    return dec.init_pool(blocks + 1, jax.devices()[0], lanes=slots)


def system_outputs(dec, g, toks, slots: int):
    """`serve_lm_state.system_outputs` on the walks' one pool shape:
    `toks` through the step AS THE SERVER RUNS IT, `slots` lanes, the
    sequence in lane 0 from position 0 and the other lanes idle.  ->
    ([positions, vocab] logits, the routing of every position stacked
    on axis 1, and under "state" and "tails" lane 0's after the last
    position [delta layers, ...], under "latent" the rows its table
    blocks hold [latent layers, positions, latent + rope])."""
    n = len(toks)
    need = -(-n // dec.block_size)
    pool_k, pool_v = _pools(dec, slots, latent.walk_blocks(dec, slots, n))
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
    width = next(s[1] for name, s in dec.state_shapes.items()
                 if name.endswith("kv_a_proj.w_0"))
    rows = np.asarray(pool_k[0][:, 1:need + 1].astype(np.float32))
    return np.concatenate([np.asarray(x) for x in got]), {
        "state": np.stack([np.asarray(h[0]) for h in pool_k[1]]),
        "tails": np.stack([np.asarray(t[0]) for t in pool_v[1]]),
        "latent": rows.reshape(rows.shape[0], -1, rows.shape[-1])[
            :, :n, :width],
        **{k: np.concatenate([np.asarray(r[k]) for r in routed], 1)
           for k in routed[0]}}


def router_scores(dec, g, routers, toks, slots: int, blocks: int):
    """`serve_lm_balanced.router_scores` with the lanes' states in the
    pools and one table: `toks` [positions, slots] through every lane of
    the served step -> the router's scores of every layer with experts,
    sigmoid(its input @ its matrix) in float32, [layers, positions x
    slots, E]."""
    import jax
    import jax.numpy as jnp

    n = len(toks)
    need = -(-n // dec.block_size)
    pool_k, pool_v = _pools(dec, slots, blocks)
    tables = np.zeros((slots, dec.max_blocks_per_seq), np.int32)
    tables[:, :need] = 1 + np.arange(slots * need).reshape(slots, need)
    zs, zt = np.zeros(slots, np.uint32), np.zeros(slots, np.float32)
    act = np.ones(slots, bool)
    w = jnp.stack([g[name] for name in routers]).astype(jnp.float32)

    @jax.jit
    def scores_of(inputs):
        return jax.nn.sigmoid(jnp.einsum(
            "lsd,lde->lse", inputs, w,
            precision=jax.lax.Precision.HIGHEST))

    seen = []
    for pos in range(n):
        args = (g, pool_k, pool_v, tables, np.full(slots, pos, np.int32),
                toks[pos], zs, zt, act)
        seen.append(scores_of(dec.step_routing(*args)[1]["inputs"]))
        _, pool_k, pool_v, *_ = dec.step(*args)
    return jnp.concatenate(seen, axis=1)


def largest(x, k: int):
    """bool mask of the `k` largest of x [T, n] along its last axis, a
    tie to the lower index (`jax.lax.top_k`'s rule), by `k` argmax
    passes: the fit runs it 400 times a layer a pass, and on a TPU a
    `top_k` over [8192, 512] is a sort (11 ms a step of the sign rule
    where this reads under 2: my chip runs, PR 62)."""
    import jax.numpy as jnp

    mask = jnp.zeros(x.shape, bool)
    cols = jnp.arange(x.shape[-1])
    for _ in range(k):
        at = jnp.argmax(jnp.where(mask, -jnp.inf, x), axis=-1)
        mask = mask | (cols == at[..., None])
    return mask


def chosen(scores, bias, k: int, n_group: int, topk_group: int):
    """The experts the router picks, as a bool mask [T, E], from its
    scores [T, E] under a choice bias [E] and the group limit: c =
    scores + bias, a group's score the sum of its two largest c, the
    `topk_group` best groups kept, the k largest c among their experts
    (`lm_block.route`'s choice under `group_score: "top2_sum"`, which a
    test holds it to)."""
    import jax.numpy as jnp

    c = scores + bias.astype(jnp.float32)
    grouped = c.reshape(c.shape[0], n_group, -1)
    group = jnp.where(largest(grouped, 2), grouped, 0.0).sum(-1)
    keep = largest(group, topk_group)
    return largest(jnp.where(keep[..., None], grouped,
                             -jnp.inf).reshape(c.shape), k)


def fit(scores, k: int, n_group: int, topk_group: int, dtype):
    """`serve_lm_balanced.fit`'s sign rule (a step down where an expert
    has more than the mean load, up where fewer, the step shrinking
    geometrically from `FIT_FIRST` to `FIT_LAST` over `FIT_STEPS`) on
    the loads of `chosen`, the router as it chooses."""
    import jax
    import jax.numpy as jnp

    e_n = scores.shape[-1]
    mean = scores.shape[0] * k / e_n
    ratio = (balanced.FIT_LAST / balanced.FIT_FIRST) ** (
        1.0 / (balanced.FIT_STEPS - 1))

    def step(i, bias):
        load = chosen(scores, bias, k, n_group, topk_group).sum(
            0, dtype=jnp.float32)
        return (bias.astype(jnp.float32) - balanced.FIT_FIRST * ratio ** i
                * jnp.sign(load - mean)).astype(dtype)

    return jax.lax.fori_loop(0, balanced.FIT_STEPS, step,
                             jnp.zeros(e_n, dtype))


def balance(cell, dec, g, n_tokens: int) -> dict:
    """Fit every layer's choice bias in `g`, in place,
    `serve_lm_balanced.FIT_PASSES` passes over, the LAST on `LAST_WALKS`
    walks of other tokens (each from position 0 in the same blocks: one
    pool shape).  -> what the fit did to the loads, for the run's notes:
    the largest expert's load over the mean, a layer, under the zero
    bias and under the fitted one as the LAST pass found it (before its
    own fit)."""
    import jax
    import jax.numpy as jnp

    m, slots = cell.config, int(cell.traffic["slots"])
    k, groups = int(m["num_experts_per_tok"]), (int(m["n_group"]),
                                                int(m["topk_group"]))
    rng = np.random.default_rng([common.seed31(cell.seed), 0xB1A5])
    passes = balanced.FIT_PASSES
    toks = rng.integers(
        0, m["vocab_size"], (passes - 1 + LAST_WALKS,
                             balanced.FIT_POSITIONS, slots)).astype(np.int32)
    names = sorted((n for n in g if n.endswith("router_bias.b_0")),
                   key=lambda n: int(n.split(".")[0].split("_")[1]))
    routers = [n.replace("router_bias.b_0", "router.w_0") for n in names]
    fit_ = jax.jit(fit, static_argnums=(1, 2, 3, 4))
    blocks = latent.walk_blocks(dec, slots, n_tokens)

    @jax.jit
    def worst(scores, bias):
        load = chosen(scores, bias, k, *groups).sum(0, dtype=jnp.float32)
        return load.max() / load.mean()

    found = []
    for i in range(passes):
        walks = toks[i:] if i == passes - 1 else toks[i:i + 1]
        scores = jnp.concatenate(
            [router_scores(dec, g, routers, w, slots, blocks)
             for w in walks], axis=1)
        found.append([worst(s, g[name]) for s, name in zip(scores, names)])
        for s, name in zip(scores, names):
            g[name] = fit_(s, k, *groups, g[name].dtype)
    return {"tokens": int(toks[passes - 1:].size), "layers": len(names),
            "passes": passes, "last_walks": LAST_WALKS,
            "max_load_over_mean_seeded":
                [round(float(x), 3) for x in found[0]],
            "max_load_over_mean_fitted":
                [round(float(x), 3) for x in found[-1]]}


def check_against_reference(cell, dec, g, n_tokens: int):
    """`serve_lm_ring.check_against_reference` over weights whose choice
    biases `balance` has fitted first, the reference's two passes
    compiling on threads under the fit's walks: `g` is the dict
    `build_server` goes on to serve, so the server holds what was
    compared."""
    warming = latent.warm_reference(cell, dict(g), n_tokens)
    fitted = balance(cell, dec, g, n_tokens)
    for t in warming:
        t.join()
    cell.mark("choice bias fitted")
    out = _compare(cell, dec, g, n_tokens)
    out["balance"] = fitted
    return out


def run(cell):
    floor = float(cell.config["kda_lower_bound"])
    state.make_weights = lambda shapes, seed, dtype: make_weights(
        shapes, seed, dtype, floor)
    state.system_outputs = system_outputs
    state.ring.check_against_reference = check_against_reference
    return state.run(cell)
