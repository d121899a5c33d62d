"""Job `serve_lm_conv`: `serve_lm_state` for a block whose lanes keep a
convolution TAIL and no recurrent state (`BlockSpec.layer_types`
"conv": a gated short convolution): the same clients, load, ramp,
window, accounting and both comparisons (`serve_lm_state.run`, which
this calls), imported from `serve_lm_state`, `serve_lm_closed` and
`serve_lm_ring` unedited.  Two functions of `serve_lm_state` are
replaced in its module before it runs, because they name what this
block does not have:

  `make_weights` (its lines 51 to 105)  there the arrays of a Mamba-2
        mixer and an embedding at sigma 0.02 / 12 for Granite's
        multipliers; here `serve_lm_ring.make_weights`' draws (normal,
        sigma 0.02, the embedding too) but for the arrays the
        configuration's `assumed` names: `conv.w_0` uniform in
        +-1/sqrt(3), `router_bias.b_0` normal at `BIAS_SIGMA`, and the
        head norms' scales `q_norm.scale_0` and `k_norm.scale_0`
        log-uniform in 1/2 to 2.
  `system_outputs` (its lines 108 to 140)  there lane 0's SSM states
        are read from beside the K pool, where this block keeps
        nothing; here lane 0's TAILS are read from beside the V pool
        after the walk, under "tails", for the reference's
        `tail_rms_err`.

Beside `compare`'s numbers the run notes `bias_moved_choice_share`:
the share of the walk's (position, sparse layer) pairs whose chosen set
of experts the seeded choice bias changed, on the router's own inputs
(`assumed.expert_bias` wants it between a tenth and a third).
"""
from __future__ import annotations

import os

import numpy as np

import common

state = common.load_module(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "serve_lm_state.py"))

# the seeded choice bias's sigma (the configuration's
# `assumed.expert_bias`)
BIAS_SIGMA = 0.0075


def make_weights(shapes: dict, seed: int, dtype):
    """`serve_lm_ring.make_weights`, and over it the draws `assumed`
    names, small arrays made on the host from the same seed: the
    convolution's taps uniform in +-1/sqrt(3), the router's choice bias
    normal at `BIAS_SIGMA`, the head norms' scales log-uniform in 1/2
    to 2 (a rotation keeps a head's mean square, so scales of 1 + noise
    before RoPE and after it are the same numbers and a norm on the
    wrong side could not show)."""
    import jax
    import jax.numpy as jnp

    g = state.ring.make_weights(shapes, seed, dtype)
    rng = np.random.default_rng([common.seed31(seed), 0xC0F])
    for name in sorted(shapes):
        shape = tuple(shapes[name])
        if name.endswith(".conv.w_0"):
            v = rng.uniform(-1.0, 1.0, shape) / np.sqrt(shape[0])
        elif name.endswith("router_bias.b_0"):
            v = BIAS_SIGMA * rng.standard_normal(shape)
        elif name.endswith(("q_norm.scale_0", "k_norm.scale_0")):
            v = np.exp(rng.uniform(-np.log(2.0), np.log(2.0), shape))
        else:
            continue
        g[name] = jax.device_put(jnp.asarray(v, jnp.float32).astype(dtype),
                                 jax.devices()[0])
    return g


def system_outputs(dec, g, toks, slots: int):
    """`serve_lm_state.system_outputs` for a step whose lanes keep
    tails alone: `toks` through the step AS THE SERVER RUNS IT, `slots`
    lanes, the sequence in lane 0 from position 0 (where the step
    starts the lane's tails from zero) and the other lanes idle.
    -> ([positions, vocab] logits, the routing of every position
    stacked on axis 1, and under "tails" lane 0's tails after the last
    position, [conv layers, taps - 1, D])."""
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
        "tails": np.stack([np.asarray(t[0]) for t in pool_v[1]]),
        **{k: np.concatenate([np.asarray(r[k]) for r in routed], 1)
           for k in routed[0]}}


def bias_moved_choice_share(g, routing, top_k: int) -> float:
    """Share of the (sparse layer, position) pairs of a walk whose
    `top_k` largest of sigmoid(input @ router) are not its `top_k`
    largest of that plus the layer's choice bias: float64 on the host,
    over the router's own inputs."""
    names = sorted((n for n in g if n.endswith("router_bias.b_0")),
                   key=lambda n: int(n.split(".")[0].split("_")[1]))
    moved = []
    for i, name in enumerate(names):
        w = np.asarray(g[name.replace("router_bias.b_0", "router.w_0")],
                       np.float64)
        s = 1.0 / (1.0 + np.exp(-np.asarray(routing["inputs"][i],
                                            np.float64) @ w))
        with_bias = s + np.asarray(g[name], np.float64)
        plain, biased = (np.sort(np.argsort(-x, -1)[:, :top_k], -1)
                         for x in (s, with_bias))
        moved.append((plain != biased).any(-1))
    return float(np.mean(moved))


def run(cell):
    seen = {}

    def outputs(dec, g, toks, slots):
        logits, routing = system_outputs(dec, g, toks, slots)
        seen["bias_moved_choice_share"] = bias_moved_choice_share(
            g, routing, int(cell.config["num_experts_per_tok"]))
        return logits, routing

    state.make_weights = make_weights
    state.system_outputs = outputs
    run_ = state.run(cell)
    run_.notes["reference"].update(seen)
    return run_
